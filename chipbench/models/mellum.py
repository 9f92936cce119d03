"""Mellum 2 (``model_type`` ``mellum``): the plain reference, the on-device
weights and the needed work.

Source: ``huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct``
``config.json``. This file shares no code with ``tensorframes_tpu/``; it
follows the layer equations the config's keys give. With ``h`` the
residual stream, per layer:

- ``x = RMSNorm(h; g1, eps)``, ``q = x Wq`` (hidden -> heads x head_dim),
  ``k = x Wk``, ``v = x Wv`` (hidden -> kv_heads x head_dim), no bias;
  rotary on q and k in the rotate-half pairing ``(x_i, x_{i + hd/2})``;
- sliding layers: ``inv_freq_i = theta ** (-2i / hd)``; key ``j`` is visible
  to query ``p`` iff ``p - window < j <= p``;
- full layers (YaRN): ``extrap_i = theta ** (-2i / hd)``, ``interp_i =
  extrap_i / factor``, ``low = floor(hd ln(orig / (beta_fast 2 pi)) / (2 ln
  theta))``, ``high = ceil(hd ln(orig / (beta_slow 2 pi)) / (2 ln theta))``
  (18 and 35 at the published values), ``r_i = clip((i - low) / (high -
  low), 0, 1)``, ``inv_freq_i = interp_i r_i + extrap_i (1 - r_i)``; cos and
  sin are multiplied by ``attention_factor``; causal, no window;
- scores ``q k^T / sqrt(hd)``, softmax in float32, each K/V head serves
  ``heads / kv_heads`` query heads; ``h <- h + a Wo``;
- ``y = RMSNorm(h; g2)``, ``p = softmax(y Wr)`` over all experts in
  float32, the ``num_experts_per_tok`` largest renormalised to sum 1,
  ``h <- h + sum_e p_e Wdown_e(SiLU(Wgate_e y) * (Wup_e y))``;
- final RMSNorm, untied head.

Departures, all listed under ``assumed`` in the configuration file: no
per-head q/k normalisation (the config names none); no multi-token
prediction head (no key of the config describes one); random weights from
the seed (embedding and head N(0, 0.02), projections N(0, 1/fan_in), gains
1); the served length cap ``n_positions``.

Precision. The weights and the cache are bfloat16 (what the configuration
states); ``reference_logits`` reads them three ways:

- ``"float32"``: weights upcast, every product at ``highest``, nothing
  rounded on the way;
- ``"default"``: the stated precision — the operand of every weight product
  and K, V and the softmax's output rounded to bfloat16, accumulation,
  norms, softmax, router and residual in float32;
- ``"bfloat16"``: the name under which the driver asks for the control,
  which for this family is one precision BELOW what it states: weights and
  cache rounded to ``float8_e4m3fn``, everything else as ``"default"``.

The reference walks one sequence at a time, a layer per jitted call, the
queries of a layer in blocks, the experts one after another over every
token (no routing tables, no cache, no paging, no kernels).
"""

import functools
import math

import numpy as np

_Q_BLOCK = 128  # query rows scored at a time
_HEAD_BLOCK = 128  # positions the output head is asked for at a time
_BUCKETS = (1024, 2048, 4096, 8192)  # a sequence is padded up to one, or whole


# ------------------------------------------------------------ needed work


def _dims(cfg):
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return d, q, kv, cfg["moe_intermediate_size"]


def layer_types(cfg):
    return list(cfg["layer_types"][: cfg["num_hidden_layers"]])


def attention_params(cfg):
    d, q, kv, _ = _dims(cfg)
    return d * q + 2 * d * kv + q * d


def expert_params(cfg):
    d, _, _, f = _dims(cfg)
    return 3 * d * f


def layer_params(cfg):
    """Weights of one layer: projections, router, every expert, the two
    norm gains."""
    d = cfg["hidden_size"]
    return (
        attention_params(cfg) + d * cfg["num_experts"]
        + cfg["num_experts"] * expert_params(cfg) + 2 * d
    )


def param_count(cfg):
    """Parameters of the model as this file builds it."""
    d = cfg["hidden_size"]
    return (
        2 * cfg["vocab_size"] * d
        + cfg["num_hidden_layers"] * layer_params(cfg) + d
    )


def kv_bytes_per_token(cfg, itemsize=2):
    """K and V of one token over all layers, were every layer to keep it."""
    _, _, kv, _ = _dims(cfg)
    return 2 * cfg["num_hidden_layers"] * kv * itemsize


def head_flops(cfg):
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def token_weight_flops(cfg):
    """FLOPs of one token's weight products in one layer: the projections,
    the router and the experts it is routed to (not every expert)."""
    d = cfg["hidden_size"]
    return 2 * (
        attention_params(cfg) + d * cfg["num_experts"]
        + cfg["num_experts_per_tok"] * expert_params(cfg)
    )


def _visible(start, stop, window):
    """Sum over positions ``start <= p < stop`` of the keys each sees:
    ``p + 1``, or ``min(p + 1, window)`` under a window."""
    n = stop - start
    total = n * (start + 1 + stop) // 2
    if window and stop > window:
        lo = max(start, window)  # positions whose p + 1 exceeds the window
        m = stop - lo
        total -= m * (lo + 1 + stop) // 2 - m * window
    return total


def sequence_flops(cfg, start, stop, heads):
    """FLOPs for the tokens at positions ``start <= p < stop`` of one
    sequence plus ``heads`` evaluations of the output head: each token's
    weight products (its own experts only) and its score and value products
    over the keys it sees (``min(p + 1, window)`` in a sliding layer)."""
    _, q, _, _ = _dims(cfg)
    n = stop - start
    total = heads * head_flops(cfg)
    for kind in layer_types(cfg):
        window = cfg["sliding_window"] if kind == "sliding_attention" else 0
        total += token_weight_flops(cfg) * n + 4 * q * _visible(
            start, stop, window
        )
    return total


def _least_weight_bytes(cfg, itemsize=2):
    """Weights no step can avoid reading: every layer's projections and
    router, as many experts a layer as ONE token touches (a step with more
    tokens touches more, up to all of them; the count handed to these
    functions does not say how many tokens there were, so the floor takes
    the fewest), the output head, the final gain."""
    d = cfg["hidden_size"]
    per_layer = (
        attention_params(cfg) + d * cfg["num_experts"] + 2 * d
        + cfg["num_experts_per_tok"] * expert_params(cfg)
    )
    return itemsize * (
        cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"] + d
    )


def _least_kv_read_bytes(cfg, positions, itemsize=2):
    """Least K and V bytes read to attend over ``positions`` live positions
    in all, however they are split over sequences: a full layer reads every
    one; a sliding layer at least ``min(positions, window)`` (all of them in
    one sequence, which reads one window)."""
    _, _, kv, _ = _dims(cfg)
    total = 0
    for kind in layer_types(cfg):
        seen = positions
        if kind == "sliding_attention":
            seen = min(positions, cfg["sliding_window"])
        total += 2 * kv * itemsize * seen
    return total


def decode_step_bytes(cfg, contexts, itemsize=2):
    """Least bytes one decode step moves (``contexts``: positions visible to
    the slots in the step; the driver hands one number, their mean total)."""
    return _least_weight_bytes(cfg, itemsize) + _least_kv_read_bytes(
        cfg, int(sum(contexts)), itemsize
    )


def prefill_bytes(cfg, prompt_len, itemsize=2):
    """Least bytes the prefill of one prompt moves: the weights once and
    the prompt's K and V written once in every layer."""
    return _least_weight_bytes(cfg, itemsize) + kv_bytes_per_token(
        cfg, itemsize
    ) * int(prompt_len)


def chunk_bytes(cfg, start, tokens, itemsize=2):
    """Least bytes one prefill chunk of ``tokens`` positions from ``start``
    moves: the weights once, the chunk's K and V written once, the K and V
    before it that its first query still sees read once."""
    before = 0
    _, _, kv, _ = _dims(cfg)
    for kind in layer_types(cfg):
        seen = start
        if kind == "sliding_attention":
            seen = min(start, cfg["sliding_window"] - 1)
        before += 2 * kv * itemsize * seen
    return (
        _least_weight_bytes(cfg, itemsize) + before
        + kv_bytes_per_token(cfg, itemsize) * int(tokens)
    )


def grouped_products(cfg, pairs, experts_hit, itemsize=2):
    """``[(FLOPs, bytes), ...]`` of the three grouped products of one
    expert layer (gate, up, down) over ``pairs`` token-expert pairs that
    touch ``experts_hit`` experts: two FLOPs per multiply-add of every
    pair's row, each touched expert's matrix read once, the rows read at
    the weights' width and the results written in float32."""
    d, _, _, f = _dims(cfg)
    hit = min(int(round(experts_hit)), cfg["num_experts"], int(pairs))

    def product(k, n):
        return (
            2 * pairs * k * n,
            hit * k * n * itemsize + pairs * k * itemsize + pairs * n * 4,
        )

    return [product(d, f), product(d, f), product(f, d)]


# ---------------------------------------------------------------- weights


def model_description(cfg):
    """The description the serving engine reads beside the weights: plain
    data, in the field names of its ``ModelSpec``."""
    names = {"sliding_attention": "window", "full_attention": "full"}
    rope = {}
    for kind, r in cfg["rope_parameters"].items():
        out = {"theta": float(r["rope_theta"])}
        if r["rope_type"] == "yarn":
            out.update(
                kind="yarn", factor=float(r["factor"]),
                original_max_position=int(
                    r["original_max_position_embeddings"]
                ),
                beta_fast=float(r["beta_fast"]),
                beta_slow=float(r["beta_slow"]),
                attention_factor=float(r["attention_factor"]),
            )
        rope[names[kind]] = out
    return {
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "max_len": cfg["n_positions"],
        "norm": "rms", "norm_eps": cfg["rms_norm_eps"],
        "position": "rotary",
        "layer_types": [names[t] for t in layer_types(cfg)],
        "window": cfg["sliding_window"],
        "rope": rope,
        "mlp": "gated_experts",
        "n_experts": cfg["num_experts"],
        "experts_per_token": cfg["num_experts_per_tok"],
        "tied_head": bool(cfg["tie_word_embeddings"]),
        "residual_dtype": "float32",
    }


def init_params(seed, cfg, dtype="bfloat16"):
    """The params tree ``GenerationEngine`` takes, made on the device, with
    the model description in it."""
    import jax
    import jax.numpy as jnp

    d, q, kv, f = _dims(cfg)
    e, vocab = cfg["num_experts"], cfg["vocab_size"]
    dt = jnp.dtype(dtype)
    key = jax.random.PRNGKey(np.uint32(int(seed) % (2**32)))

    def normal(k, shape, std):
        return jax.random.normal(k, shape, dt) * jnp.asarray(std, dt)

    @jax.jit
    def tables(k):
        k1, k2 = jax.random.split(k)
        return normal(k1, (vocab, d), 0.02), normal(k2, (d, vocab), 0.02)

    @jax.jit
    def block(k):
        ks = jax.random.split(k, 6)
        gain = lambda: {"g": jnp.ones((d,), dt)}
        return {
            "ln1": gain(),
            "qkv": normal(ks[0], (d, q + 2 * kv), d**-0.5),
            "proj": normal(ks[1], (q, d), q**-0.5),
            "ln2": gain(),
            "moe": {
                "router": normal(ks[2], (d, e), d**-0.5),
                "w_gate": normal(ks[3], (e, d, f), d**-0.5),
                "w_up": normal(ks[4], (e, d, f), d**-0.5),
                "w_down": normal(ks[5], (e, f, d), f**-0.5),
            },
        }

    embed, head = tables(jax.random.fold_in(key, 0))
    return {
        "embed": embed, "head": head,
        "blocks": [
            block(jax.random.fold_in(key, 1 + li))
            for li in range(cfg["num_hidden_layers"])
        ],
        "ln_f": {"g": jnp.ones((d,), dt)},
        "spec": model_description(cfg),
    }


# -------------------------------------------------------------- reference


def inv_freq(cfg, kind):
    """``(inverse frequencies [head_dim / 2] float32, the factor on cos and
    sin)`` of one layer type, by the equations above."""
    r = cfg["rope_parameters"][kind]
    hd = cfg["head_dim"]
    i = np.arange(hd // 2, dtype=np.float64)
    extrap = float(r["rope_theta"]) ** (-2.0 * i / hd)
    if r["rope_type"] == "default":
        return extrap.astype(np.float32), 1.0
    low, high = yarn_range(cfg, kind)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    interp = extrap / float(r["factor"])
    mixed = interp * ramp + extrap * (1.0 - ramp)
    return mixed.astype(np.float32), float(r["attention_factor"])


def yarn_range(cfg, kind="full_attention"):
    """The first and last dimension of the YaRN ramp (18 and 35)."""
    r = cfg["rope_parameters"][kind]
    hd, theta = cfg["head_dim"], float(r["rope_theta"])
    orig = r["original_max_position_embeddings"]

    def dim(turns):
        return hd * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(theta)
        )

    return (
        max(math.floor(dim(r["beta_fast"])), 0),
        min(math.ceil(dim(r["beta_slow"])), hd - 1),
    )


@functools.lru_cache(maxsize=None)
def _programs(precision, n_heads, n_kv, hd, top_k, eps):
    """The reference's jitted pieces for one precision."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    exact = precision == "float32"
    prec = "highest" if exact else None
    control = precision == "bfloat16"

    def weight(w):  # what the product sees of a stored weight
        if control:
            w = w.astype(jnp.float8_e4m3fn)
        return w.astype(f32) if exact else w.astype(jnp.bfloat16)

    def operand(x):  # what the product sees of an activation
        return x if exact else x.astype(jnp.bfloat16)

    def cached(x):  # what attention sees of K and V
        if control:
            x = x.astype(jnp.bfloat16).astype(jnp.float8_e4m3fn)
        return x if exact else x.astype(jnp.bfloat16)

    def mm(x, w):
        return jnp.matmul(
            operand(x), weight(w), precision=prec, preferred_element_type=f32
        )

    def rms(x, g):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * g.astype(f32)

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(f32)

    @jax.jit
    def layer(w, h, freqs, factor, window):
        n, d = h.shape
        group = n_heads // n_kv
        x = rms(h, w["ln1"]["g"])
        qkv = mm(x, w["qkv"])
        q, k, v = jnp.split(qkv, [n_heads * hd, (n_heads + n_kv) * hd], -1)
        ang = jnp.arange(n, dtype=f32)[:, None] * freqs[None, :]
        cos = jnp.concatenate([jnp.cos(ang)] * 2, -1) * factor
        sin = jnp.concatenate([jnp.sin(ang)] * 2, -1) * factor

        def rope(t):  # [n, heads, hd]
            rot = jnp.concatenate([-t[..., hd // 2 :], t[..., : hd // 2]], -1)
            return t * cos[:, None, :] + rot * sin[:, None, :]

        q = rope(q.reshape(n, n_heads, hd)).reshape(n, n_kv, group, hd)
        k = cached(rope(k.reshape(n, n_kv, hd)))
        v = cached(v.reshape(n, n_kv, hd))
        keys = jnp.arange(n)

        def scored(q_blk, at):  # [B, n_kv, group, hd], first row's position
            pos = at + jnp.arange(q_blk.shape[0])
            s = jnp.einsum(
                "qkgd,tkd->kgqt", operand(q_blk), k, precision=prec,
                preferred_element_type=f32,
            ) / math.sqrt(hd)
            seen = (keys[None, :] <= pos[:, None]) & (
                keys[None, :] > pos[:, None] - window
            )
            p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
            a = jnp.einsum(
                "kgqt,tkd->qkgd", operand(p), v, precision=prec,
                preferred_element_type=f32,
            )
            return a.reshape(q_blk.shape[0], n_heads * hd)

        blocks = n // _Q_BLOCK
        att = jax.lax.map(
            lambda i: scored(
                jax.lax.dynamic_slice_in_dim(q, i * _Q_BLOCK, _Q_BLOCK, 0),
                i * _Q_BLOCK,
            ),
            jnp.arange(blocks),
        ).reshape(n, n_heads * hd)
        h = h + mm(att, w["proj"])

        y = rms(h, w["ln2"]["g"])
        e = w["moe"]
        logits = jnp.matmul(
            y, e["router"].astype(f32), precision="highest"
        )
        probs = jax.nn.softmax(logits, axis=-1)
        top, ids = jax.lax.top_k(probs, top_k)
        top = top / jnp.sum(top, axis=-1, keepdims=True)
        # each expert over every token, weighted by the gate of the tokens
        # that chose it and nought for the others
        gate_of = jnp.zeros_like(probs).at[
            jnp.arange(n)[:, None], ids
        ].set(top)

        def one(acc, xs):
            wg, wu, wd, g = xs
            out = mm(jax.nn.silu(mm(y, wg)) * mm(y, wu), wd)
            return acc + out * g[:, None], None

        moe, _ = jax.lax.scan(
            one, jnp.zeros_like(h),
            (e["w_gate"], e["w_up"], e["w_down"], gate_of.T),
        )
        return h + moe

    @jax.jit
    def head(g, table, h, cols):
        return mm(rms(h[cols], g), table)

    return embed, layer, head


def reference_logits(params, cfg, tokens, rows, cols, precision="float32"):
    """Logits after ``tokens[rows[i], : cols[i] + 1]`` for every ``i``.

    ``tokens`` is ``[batch, length]`` int32, zero-padded on the right;
    causal attention makes a row's padding invisible to its real positions.
    Each row is one forward pass over the whole sequence, cut to the
    positions asked for and padded to one of a few lengths: no cache, no
    paging, no batching across rows or steps."""
    import jax.numpy as jnp

    embed, layer, head = _programs(
        precision, cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], cfg["num_experts_per_tok"], cfg["rms_norm_eps"],
    )
    tokens = np.asarray(tokens)
    rows, cols = np.asarray(rows), np.asarray(cols)
    width = tokens.shape[1]
    kinds = layer_types(cfg)
    tables = {
        kind: (jnp.asarray(inv_freq(cfg, kind)[0]), inv_freq(cfg, kind)[1])
        for kind in set(kinds)
    }
    parts, order = [], []
    for r in np.unique(rows):
        mine = np.nonzero(rows == r)[0]
        need = int(cols[mine].max()) + 1
        n = next((b for b in _BUCKETS if need <= b <= width), width)
        n = -(-n // _Q_BLOCK) * _Q_BLOCK
        row = np.zeros(n, np.int32)
        row[: min(n, width)] = tokens[r, : min(n, width)]
        h = embed(params["embed"], jnp.asarray(row))
        for kind, w in zip(kinds, params["blocks"]):
            window = cfg["sliding_window"] if kind == "sliding_attention" else n
            freqs, factor = tables[kind]
            h = layer(
                w, h, freqs, jnp.float32(factor), jnp.int32(window)
            )
        asked = np.zeros(-(-len(mine) // _HEAD_BLOCK) * _HEAD_BLOCK, np.int32)
        asked[: len(mine)] = cols[mine]
        got = head(
            params["ln_f"]["g"], params["head"], h, jnp.asarray(asked)
        )
        parts.append(got[: len(mine)])
        order.append(mine)
    return jnp.concatenate(parts)[np.argsort(np.concatenate(order))]
