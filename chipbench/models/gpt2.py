"""GPT-2: the plain reference, the on-device weights and the needed work.

Source: Radford et al. 2019 and ``huggingface.co/openai-community/gpt2-xl``
``config.json``. This file shares no code with
``tensorframes_tpu/models/transformer.py``; it follows the published block
(pre-LayerNorm attention and MLP residuals, learned positions, tied head,
``gelu_new``). Departures from the checkpoint, all of them the repo block's
own and listed under ``assumed`` in the configuration file:

- no bias on the four projections (qkv, proj, up, down);
- LayerNorm epsilon 1e-5;
- random weights from the seed (embeddings N(0, 0.02), projections
  N(0, 1/fan_in), LayerNorm gain 1, bias 0), not the checkpoint's.

The weights are made on the device, one jitted program per kind of leaf,
in the tree ``init_transformer`` returns, so the engine takes them as a
params dict. The reference walks the layers one jitted block at a time at
``highest`` matmul precision; the control is the same walk in bfloat16.
"""

import functools
import math

import numpy as np


def param_count(cfg):
    """Parameters of the model as this file builds it (tied head once)."""
    d, ff = cfg["n_embd"], 4 * cfg["n_embd"]
    block = d * 3 * d + d * d + d * ff + ff * d + 4 * d
    return (
        cfg["vocab_size"] * d + cfg["n_positions"] * d
        + cfg["n_layer"] * block + 2 * d
    )


def kv_bytes_per_token(cfg, itemsize=4):
    """K and V of one token over all layers, unpadded."""
    return 2 * cfg["n_layer"] * cfg["n_embd"] * itemsize


def token_flops(cfg, context):
    """FLOPs one token needs when it attends to ``context`` positions
    (itself included): two per multiply-add of every projection, and of
    the score and value products over the context; no output head."""
    return sequence_flops(cfg, context - 1, context, 0)


def head_flops(cfg):
    return 2 * cfg["n_embd"] * cfg["vocab_size"]


def sequence_flops(cfg, start, stop, heads):
    """FLOPs for the tokens at positions ``start <= p < stop`` of one
    sequence, each attending to ``p + 1`` positions, plus ``heads``
    evaluations of the output head. A prefill of ``n`` tokens is
    ``(0, n, 1)``: only its last position's logits are needed."""
    n = stop - start
    contexts = n * (start + 1 + stop) // 2  # sum of (p + 1) over the span
    d, ff = cfg["n_embd"], 4 * cfg["n_embd"]
    dense = 2 * (d * 3 * d + d * d + 2 * d * ff)
    return (
        cfg["n_layer"] * (dense * n + 4 * d * contexts)
        + heads * head_flops(cfg)
    )


def weight_bytes(cfg, itemsize=4):
    return param_count(cfg) * itemsize


def decode_step_bytes(cfg, contexts, itemsize=4):
    """Least bytes one decode step moves: every weight once, and the live
    K and V of each slot in the step (``contexts``: positions visible to
    each slot, unpadded)."""
    return weight_bytes(cfg, itemsize) + kv_bytes_per_token(
        cfg, itemsize
    ) * int(sum(contexts))


def prefill_bytes(cfg, prompt_len, itemsize=4):
    """Least bytes one prefill moves: every weight once and the prompt's
    K and V written once."""
    return weight_bytes(cfg, itemsize) + kv_bytes_per_token(
        cfg, itemsize
    ) * int(prompt_len)


# ---------------------------------------------------------------- weights


def init_params(seed, cfg, dtype="float32"):
    """The params tree ``GenerationEngine`` takes, made on the device."""
    import jax
    import jax.numpy as jnp

    d, ff = cfg["n_embd"], 4 * cfg["n_embd"]
    dt = jnp.dtype(dtype)
    key = jax.random.PRNGKey(np.uint32(int(seed) % (2**32)))

    @jax.jit
    def tables(k):
        k1, k2 = jax.random.split(k)
        embed = 0.02 * jax.random.normal(k1, (cfg["vocab_size"], d), dt)
        pos = 0.02 * jax.random.normal(k2, (cfg["n_positions"], d), dt)
        return embed, pos

    @jax.jit
    def block(k):
        ks = jax.random.split(k, 4)

        def dense(kk, fan_in, fan_out):
            w = jax.random.normal(kk, (fan_in, fan_out), dt)
            return w * jnp.asarray(fan_in**-0.5, dt)

        ln = lambda: {"g": jnp.ones((d,), dt), "b": jnp.zeros((d,), dt)}
        return {
            "ln1": ln(), "qkv": dense(ks[0], d, 3 * d),
            "proj": dense(ks[1], d, d), "ln2": ln(),
            "up": dense(ks[2], d, ff), "down": dense(ks[3], ff, d),
        }

    embed, pos = tables(jax.random.fold_in(key, 0))
    return {
        "embed": embed, "pos": pos,
        "blocks": [
            block(jax.random.fold_in(key, 1 + li))
            for li in range(cfg["n_layer"])
        ],
        "ln_f": {"g": jnp.ones((d,), dt), "b": jnp.zeros((d,), dt)},
        "n_heads": cfg["n_head"],
    }


# -------------------------------------------------------------- reference


def _ln(x, p, eps):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def _gelu_new(x):
    import jax.numpy as jnp

    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x**3)))


@functools.lru_cache(maxsize=None)
def _programs(n_head, eps, dtype):
    """The reference's three jitted pieces for one precision: ``float32``
    computes every product at ``highest``; ``default`` is float32 with
    products at the chip's default precision (what the configuration
    states); ``bfloat16`` holds weights, activations and the residual in
    bfloat16 (the control)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype("float32" if dtype == "default" else dtype)
    prec = "highest" if dtype == "float32" else None
    cast = lambda tree: jax.tree.map(lambda a: a.astype(dt), tree)

    @jax.jit
    def embed(tables, tokens):
        e, p = cast(tables)
        return e[tokens] + p[: tokens.shape[1]][None]

    @jax.jit
    def block(w, h):
        w = cast(w)
        b, n, d = h.shape
        hd = d // n_head
        x = _ln(h, w["ln1"], eps)
        qkv = jnp.matmul(x, w["qkv"], precision=prec)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        heads = lambda t: t.reshape(b, n, n_head, hd).transpose(0, 2, 1, 3)
        q, k, v = heads(q), heads(k), heads(v)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=prec)
        s = s / jnp.asarray(math.sqrt(hd), dt)
        mask = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
        s = jnp.where(mask[None, None], s, jnp.finfo(dt).min)
        p = jax.nn.softmax(s, axis=-1)
        a = jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=prec)
        a = a.transpose(0, 2, 1, 3).reshape(b, n, d)
        h = h + jnp.matmul(a, w["proj"], precision=prec)
        x = _ln(h, w["ln2"], eps)
        up = _gelu_new(jnp.matmul(x, w["up"], precision=prec))
        return h + jnp.matmul(up, w["down"], precision=prec)

    @jax.jit
    def head(ln_f, table, h, rows, cols):
        picked = h[rows, cols]  # [n, d]
        x = _ln(picked, cast(ln_f), eps)
        logits = jnp.matmul(x, cast(table).T, precision=prec)
        return logits.astype(jnp.float32)

    return embed, block, head


def reference_logits(params, cfg, tokens, rows, cols, dtype="float32"):
    """Logits after ``tokens[rows[i], : cols[i] + 1]`` for every ``i``.

    ``tokens`` is ``[batch, length]`` int32, zero-padded on the right;
    causal attention makes a row's padding invisible to its real
    positions. One forward pass over whole sequences: no cache, no
    paging, no batching across steps."""
    import jax.numpy as jnp

    embed, block, head = _programs(
        cfg["n_head"], cfg["layer_norm_epsilon"], dtype
    )
    h = embed((params["embed"], params["pos"]), jnp.asarray(tokens))
    for w in params["blocks"]:
        h = block(w, h)
    return head(
        params["ln_f"], params["embed"], h, jnp.asarray(rows),
        jnp.asarray(cols),
    )
