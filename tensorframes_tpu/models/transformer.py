"""Decoder-only transformer LM on the framework's attention stack.

The reference's deepest model workload is scoring a frozen VGG/Inception
graph through the dataframe ops (``read_image.py:147-167``); this module is
the modern analog: a transformer whose attention runs on the Pallas flash
kernel single-chip (:func:`tensorframes_tpu.ops.flash_attention`) or on
ring attention across the ``sp`` mesh axis for long sequences
(:func:`tensorframes_tpu.ops.ring_attention`), and whose scoring dispatches
through ``map_blocks`` like any other captured program.

Architecture: learned positional embeddings, pre-LN blocks
(MHA -> residual, GELU MLP -> residual), final LN, tied output head.
All matmuls stay [tokens, d] x [d, d'] so XLA tiles them onto the MXU.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import numpy as np

__all__ = [
    "ModelSpec",
    "RopeSpec",
    "model_spec",
    "block_forward",
    "rope_inv_freq",
    "device_tree",
    "static_entries",
    "init_transformer",
    "init_draft_transformer",
    "transformer_logits",
    "transformer_generate",
    "transformer_step",
    "transformer_prefill",
    "transformer_prefill_chunk",
    "transformer_verify_chunk",
    "transformer_tp_specs",
    "gather_tp_params",
    "transformer_loss",
    "token_nll",
    "TransformerLM",
    "filter_logits",
    "left_pad_prompts",
]

Params = Dict[str, Any]


def init_transformer(
    seed: int,
    vocab: int,
    d_model: int = 64,
    n_heads: int = 4,
    n_layers: int = 2,
    max_len: int = 128,
    d_ff: Optional[int] = None,
    moe_experts: Optional[int] = None,
    n_kv_heads: Optional[int] = None,
    dtype=np.float32,
) -> Params:
    """``moe_experts``: replace every block's dense MLP with a top-1
    routed mixture of that many experts (:mod:`..parallel.moe`); the
    expert slabs shard over an ``ep`` mesh axis at apply time.

    ``n_kv_heads``: grouped-query attention (GQA) — k/v get this many
    heads (must divide ``n_heads``; default = ``n_heads`` = standard
    MHA, ``1`` = MQA), each shared by ``n_heads / n_kv_heads`` query
    heads. The fused qkv projection shrinks to
    ``[d, d + 2 * n_kv_heads * head_dim]`` and the decode KV cache
    holds only ``n_kv_heads`` heads — the cache (usually the decode
    memory ceiling) shrinks by the group factor."""
    if d_model % n_heads:
        raise ValueError(f"d_model {d_model} must divide by n_heads {n_heads}")
    n_kv_heads = n_heads if n_kv_heads is None else n_kv_heads
    if n_kv_heads < 1 or n_heads % n_kv_heads:
        raise ValueError(
            f"n_heads {n_heads} must divide by n_kv_heads {n_kv_heads} "
            f"(>= 1)"
        )
    d_ff = d_ff or 4 * d_model
    kv_d = (d_model // n_heads) * n_kv_heads
    rng = np.random.default_rng(seed)

    def dense(fan_in, fan_out):
        return (rng.normal(0, fan_in**-0.5, (fan_in, fan_out))).astype(dtype)

    params: Params = {
        "embed": (rng.normal(0, 0.02, (vocab, d_model))).astype(dtype),
        "pos": (rng.normal(0, 0.02, (max_len, d_model))).astype(dtype),
        "blocks": [],
        "ln_f": {"g": np.ones(d_model, dtype), "b": np.zeros(d_model, dtype)},
        # n_kv_heads is NOT stored: it is derivable from the qkv weight's
        # static column count (see _kv_heads), so every site that strips
        # the one non-array entry ("n_heads") before device_put stays
        # unchanged and old checkpoints load as plain MHA
        "n_heads": n_heads,
    }
    for li in range(n_layers):
        block = {
            "ln1": {"g": np.ones(d_model, dtype), "b": np.zeros(d_model, dtype)},
            "qkv": dense(d_model, d_model + 2 * kv_d),
            "proj": dense(d_model, d_model),
            "ln2": {"g": np.ones(d_model, dtype), "b": np.zeros(d_model, dtype)},
        }
        if moe_experts is None:
            block["up"] = dense(d_model, d_ff)
            block["down"] = dense(d_ff, d_model)
        else:
            from ..parallel.moe import init_moe

            # derive expert seeds from the model rng so they never collide
            # with the main seed (seed*k+li would reuse generator streams)
            block["moe"] = init_moe(
                int(rng.integers(0, 2**31)), d_model, d_ff, moe_experts,
                dtype=dtype,
            )
        params["blocks"].append(block)
    return params


def init_draft_transformer(
    target_params: Params,
    seed: int,
    *,
    d_model: Optional[int] = None,
    n_heads: Optional[int] = None,
    n_layers: Optional[int] = None,
    d_ff: Optional[int] = None,
    n_kv_heads: Optional[int] = None,
    dtype=None,
) -> Params:
    """A small DRAFT model for speculative decoding, derived from a
    target model's params: same vocabulary and positional table (the
    two properties the serving engine's draft/verify contract requires
    — draft proposals are token ids in the target's vocab, and the
    draft must reach every position the target can), smaller everything
    else. Defaults: half the target's layers, the target's width/heads.
    The draft is a plain :func:`init_transformer` model — train or
    distill it like any other; the serving engine only needs the params
    (``GenerationEngine(..., draft_params=...)``,
    docs/serving_llm.md "Speculative decoding")."""
    vocab = int(np.shape(target_params["embed"])[0])
    tgt_d = int(np.shape(target_params["embed"])[1])
    max_len = int(np.shape(target_params["pos"])[0])
    tgt_heads = int(target_params["n_heads"])
    d_model = tgt_d if d_model is None else int(d_model)
    n_heads = tgt_heads if n_heads is None else int(n_heads)
    n_layers = (
        max(1, len(target_params["blocks"]) // 2)
        if n_layers is None
        else int(n_layers)
    )
    if dtype is None:
        dtype = np.dtype(
            getattr(target_params["embed"], "dtype", np.float32)
        )
    return init_transformer(
        seed,
        vocab,
        d_model=d_model,
        n_heads=n_heads,
        n_layers=n_layers,
        max_len=max_len,
        d_ff=d_ff,
        n_kv_heads=n_kv_heads,
        dtype=dtype,
    )


#: the entries of a params tree that are not arrays: the head count of
#: the GPT-2-style tree, and the model description a tree may carry
STATIC_KEYS = ("n_heads", "spec")


def device_tree(params: Params) -> Params:
    """``params`` without its non-array entries: what goes to the device
    and into a jitted program as an argument."""
    return {k: v for k, v in params.items() if k not in STATIC_KEYS}


def static_entries(params: Params) -> Params:
    """The non-array entries of ``params`` (:data:`STATIC_KEYS`): closed
    over by a step program and merged back into its weight argument."""
    return {k: params[k] for k in STATIC_KEYS if k in params}


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """Rotary parameters of one layer type. ``kind`` ``"default"`` is
    plain RoPE (``theta ** (-2i / head_dim)``); ``"yarn"`` blends the
    plain frequencies with the same divided by ``factor`` along a linear
    ramp between the dimensions that make ``beta_fast`` and ``beta_slow``
    turns over ``original_max_position`` positions, and scales cos and
    sin by ``attention_factor``."""

    theta: float
    kind: str = "default"
    factor: float = 1.0
    original_max_position: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """What a block walk has to know of a model beyond its weights'
    shapes — frozen and hashable, so a step program can close over it.

    A GPT-2-style tree (``init_transformer``) carries none and gets the
    description its weights imply (:func:`model_spec`): LayerNorm 1e-5,
    learned positions, GELU MLP, tied head, head width ``d_model /
    n_heads``, K/V heads from ``qkv``'s columns. Any other model puts a
    plain dict under ``params["spec"]`` with these fields' names
    (``rope`` a dict by layer type), and the same four walks run it."""

    n_heads: int
    n_kv_heads: int
    head_dim: int
    max_len: int
    norm: str = "layer"  # "layer" | "rms"
    norm_eps: float = 1e-5
    position: str = "learned"  # "learned" | "rotary"
    #: one of "full" / "window" per layer; empty = every layer full
    layer_types: Tuple[str, ...] = ()
    #: key j is visible to query p iff p - window < j <= p (window layers)
    window: int = 0
    rope: Tuple[Tuple[str, RopeSpec], ...] = ()
    mlp: str = "gelu"  # "gelu" | "gated_experts"
    n_experts: int = 0
    experts_per_token: int = 0
    tied_head: bool = True
    #: dtype of the residual stream and of every product's accumulator;
    #: None = the weights' own (the GPT-2-style tree)
    residual_dtype: Optional[str] = None

    def layer_type(self, li: int) -> str:
        return self.layer_types[li] if self.layer_types else "full"

    def layer_window(self, li: int) -> int:
        """The window of layer ``li``; 0 for a full layer."""
        return self.window if self.layer_type(li) == "window" else 0

    def rope_of(self, li: int) -> RopeSpec:
        return dict(self.rope)[self.layer_type(li)]

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The cache kinds this model needs, full first."""
        seen = set(self.layer_types) or {"full"}
        return tuple(k for k in ("full", "window") if k in seen)


def model_spec(params: Params) -> ModelSpec:
    """The model description of a params tree: built from the dict under
    ``params["spec"]`` where there is one, read off the weights of a
    GPT-2-style tree otherwise."""
    raw = params.get("spec")
    if isinstance(raw, ModelSpec):
        return raw
    if raw is not None:
        raw = dict(raw)
        rope = tuple(
            (kind, RopeSpec(**r))
            for kind, r in sorted(dict(raw.pop("rope", {})).items())
        )
        raw["layer_types"] = tuple(raw.get("layer_types", ()))
        return ModelSpec(rope=rope, **raw)
    n_heads = int(params["n_heads"])
    d_model = int(np.shape(params["embed"])[1])
    return _gpt2_spec(
        n_heads, d_model // n_heads,
        _kv_heads(params["blocks"][0], d_model, n_heads),
        int(np.shape(params["pos"])[0]),
    )


@functools.lru_cache(maxsize=None)
def _gpt2_spec(n_heads: int, head_dim: int, n_kv: int, max_len: int):
    return ModelSpec(
        n_heads=n_heads, n_kv_heads=n_kv, head_dim=head_dim,
        max_len=max_len,
    )


def rope_inv_freq(rope: RopeSpec, head_dim: int) -> np.ndarray:
    """``[head_dim / 2]`` float32 inverse frequencies of one layer type."""
    half = head_dim // 2
    i = np.arange(half, dtype=np.float64)
    extrap = rope.theta ** (-2.0 * i / head_dim)
    if rope.kind == "default":
        return extrap.astype(np.float32)
    if rope.kind != "yarn":
        raise ValueError(f"unknown rope kind {rope.kind!r}")

    def turns_dim(turns):  # the dimension that makes ``turns`` rotations
        return head_dim * math.log(
            rope.original_max_position / (turns * 2.0 * math.pi)
        ) / (2.0 * math.log(rope.theta))

    low = max(math.floor(turns_dim(rope.beta_fast)), 0)
    high = min(math.ceil(turns_dim(rope.beta_slow)), head_dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    interp = extrap / rope.factor
    return (interp * ramp + extrap * (1.0 - ramp)).astype(np.float32)


def _rotary(spec: ModelSpec, li: int, q, k, positions):
    """Rotate ``q`` ``[..., n_kv, group, hd]`` and ``k`` ``[..., n_kv,
    hd]`` by their ``positions`` ``[...]`` in the rotate-half pairing
    ``(x_i, x_{i + hd/2})``, in float32."""
    import jax.numpy as jnp

    rope = spec.rope_of(li)
    inv = jnp.asarray(rope_inv_freq(rope, spec.head_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    scale = jnp.float32(rope.attention_factor)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1) * scale
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1) * scale

    def turn(x, c, s):
        half = x.shape[-1] // 2
        rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
        return x * c + rot * s

    kc, ks = cos[..., None, :], sin[..., None, :]
    return (
        turn(q, kc[..., None, :], ks[..., None, :]).astype(q.dtype),
        turn(k, kc, ks).astype(k.dtype),
    )


def _norm(spec: ModelSpec, x, p):
    import jax
    import jax.numpy as jnp

    if spec.norm == "rms":
        x = x.astype(jnp.float32)
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + spec.norm_eps) * jnp.asarray(
            p["g"]
        ).astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + spec.norm_eps) * p["g"] + p["b"]


def _ln(x, p):
    return _norm(_LAYER_NORM, x, p)


def _mm(spec: ModelSpec, x, w):
    """``x @ w``; with a stated residual dtype the operand takes the
    weight's dtype and the product accumulates in the residual's."""
    import jax.numpy as jnp

    w = jnp.asarray(w)
    if spec.residual_dtype is None:
        return x @ w
    return jnp.dot(
        x.astype(w.dtype), w, preferred_element_type=spec.residual_dtype
    )


def _embed(spec: ModelSpec, params, tokens, positions):
    """Token rows, plus the learned position rows where the model has
    them (``positions`` broadcasts against ``tokens``; None = the
    sequence axis of ``tokens`` counted from 0)."""
    import jax.numpy as jnp

    h = jnp.asarray(params["embed"])[tokens]
    if spec.position == "learned":
        pos = jnp.asarray(params["pos"])
        if positions is None:  # a whole sequence from position 0
            h = h + pos[: tokens.shape[1]][None]
        else:
            h = h + pos[positions]
    if spec.residual_dtype is not None:
        h = h.astype(spec.residual_dtype)
    return h


def _head(spec: ModelSpec, params, h):
    """Final norm and the output head (the embedding again when tied)."""
    import jax.numpy as jnp

    x = _norm(spec, h, params["ln_f"])
    if spec.tied_head:
        return _mm(spec, x, jnp.asarray(params["embed"]).T)
    return _mm(spec, x, params["head"])


def _ffn(spec: ModelSpec, block, x, moe_top_k: int = 1, routed=None):
    """The block's second half on the normed input: the GELU MLP, the
    GELU expert layer of ``init_moe`` (masked, ``moe_top_k``), or the
    gated experts of the description through the grouped product.
    ``routed``, a list, collects each expert layer's per-expert pair
    counts."""
    import jax
    import jax.numpy as jnp

    if "moe" not in block:
        return jax.nn.gelu(x @ jnp.asarray(block["up"])) @ (
            jnp.asarray(block["down"])
        )
    from ..parallel.moe import moe_ffn, moe_grouped

    if spec.mlp == "gated_experts":
        y, counts = moe_grouped(block["moe"], x, k=spec.experts_per_token)
        if routed is not None:
            routed.append(counts)
        return y
    if x.ndim == 2:
        return moe_ffn(block["moe"], x[:, None, :], k=moe_top_k)[:, 0]
    return moe_ffn(block["moe"], x, k=moe_top_k)


def block_forward(
    spec: ModelSpec, li: int, block, h, positions, attend,
    moe_top_k: int = 1, routed=None, ffn=None,
):
    """THE block: norm, q/k/v, positions, attention through ``attend``,
    residual; norm, MLP or experts, residual — over the model
    description, for every walk (:func:`transformer_logits`,
    :func:`transformer_step`, :func:`transformer_prefill`, the chunk
    family, the pipelined stage), so that a norm, a position scheme, an
    expert layer or a window arrives once.

    ``h`` is ``[..., d_model]`` and ``positions`` ``[...]`` (or
    broadcastable to it). ``attend(li, q, k, v)`` gets ``q`` ``[...,
    n_kv, group, hd]`` and ``k`` / ``v`` ``[..., n_kv, hd]``, rotated
    where the model is rotary, owns the K/V state and the mask (the
    layer's window is ``spec.layer_window(li)``), and returns the
    context ``[..., n_heads * hd]``. ``ffn(block, x)`` replaces the
    second half's function (the training walk's expert-parallel
    apply). The named scopes put the layer part into every device
    operation's name; they change no operation."""
    import jax
    import jax.numpy as jnp

    n_kv, hd = spec.n_kv_heads, spec.head_dim
    group = spec.n_heads // n_kv
    q_d, kv_d = spec.n_heads * hd, n_kv * hd
    lead = h.shape[:-1]
    with jax.named_scope("attn"):
        x = _norm(spec, h, block["ln1"])
        qkv = _mm(spec, x, block["qkv"])
        q, k, v = jnp.split(qkv, [q_d, q_d + kv_d], axis=-1)
        q = q.reshape(lead + (n_kv, group, hd))
        k = k.reshape(lead + (n_kv, hd))
        v = v.reshape(lead + (n_kv, hd))
        if spec.position == "rotary":
            q, k = _rotary(
                spec, li, q, k, jnp.broadcast_to(positions, lead)
            )
        h = h + _mm(spec, attend(li, q, k, v), block["proj"])
    with jax.named_scope("mlp"):
        x = _norm(spec, h, block["ln2"])
        if ffn is not None:
            return h + ffn(block, x)
        return h + _ffn(spec, block, x, moe_top_k, routed).astype(h.dtype)


_LAYER_NORM = ModelSpec(n_heads=1, n_kv_heads=1, head_dim=1, max_len=0)


def _kv_heads(block, d_model: int, n_heads: int) -> int:
    """GQA group count from the qkv weight's STATIC shape: columns are
    ``d + 2 * n_kv * head_dim``, so ``n_kv`` needs no extra stored
    metadata (plain MHA weights give ``n_kv == n_heads``)."""
    kv_d = (int(np.shape(block["qkv"])[1]) - d_model) // 2
    return kv_d // (d_model // n_heads)


def _sequence_attend(spec, causal, attn_impl, mesh, batch_axis):
    """The whole-sequence ``attend`` of the training and scoring walk:
    ``q`` ``[B, L, n_kv, group, hd]`` against this layer's own ``k`` /
    ``v`` ``[B, L, n_kv, hd]``. The dense read scores each K/V head
    against its query group as it lies; the sequence kernels are
    head-uniform, so only they get K and V repeated per query head."""
    import jax
    import jax.numpy as jnp

    from ..ops import flash_attention, ring_attention, ulysses_attention
    from ..ops.attention import _NEG_BIG

    def attend(li, q, k, v):
        bsz, length, n_kv, group, hd = q.shape
        window = spec.layer_window(li)
        if attn_impl == "reference":
            s = jnp.einsum("bqkgd,btkd->bkgqt", q, k).astype(jnp.float32)
            s = s * (1.0 / float(np.sqrt(hd)))
            qi = jnp.arange(length)[:, None]
            ki = jnp.arange(length)[None, :]
            if causal:
                valid = qi >= ki
                if window:
                    valid &= ki > qi - window
                s = jnp.where(valid, s, _NEG_BIG)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bkgqt,btkd->bqkgd", p, v.astype(jnp.float32))
            return o.astype(q.dtype).reshape(bsz, length, -1)
        if window:
            raise ValueError(
                f"attn_impl {attn_impl!r} has no window mask; window "
                f"layers run with attn_impl='reference'"
            )
        qh = q.reshape(bsz, length, n_kv * group, hd).transpose(0, 2, 1, 3)
        kh = jnp.repeat(k.transpose(0, 2, 1, 3), group, axis=1)
        vh = jnp.repeat(v.transpose(0, 2, 1, 3), group, axis=1)
        if attn_impl == "ring":
            o = ring_attention(
                qh, kh, vh, mesh=mesh, causal=causal, batch_axis=batch_axis
            )
        elif attn_impl == "ulysses":
            o = ulysses_attention(
                qh, kh, vh, mesh=mesh, causal=causal, batch_axis=batch_axis
            )
        else:
            o = flash_attention(qh, kh, vh, causal=causal)
        return o.transpose(0, 2, 1, 3).reshape(bsz, length, -1)

    return attend


def _dense_block(
    block, h, n_heads, causal=True, attn_impl="reference", mesh=None,
    batch_axis=None,
):
    """One GPT-2-style block over a whole sequence: :func:`block_forward`
    with the description its weights imply — the pipelined stage's
    body (:func:`_pipe_stage_fn`)."""
    import jax.numpy as jnp

    d_model = h.shape[-1]
    spec = _gpt2_spec(
        n_heads, d_model // n_heads, _kv_heads(block, d_model, n_heads), 0
    )
    return block_forward(
        spec, 0, block, h, jnp.arange(h.shape[1])[None],
        _sequence_attend(spec, causal, attn_impl, mesh, batch_axis),
    )


def _head_nll(embed, ln_f, x, targets):
    """Loss head: final norm + tied unembedding + next-token cross entropy
    (mean). Shared by the pipelined loss (:func:`_pipe_loss_fn`); the
    full-model path computes the same math spread across
    :func:`transformer_logits`/:func:`token_nll` (kept separate there
    because scoring needs the per-position NLL, not the mean)."""
    import jax
    import jax.numpy as jnp

    logits = _ln(x, ln_f) @ embed.T
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, targets[..., None].astype(jnp.int32), axis=-1
    )
    return -picked[..., 0].mean()


def transformer_logits(
    params: Params,
    tokens,
    causal: bool = True,
    attn_impl: str = "reference",
    mesh=None,
    batch_axis=None,
    collect_moe_aux: bool = False,
    moe_top_k: int = 1,
    moe_impl: str = "masked",
    remat: bool = False,
):
    """``tokens`` [B, L] int32 -> logits [B, L, vocab].

    ``attn_impl``: "reference" (dense, XLA-fused — best for short L),
    "flash" (Pallas kernel), "ring" (K/V rotation over ``mesh``'s sp
    axis), or "ulysses" (all-to-all head-sharding over the same axis;
    needs heads divisible by the axis size).

    MoE blocks route top-``moe_top_k``; ``moe_impl`` picks the expert
    data path on an ``ep`` mesh: "masked" (exact masked compute, every
    chip sees all tokens) or "dispatch" (Switch all-to-all with capacity
    buffers — lower FLOPs/communication at scale, drops overflow
    tokens).

    ``remat=True`` wraps each block in ``jax.checkpoint``: the backward
    pass recomputes block activations instead of saving them, so
    activation memory is O(1) in depth — the standard FLOPs-for-HBM
    trade for long-context / deep training (MoE blocks stay un-rematted
    when ``collect_moe_aux`` needs their intermediate activations)."""
    if moe_impl not in ("masked", "dispatch"):
        raise ValueError(
            f"unknown moe_impl {moe_impl!r}; expected 'masked' or "
            f"'dispatch'"
        )
    if attn_impl not in ("reference", "flash", "ring", "ulysses"):
        raise ValueError(
            f"unknown attn_impl {attn_impl!r}; expected 'reference', "
            f"'flash', 'ring', or 'ulysses'"
        )
    import jax
    import jax.numpy as jnp

    from ..parallel.moe import (
        EXPERT_AXIS,
        moe_apply,
        moe_dispatch_apply,
        moe_ffn,
        moe_grouped,
        moe_load_balance_loss,
    )

    spec = model_spec(params)
    # params may be host numpy (frozen-model scoring closes over them);
    # the walk jnp-ifies before indexing with traced token ids
    positions = jnp.arange(tokens.shape[1])[None]
    x = _embed(spec, params, tokens, None)
    attend = _sequence_attend(spec, causal, attn_impl, mesh, batch_axis)

    def experts(block, h):
        if spec.mlp == "gated_experts":
            return moe_grouped(block["moe"], h, k=spec.experts_per_token)[0]
        if mesh is not None and EXPERT_AXIS in mesh.axis_names:
            apply = (
                moe_dispatch_apply if moe_impl == "dispatch" else moe_apply
            )
            return apply(block["moe"], h, mesh=mesh, k=moe_top_k)
        return moe_ffn(block["moe"], h, k=moe_top_k)

    def run_dense(li, block, h):
        return block_forward(spec, li, block, h, positions, attend)

    def run_moe(li, block, h):
        mid = []

        def ffn(block, hx):
            mid.append(hx)
            return experts(block, hx).astype(h.dtype)

        return block_forward(
            spec, li, block, h, positions, attend, ffn=ffn
        ), mid[0]

    if remat:
        run_dense = jax.checkpoint(run_dense, static_argnums=0)
        if not collect_moe_aux:
            run_moe = jax.checkpoint(run_moe, static_argnums=0)

    moe_aux = 0.0
    top_k = spec.experts_per_token or moe_top_k
    for li, block in enumerate(params["blocks"]):
        if "moe" in block:
            x, h_mid = run_moe(li, block, x)
            if collect_moe_aux:
                moe_aux = moe_aux + moe_load_balance_loss(
                    block["moe"], h_mid, k=top_k
                )
        else:
            x = run_dense(li, block, x)
    logits = _head(spec, params, x)
    if collect_moe_aux:
        return logits, moe_aux
    return logits


def _is_concrete_scalar(x) -> bool:
    """True when ``x`` is a plain Python/numpy number (its VALUE may steer
    trace-time structure); False for tracers (value unknown — the caller
    must have opted into the sampled/filtered program shape)."""
    return isinstance(x, (int, float, np.integer, np.floating))


def filter_logits(logits, top_k: int = 0, top_p=1.0):
    """Top-k / nucleus (top-p) logit filtering, [B, V] -> [B, V] with
    masked-out entries at a large negative. ``top_k`` is static (0 = off);
    ``top_p`` may be a traced scalar (1.0 = off when concrete). Nucleus
    keeps the smallest prefix of descending-probability tokens whose
    cumulative mass reaches ``top_p`` (the first token always survives, so
    a tiny top_p degrades to greedy, not to an empty support)."""
    import jax
    import jax.numpy as jnp

    neg = jnp.finfo(jnp.float32).min * 0.7
    if top_k and top_k > 0:
        # top_k >= vocab keeps everything (lax.top_k would fail the trace
        # with an opaque XLA shape error instead)
        k = min(int(top_k), logits.shape[-1])
        if k < logits.shape[-1]:
            kth = jax.lax.top_k(logits, k)[0][..., -1:]
            logits = jnp.where(logits < kth, neg, logits)
    if top_p is not None and not (
        _is_concrete_scalar(top_p) and top_p >= 1.0
    ):
        sl = jnp.sort(logits, axis=-1)[..., ::-1]  # descending
        ps = jax.nn.softmax(sl, axis=-1)
        css = jnp.cumsum(ps, axis=-1)
        # token j (sorted order) kept iff the mass BEFORE it is < top_p
        keep = (css - ps) < top_p
        k_eff = keep.sum(axis=-1, keepdims=True)  # >= 1 by construction
        thresh = jnp.take_along_axis(sl, k_eff - 1, axis=-1)
        logits = jnp.where(logits < thresh, neg, logits)
    return logits


def left_pad_prompts(seqs, pad_id: int = 0):
    """Pack variable-length prompts into the left-padded ``[B, P]`` layout
    :func:`transformer_generate` takes for ragged batches (each row's
    tokens right-aligned at positions ``P-len..P-1``). Returns
    ``(prompt, lengths)``."""
    lengths = np.asarray([len(s) for s in seqs], dtype=np.int32)
    if (lengths < 1).any():
        raise ValueError("every prompt needs at least one token")
    p = int(lengths.max())
    out = np.full((len(seqs), p), pad_id, dtype=np.int32)
    for i, s in enumerate(seqs):
        out[i, p - len(s):] = np.asarray(s, dtype=np.int32)
    return out, lengths


def transformer_step(params, tok, positions, attend, moe_top_k: int = 1,
                     routed=None):
    """One decoder step for a batch of single tokens — THE per-token block
    walk, shared by the scan decode (:func:`transformer_generate`) and the
    paged serving engine (:mod:`tensorframes_tpu.serve`) so the two decode
    paths cannot drift apart.

    ``tok`` [B] int32 current tokens; ``positions`` [B] int32 positional
    indices (already offset/clipped by the caller). Attention is delegated
    to ``attend(li, q, k, v) -> [B, d_model]``: the callback owns the KV
    state — it receives layer ``li``'s query ``[B, n_kv, group, hd]``
    (grouped-query layout; ``group == 1`` rows share a k/v head) and this
    step's k/v ``[B, n_kv, hd]``, stores k/v wherever the caller keeps its
    cache (scan-carried dense cache, paged pool), reads the visible
    history, and returns the pre-``proj`` attention context. The block
    itself is :func:`block_forward`'s, over :func:`model_spec`'s
    description: a rotary model's q and k arrive rotated, and the mask
    of a window layer (``spec.layer_window(li)``) is the callback's.
    ``routed``, a list, collects each expert layer's per-expert pair
    counts. Returns logits ``[B, vocab]``."""
    import jax

    spec = model_spec(params)
    h = _embed(spec, params, tok, positions)
    for li, block in enumerate(params["blocks"]):
        h = block_forward(
            spec, li, block, h, positions, attend, moe_top_k, routed
        )
    with jax.named_scope("head"):
        return _head(spec, params, h)


def transformer_prefill(params, tokens, store, moe_top_k: int = 1):
    """Batched causal prompt pass that hands each layer's k/v to the
    caller: ``tokens`` [B, P] -> ``logits [B, P, vocab]``, with
    ``store(li, k, v)`` called once per layer on that layer's ``k`` /
    ``v`` ``[B, P, n_kv, hd]`` (the per-token rows as the projection
    produced them).

    This is the prefill half of serving decode: the whole prompt runs as
    dense MXU matmuls in one pass (instead of P sequential cache steps),
    the callback writes the rows wherever the caller keeps its cache —
    layer by layer, so no ``[L, B, P, ...]`` stack of all layers' k/v is
    ever held — and the caller continues with :func:`transformer_step`.
    Attention uses the same grouped-query einsum family as the step
    path."""
    import jax
    import jax.numpy as jnp

    tokens = jnp.asarray(tokens, dtype=jnp.int32)
    bsz, plen = tokens.shape
    spec = model_spec(params)
    scale = 1.0 / float(np.sqrt(spec.head_dim))
    neg = jnp.finfo(jnp.float32).min * 0.7
    qi, ki = jnp.arange(plen)[:, None], jnp.arange(plen)[None, :]
    causal = qi >= ki  # [P(q), P(k)]

    def attend(li, q, k, v):
        store(li, k, v)
        window = spec.layer_window(li)
        visible = causal & (ki > qi - window) if window else causal
        # [B, n_kv, P, hd] — what the decode step's dense twin reads
        kc = k.transpose(0, 2, 1, 3)
        vc = v.transpose(0, 2, 1, 3)
        qh = q.transpose(0, 2, 3, 1, 4)
        s = jnp.einsum("bkgqd,bktd->bkgqt", qh, kc) * scale
        s = jnp.where(visible[None, None, None], s, neg)
        att = jnp.einsum(
            "bkgqt,bktd->bkgqd", jax.nn.softmax(s, axis=-1), vc
        )
        return att.transpose(0, 3, 1, 2, 4).reshape(bsz, plen, -1)

    h = _embed(spec, params, tokens, None)
    positions = jnp.arange(plen)[None]
    for li, block in enumerate(params["blocks"]):
        h = block_forward(
            spec, li, block, h, positions, attend, moe_top_k
        )
    with jax.named_scope("head"):
        return _head(spec, params, h)


def transformer_prefill_chunk(params, tokens, positions, attend,
                              moe_top_k: int = 1, head_at=None):
    """One CHUNK of a prompt through the block walk, with attention
    delegated — the mid-sequence sibling of :func:`transformer_step`
    (single token, cache owned by the caller) and
    :func:`transformer_prefill` (whole prompt, dense causal, k/v
    handed to a callback). Chunked prefill needs a third shape: a ``[B, C]`` span of
    tokens at arbitrary ``positions``, attending to cache the caller
    already holds (earlier chunks, or a shared-prefix hit) PLUS itself
    causally.

    ``tokens`` [B, C] int32; ``positions`` [C] int32 (absolute; the
    caller clips padding positions in-bounds). ``attend(li, q, k, v) ->
    [B, C, d_model]``: q ``[B, C, n_kv, group, hd]`` (grouped-query
    layout), this chunk's k/v ``[B, C, n_kv, hd]`` — the callback
    scatters k/v wherever it keeps its cache and reads the visible
    history under its own causal mask. The per-row math (LN, MLP,
    residuals, head split) is token-local and identical to
    :func:`transformer_prefill`'s, so a prompt prefilled in chunks
    produces byte-identical k/v and logits to one dense pass. Returns
    logits ``[B, C, vocab]`` — or, with ``head_at`` (a traced row of the
    chunk), ``[B, vocab]`` for that row alone: the output head is as wide
    as the vocabulary, and a prefill needs it once."""
    import jax.numpy as jnp

    tokens = jnp.asarray(tokens, dtype=jnp.int32)
    return _chunk_blocks(
        params, tokens, jnp.asarray(positions)[None], attend, moe_top_k,
        head_at,
    )


def transformer_verify_chunk(params, tokens, positions, attend,
                             moe_top_k: int = 1):
    """The batched mid-sequence VERIFY step — the serving engine's
    speculative-decoding sibling of :func:`transformer_prefill_chunk`:
    the same delegated ``[B, C]`` block walk, but ``positions`` is
    ``[B, C]`` because every decode slot sits at its OWN absolute
    offset (slot ``b``'s ``k + 1`` verify tokens start at that
    sequence's pending position, not a shared chunk start). The per-row
    math is token-local and shared with the chunk walk
    (:func:`_chunk_blocks`), which is what makes a verify pass's
    logits — and therefore the target tokens sampled from them —
    byte-identical to the per-token decode step's at every position
    (docs/serving_llm.md "Speculative decoding"). Returns logits
    ``[B, C, vocab]``."""
    import jax.numpy as jnp

    tokens = jnp.asarray(tokens, dtype=jnp.int32)
    return _chunk_blocks(params, tokens, positions, attend, moe_top_k)


def _chunk_blocks(params, tokens, positions, attend, moe_top_k: int,
                  head_at=None):
    """The shared ``[B, C]`` delegated-attention block walk of the
    chunk family (:func:`transformer_prefill_chunk` /
    :func:`transformer_verify_chunk`) — one implementation so the
    prefill-chunk and verify programs cannot drift apart; the block is
    :func:`block_forward`'s, like every walk's. ``positions`` is
    ``[1, C]`` or ``[B, C]``."""
    spec = model_spec(params)
    h = _embed(spec, params, tokens, positions)
    for li, block in enumerate(params["blocks"]):
        h = block_forward(
            spec, li, block, h, positions, attend, moe_top_k
        )
    if head_at is not None:
        h = h[:, head_at]
    return _head(spec, params, h)


def transformer_tp_specs(params, axis: str = "tp"):
    """PartitionSpec tree for the TENSOR-PARALLEL SERVING weight layout
    (``params`` WITHOUT the ``n_heads`` entry — the device tree the
    serving engine ships): every large matrix is sharded AT REST along
    its hidden-ish axis — ``qkv`` and ``up`` on their output columns,
    ``proj`` and ``down`` on their input rows (= the MLP hidden dim) —
    while embeddings, positions, and layernorms stay replicated (the
    embedding is read by token lookup AND the tied head, both of which
    want full rows). Per-chip weight HBM shrinks ~1/N with the mesh.

    The compute plan (:mod:`tensorframes_tpu.serve.tp`) gathers these
    shards back to FULL weights inside each step program
    (:func:`gather_tp_params`) and runs every matmul at the solo
    program's exact shapes. That is deliberate: the serving contract is
    byte-identical decode streams at every TP degree, and neither
    Megatron row-parallel partial sums nor column-sliced GEMMs preserve
    float reduction order — an all-gathered shard tree, by contrast,
    reconstructs the solo weights bit-for-bit. The sharded COMPUTE lives
    where it is bit-exact by construction: the per-KV-head paged
    attention walk and the page pool, which are batch-indexed in the
    head axis. ``MoE`` blocks have no serving TP plan yet — rejected
    here so the error names the gap."""
    from jax.sharding import PartitionSpec as P

    rep = P()

    def ln_spec():
        return {"g": rep, "b": rep}

    blocks = []
    for i, block in enumerate(params["blocks"]):
        if "moe" in block:
            raise ValueError(
                f"block {i} is a mixture-of-experts block; tensor-"
                f"parallel serving shards dense blocks only (MoE serving "
                f"shards over an 'ep' mesh — not wired into the engine "
                f"yet)"
            )
        blocks.append(
            {
                "ln1": ln_spec(),
                "qkv": P(None, axis),
                "proj": P(axis, None),
                "ln2": ln_spec(),
                "up": P(None, axis),
                "down": P(axis, None),
            }
        )
    return {
        "embed": rep,
        "pos": rep,
        "ln_f": ln_spec(),
        "blocks": blocks,
    }


def gather_tp_params(p_loc, axis: str = "tp"):
    """Inside a ``shard_map`` body: all-gather the weight shards of
    :func:`transformer_tp_specs`'s layout back to FULL weights. Tiled
    gathers concatenate the shards in mesh order along the sharded axis,
    so the gathered tree is bit-for-bit the solo weight tree — the
    property the byte-identical-streams contract of
    :mod:`tensorframes_tpu.serve.tp` rides on."""
    import jax

    def g(a, ax):
        return jax.lax.all_gather(a, axis, axis=ax, tiled=True)

    blocks = [
        {
            **b,
            "qkv": g(b["qkv"], 1),
            "proj": g(b["proj"], 0),
            "up": g(b["up"], 1),
            "down": g(b["down"], 0),
        }
        for b in p_loc["blocks"]
    ]
    return {**p_loc, "blocks": blocks}


def transformer_generate(
    params: Params,
    prompt,
    max_new_tokens: int,
    temperature=0.0,
    seed=0,
    moe_top_k: int = 1,
    top_k: int = 0,
    top_p=1.0,
    prompt_lengths=None,
):
    """Autoregressive decode with a KV cache, compiled as ONE
    ``lax.scan`` program: per step the new token's q/k/v are computed,
    k/v land in a static-shape cache via ``dynamic_update_slice``, and
    attention reads the cache under a position mask — no recompilation
    per step, no growing shapes (the XLA-native decode loop; a Python
    loop re-running :func:`transformer_logits` on the growing sequence
    recompiles per length and recomputes O(L^2) work per token).

    ``temperature`` 0 = greedy argmax; > 0 samples categorically with a
    per-step key folded from ``seed``, after :func:`filter_logits` applies
    ``top_k`` / nucleus ``top_p`` truncation. ``temperature``, ``seed``
    and ``top_p`` may be TRACED scalars (pass them as jit arguments — one
    compiled program serves every seed/temperature sweep); ``top_k`` is
    static. Returns ``[B, P + max_new_tokens]`` int32 (prompt included).
    ``prompt + max_new_tokens`` must fit ``max_len`` (the positional
    table).

    Ragged batches: pass LEFT-padded prompts (each row's tokens at
    positions ``P-len..P-1``; :func:`left_pad_prompts` packs them) plus
    ``prompt_lengths`` [B]. Pad slots are excluded from attention and
    per-row position offsets keep the positional table aligned, so every
    row decodes exactly as it would alone; generation starts at the shared
    slot ``P`` for all rows."""
    import jax
    import jax.numpy as jnp

    prompt = jnp.asarray(prompt, dtype=jnp.int32)
    if prompt.ndim != 2 or prompt.shape[1] < 1:
        raise ValueError("prompt must be [B, P>=1] token ids")
    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1; got {max_new_tokens}"
        )
    bsz, plen = prompt.shape
    spec = model_spec(params)
    n_kv, hd = spec.n_kv_heads, spec.head_dim
    total = plen + max_new_tokens
    if total > spec.max_len:
        raise ValueError(
            f"prompt ({plen}) + max_new_tokens ({max_new_tokens}) = "
            f"{total} exceeds max_len {spec.max_len}"
        )
    blocks = params["blocks"]
    scale = 1.0 / float(np.sqrt(hd))
    neg = jnp.finfo(jnp.float32).min * 0.7
    # greedy vs sampled is a STRUCTURAL choice: concrete temperature <= 0
    # means greedy; a traced temperature always means the sampled program
    sampled = not (_is_concrete_scalar(temperature) and temperature <= 0)
    if prompt_lengths is None:
        offsets = jnp.zeros((bsz,), jnp.int32)
    else:
        offsets = plen - jnp.asarray(prompt_lengths, dtype=jnp.int32)

    # GQA: the cache stores only the model's n_kv k/v heads — the decode
    # memory ceiling shrinks by the group factor (n_kv == n_heads for MHA)
    k0 = jnp.zeros((len(blocks), bsz, n_kv, total, hd), jnp.float32)
    v0 = jnp.zeros_like(k0)

    def step(carry, t):
        kc, vc, prev = carry
        tok = jnp.where(
            t < plen,
            jax.lax.dynamic_index_in_dim(
                prompt, jnp.minimum(t, plen - 1), axis=1, keepdims=False
            ),
            prev,
        )
        # visible = causal AND not a pad slot (slot j belongs to row b's
        # prompt iff j >= offsets[b])
        slots = jnp.arange(total)[None, :]
        visible = (slots <= t) & (slots >= offsets[:, None])  # [B, T]
        caches = [kc, vc]

        def attend(li, q, k, v):
            # grouped-query layout: q [B, n_kv, g, hd] against a cache
            # holding only n_kv k/v heads (g = 1 and n_kv = n_heads for
            # plain MHA — same math, same program shape). k/v land in the
            # scan-carried static-shape cache at slot t; attention reads
            # the whole cache under the visibility mask.
            caches[0] = jax.lax.dynamic_update_slice(
                caches[0], k.reshape(1, bsz, n_kv, 1, hd), (li, 0, 0, t, 0)
            )
            caches[1] = jax.lax.dynamic_update_slice(
                caches[1], v.reshape(1, bsz, n_kv, 1, hd), (li, 0, 0, t, 0)
            )
            s = jnp.einsum("bkgd,bktd->bkgt", q, caches[0][li]) * scale
            seen = visible
            if spec.layer_window(li):
                seen = seen & (slots > t - spec.layer_window(li))
            s = jnp.where(seen[:, None, None, :], s, neg)
            return jnp.einsum(
                "bkgt,bktd->bkgd", jax.nn.softmax(s, axis=-1), caches[1][li]
            ).reshape(bsz, -1)

        # per-row position offset: a left-padded row's token at slot t sits
        # at real position t - offset (pad slots gather position 0; they
        # are masked out of attention above, so the value never matters)
        logits = transformer_step(
            params, tok, jnp.clip(t - offsets, 0, total - 1), attend,
            moe_top_k=moe_top_k,
        )
        kc, vc = caches
        if sampled:
            key = jax.random.fold_in(jax.random.PRNGKey(seed), t)
            scaled = logits / jnp.maximum(
                jnp.asarray(temperature, jnp.float32), 1e-6
            )
            nxt = jax.random.categorical(
                key, filter_logits(scaled, top_k=top_k, top_p=top_p),
                axis=-1,
            )
        else:
            nxt = jnp.argmax(logits, axis=-1)
        nxt = nxt.astype(jnp.int32)
        return (kc, vc, nxt), nxt

    (_, _, _), outs = jax.lax.scan(
        step, (k0, v0, prompt[:, 0]), jnp.arange(total - 1)
    )
    # step t emits the prediction for position t+1: the generated tokens
    # are the emissions of steps plen-1 .. total-2 (with left padding,
    # every row's prompt ends at slot plen-1, so this holds for ragged
    # batches too)
    return jnp.concatenate([prompt, outs[plen - 1 :].T], axis=1)


def token_nll(
    params: Params, tokens, attn_impl: str = "reference", mesh=None,
    batch_axis=None, collect_moe_aux: bool = False, moe_top_k: int = 1,
    moe_impl: str = "masked", remat: bool = False,
):
    """Per-position next-token negative log-likelihood ``[B, L-1]`` — the
    one implementation both training loss and frame scoring reduce over.
    With ``collect_moe_aux`` returns ``(nll, aux)`` from the SAME forward
    (no second pass)."""
    import jax
    import jax.numpy as jnp

    fwd = transformer_logits(
        params, tokens[:, :-1], causal=True, attn_impl=attn_impl, mesh=mesh,
        batch_axis=batch_axis, collect_moe_aux=collect_moe_aux,
        moe_top_k=moe_top_k, moe_impl=moe_impl, remat=remat,
    )
    logits, aux = fwd if collect_moe_aux else (fwd, None)
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, targets[..., None].astype(jnp.int32), axis=-1
    )
    nll = -picked[..., 0]
    return (nll, aux) if collect_moe_aux else nll


def transformer_loss(
    params: Params, tokens, attn_impl: str = "reference", mesh=None,
    batch_axis=None, moe_aux_weight: float = 0.0, moe_top_k: int = 1,
    moe_impl: str = "masked", remat: bool = False,
):
    """Next-token cross entropy (mean over all predicted positions).

    ``moe_aux_weight`` > 0 adds the Switch load-balancing loss summed over
    the MoE blocks (typical value 1e-2) — the in-tree remedy for router
    collapse when training with ``moe_experts``."""
    if moe_aux_weight:
        nll, aux = token_nll(
            params, tokens, attn_impl=attn_impl, mesh=mesh,
            batch_axis=batch_axis, collect_moe_aux=True,
            moe_top_k=moe_top_k, moe_impl=moe_impl, remat=remat,
        )
        return nll.mean() + moe_aux_weight * aux
    return token_nll(
        params, tokens, attn_impl=attn_impl, mesh=mesh,
        batch_axis=batch_axis, moe_top_k=moe_top_k, moe_impl=moe_impl,
        remat=remat,
    ).mean()


@functools.lru_cache(maxsize=None)
def _pipe_stage_fn(n_heads: int):
    """Stable stage-function object per head count: the compiled pipeline
    program caches on FUNCTION IDENTITY (see ``parallel.pipeline``), so
    this must not be recreated per call. Delegates to the SAME block body
    the full-model path uses (:func:`_dense_block`)."""

    def fn(block, h):
        return _dense_block(block, h, n_heads)

    return fn


def _pipe_loss_fn(extra, y, targets):
    """Loss head fused into the pipeline's last stage (see
    :func:`_head_nll`)."""
    return _head_nll(extra["embed"], extra["ln_f"], y, targets)


class TransformerLM:
    """Parameter holder + frame scoring + simple SGD fitting."""

    def __init__(self, params: Params):
        self.params = params

    @staticmethod
    def init(seed: int, vocab: int, **kw) -> "TransformerLM":
        return TransformerLM(init_transformer(seed, vocab, **kw))

    def _sgd_loop(
        self, tokens, steps, lr, loss_kwargs, jit_kwargs=None, place=None,
        resume=None, checkpoint_every=None, on_step=None,
        place_restored=None,
    ):
        """Shared SGD machinery for :meth:`fit` and :meth:`fit_sharded`:
        jitted value_and_grad step, loop, params reassembly. ``loss_kwargs``
        feed :func:`transformer_loss`; ``jit_kwargs`` (e.g. out_shardings)
        configure the jit; ``place`` maps host tokens to device.

        ``resume``/``checkpoint_every``/``on_step``: same auto-resume
        contract as :meth:`ShardedSGDTrainer.fit <..parallel.training.ShardedSGDTrainer.fit>`
        — restore the latest step-numbered checkpoint from ``resume`` and
        continue, write every ``checkpoint_every`` steps and at the end
        (the reference rode Spark's task retry instead, SURVEY §5)."""
        import jax

        static = static_entries(self.params)
        p = device_tree(self.params)

        def loss_fn(p_, toks_):
            return transformer_loss({**p_, **static}, toks_, **loss_kwargs)

        def step(p_, toks_):
            loss, grads = jax.value_and_grad(loss_fn)(p_, toks_)
            return jax.tree.map(lambda a, g: a - lr * g, p_, grads), loss

        step = jax.jit(step, **(jit_kwargs(p) if jit_kwargs else {}))
        toks = np.asarray(tokens, dtype=np.int32)
        if place is not None:
            toks = place(toks)
        from ..utils.checkpoint import run_checkpointed_loop

        p, losses = run_checkpointed_loop(
            lambda p_: step(p_, toks),
            p,
            steps,
            resume=resume,
            checkpoint_every=checkpoint_every,
            on_step=on_step,
            place_restored=place_restored,
        )
        self.params = {**jax.device_get(p), **static}
        return losses

    def fit(
        self,
        tokens: np.ndarray,
        steps: int = 10,
        lr: float = 0.1,
        mesh=None,
        moe_aux_weight: float = 0.0,
        moe_top_k: int = 1,
        moe_impl: str = "masked",
        attn_impl: str = "reference",
        remat: bool = False,
        resume=None,
        checkpoint_every=None,
        on_step=None,
    ):
        """Jitted SGD on next-token loss. Single chip by default; pass a
        mesh with an ``ep`` axis to train MoE blocks expert-parallel
        (``moe_impl``: "masked" exact compute or "dispatch" Switch
        all-to-all), with ``moe_aux_weight`` adding the load-balancing
        loss. ``attn_impl="flash"`` trains through the pallas kernel's
        custom VJP (long context on one chip without the [L, L] matrix);
        sequence-parallel training lives in :meth:`fit_sharded`.
        ``resume``/``checkpoint_every``/``on_step``: auto-resume from a
        checkpoint directory (see :meth:`_sgd_loop`)."""
        kw = {}
        if mesh is not None:
            kw["mesh"] = mesh
        if moe_aux_weight:
            kw["moe_aux_weight"] = moe_aux_weight
        if moe_top_k != 1:
            kw["moe_top_k"] = moe_top_k
        if moe_impl != "masked":
            kw["moe_impl"] = moe_impl
        if attn_impl != "reference":
            kw["attn_impl"] = attn_impl
        if remat:
            kw["remat"] = True
        return self._sgd_loop(
            tokens, steps, lr, loss_kwargs=kw,
            resume=resume, checkpoint_every=checkpoint_every,
            on_step=on_step,
        )

    def fit_tp(
        self,
        tokens: np.ndarray,
        mesh,
        steps: int = 10,
        lr: float = 0.1,
        resume=None,
        checkpoint_every=None,
        on_step=None,
    ):
        """One jitted SGD step over a ``dp x tp`` mesh: batch rows sharded
        over ``dp``, every block's weights Megatron-sharded over ``tp`` —
        the MLP up-projection column-parallel (output dim), ``proj`` and
        the down-projection row-parallel (input dim), embeddings and
        layernorms replicated. No hand-written collectives: the shardings
        are GSPMD annotations, and XLA inserts the activation all-reduces
        after the row-parallel matmuls and the gradient all-reduces over
        both axes inside the SAME program (SURVEY §2.5 — the reference
        has no model parallelism at all). Training semantics are exactly
        the single-device step: losses match :meth:`fit` to float
        tolerance.

        The FUSED ``qkv`` matrix ([D, q|k|v], width ``d + 2*kv_d`` —
        ``3*d_model`` for plain MHA, smaller under GQA) is also
        output-sharded, but its tp cuts land at equal fractions of the
        fused width — across the q/k/v segment boundaries — so GSPMD
        inserts a reshard between the qkv matmul and the head split
        rather than the zero-comm Megatron column pattern (that would
        need per-segment sharding, i.e. separate q/k/v parameters).
        proj/up/down realize the classic pattern.

        Constraints: batch divisible by dp, ``n_heads`` and ``d_ff``
        divisible by tp (the head einsums partition on head boundaries).
        MoE blocks train expert-parallel via :meth:`fit`'s ``mesh``
        option instead; here their slabs are replicated."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if not {"dp", "tp"} <= set(mesh.axis_names):
            raise ValueError(
                f"fit_tp needs a mesh with 'dp' and 'tp' axes; got "
                f"{mesh.axis_names}"
            )
        n_heads = self.params["n_heads"]
        tp = mesh.shape["tp"]
        if n_heads % tp:
            raise ValueError(
                f"n_heads {n_heads} must divide by tp={tp} so the "
                f"column-parallel split lands on head boundaries"
            )
        d_model = int(np.shape(self.params["embed"])[1])
        for bl in self.params["blocks"]:
            n_kv = _kv_heads(bl, d_model, n_heads)
            if n_kv % tp:
                # with fewer kv heads than tp shards the k/v einsums
                # cannot partition on head boundaries and GSPMD silently
                # replicates/reshards k/v, eroding the Megatron pattern
                # (correct, but with extra collectives) — reject rather
                # than quietly train slow
                raise ValueError(
                    f"n_kv_heads {n_kv} must divide by tp={tp}: the k/v "
                    f"head einsums partition on kv-head boundaries (use "
                    f"tp <= n_kv_heads, or MHA weights)"
                )
        b = tokens.shape[0]
        if b % mesh.shape["dp"]:
            raise ValueError(
                f"batch {b} must divide by dp={mesh.shape['dp']}"
            )

        def sh(*spec):
            return NamedSharding(mesh, P(*spec))

        def block_shardings(block):
            s = {
                "ln1": {"g": sh(), "b": sh()},
                "qkv": sh(None, "tp"),
                "proj": sh("tp", None),
                "ln2": {"g": sh(), "b": sh()},
            }
            if "up" in block:
                if block["up"].shape[1] % tp:
                    raise ValueError(
                        f"d_ff {block['up'].shape[1]} must divide by "
                        f"tp={tp}"
                    )
                s["up"] = sh(None, "tp")
                s["down"] = sh("tp", None)
            if "moe" in block:
                s["moe"] = jax.tree.map(lambda _: sh(), block["moe"])
            return s

        pshard = {
            "embed": sh(),
            "pos": sh(),
            "ln_f": {"g": sh(), "b": sh()},
            "blocks": [
                block_shardings(bl) for bl in self.params["blocks"]
            ],
        }
        tok_sh = sh("dp", None)
        return self._sgd_loop(
            tokens,
            steps,
            lr,
            loss_kwargs={},
            jit_kwargs=lambda p_: dict(
                in_shardings=(pshard, tok_sh),
                out_shardings=(pshard, NamedSharding(mesh, P())),
            ),
            place=lambda t: jax.device_put(t, tok_sh),
            resume=resume,
            checkpoint_every=checkpoint_every,
            on_step=on_step,
            # restored leaves come back committed to one device; re-pin
            # them to the Megatron plan before the sharded step sees them
            place_restored=lambda p_: jax.device_put(p_, pshard),
        )

    def fit_sharded(
        self,
        tokens: np.ndarray,
        mesh,
        steps: int = 10,
        lr: float = 0.1,
        attn_impl: str = "ring",
        resume=None,
        checkpoint_every=None,
        on_step=None,
    ):
        """One jitted SGD step over a ``dp x sp`` mesh: batch rows sharded
        over ``dp``, attention sequence-parallel over ``sp`` — ``"ring"``
        (K/V rotation, any head count) or ``"ulysses"`` (two all_to_all
        transposes + the flash kernel's custom VJP; needs heads divisible
        by sp), both with ``batch_axis="dp"``. Both axes live in the SAME
        program, so GSPMD inserts the gradient all-reduce over dp around
        the sequence-parallel collectives over sp.

        Constraint from the loss shift: the attention runs on ``L - 1``
        positions, so ``tokens.shape[1] - 1`` must divide by the sp axis
        size (and the batch by the dp size)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if set(mesh.axis_names) != {"dp", "sp"}:
            raise ValueError(
                f"fit_sharded needs a ('dp','sp') mesh; got {mesh.axis_names}"
            )
        if attn_impl not in ("ring", "ulysses"):
            # both sequence-parallel impls train (flash_attention carries a
            # custom FlashAttention-2 VJP, so ulysses differentiates
            # through its pallas kernel); plain "flash"/"reference" keep
            # the sequence resident per chip, which contradicts the sp
            # sharding this path exists for
            raise ValueError(
                f"fit_sharded supports attn_impl='ring' or 'ulysses'; got "
                f"{attn_impl!r}"
            )
        b, length = tokens.shape
        if b % mesh.shape["dp"] or (length - 1) % mesh.shape["sp"]:
            raise ValueError(
                f"batch {b} must divide by dp={mesh.shape['dp']} and "
                f"L-1={length - 1} by sp={mesh.shape['sp']}"
            )
        rep = NamedSharding(mesh, P())
        return self._sgd_loop(
            tokens,
            steps,
            lr,
            loss_kwargs=dict(
                attn_impl=attn_impl, mesh=mesh, batch_axis="dp"
            ),
            jit_kwargs=lambda p: dict(
                out_shardings=(jax.tree.map(lambda _: rep, p), None)
            ),
            place=lambda t: jax.device_put(
                t, NamedSharding(mesh, P("dp", None))
            ),
            resume=resume,
            checkpoint_every=checkpoint_every,
            on_step=on_step,
            # params are replicated in this plan; re-pin restored
            # committed leaves so the dp/sp step sees one device set
            place_restored=lambda p_: jax.tree.map(
                lambda a: jax.device_put(a, rep), p_
            ),
        )

    def fit_pipelined(
        self,
        tokens: np.ndarray,
        mesh,
        steps: int = 10,
        lr: float = 0.1,
        n_micro: int = 4,
        schedule: str = "1f1b",
        grad_accum: int = 1,
        resume=None,
        checkpoint_every=None,
        on_step=None,
    ):
        """SGD with the transformer BLOCKS pipelined over the mesh's ``pp``
        axis (one block per chip), composed with data parallelism when the
        mesh has a ``dp`` axis (microbatch rows sharded over it).
        ``resume``/``checkpoint_every``/``on_step``: auto-resume from a
        checkpoint directory (see :meth:`_sgd_loop`) — the checkpointed
        tree is the PIPELINE layout (stacked, ``pp``-sharded blocks).

        The embedding runs outside the pipeline and trains through the
        returned input cotangent; the loss head (final norm + tied
        unembedding + cross entropy) is FUSED into the last stage's
        backward (:func:`..parallel.pipeline.pipeline_train_step`).
        ``schedule``: ``'1f1b'`` (bounded activation memory, recompute in
        backward) or ``'gpipe'`` (autodiff through the forward schedule).
        ``grad_accum`` splits the batch into that many sequential
        sub-batches whose grads are averaged before the update — the
        activation-memory knob beyond microbatching.

        Requires ``len(blocks) == mesh.shape['pp']``, dense (non-MoE)
        blocks, ``batch/grad_accum`` divisible by ``n_micro`` (and the
        microbatch by the ``dp`` size when present)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.pipeline import pipeline_train_step

        if "pp" not in mesh.axis_names:
            raise ValueError(
                f"fit_pipelined needs a mesh with a 'pp' axis; got "
                f"{mesh.axis_names}"
            )
        batch_axis = "dp" if "dp" in mesh.axis_names else None
        blocks = self.params["blocks"]
        if any("moe" in blk for blk in blocks):
            raise ValueError(
                "fit_pipelined supports dense blocks; MoE blocks train "
                "on an ep mesh (see parallel.moe)"
            )
        if len(blocks) != mesh.shape["pp"]:
            raise ValueError(
                f"{len(blocks)} blocks but pp={mesh.shape['pp']}; the "
                f"pipeline stages one block per chip"
            )
        toks = np.asarray(tokens, dtype=np.int32)
        b, length = toks.shape
        if b % grad_accum or (b // grad_accum) % n_micro:
            raise ValueError(
                f"batch {b} must divide by grad_accum={grad_accum} and "
                f"then by n_micro={n_micro}"
            )
        n_heads = self.params["n_heads"]
        stage_fn = _pipe_stage_fn(n_heads)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
        stacked = jax.device_put(stacked, NamedSharding(mesh, P("pp")))
        p = {
            "embed": jnp.asarray(self.params["embed"]),
            "pos": jnp.asarray(self.params["pos"]),
            "ln_f": jax.tree.map(jnp.asarray, self.params["ln_f"]),
            "stacked": stacked,
        }
        sub = b // grad_accum
        Lm = length - 1

        def one_chunk(p_, chunk):
            ti = chunk[:, :-1]
            tgt = chunk[:, 1:]
            h0, h_vjp = jax.vjp(
                lambda e, po: e[ti] + po[:Lm][None], p_["embed"], p_["pos"]
            )
            loss, g_stacked, g_extra, dx = pipeline_train_step(
                stage_fn,
                _pipe_loss_fn,
                p_["stacked"],
                {"embed": p_["embed"], "ln_f": p_["ln_f"]},
                h0,
                tgt,
                n_micro=n_micro,
                mesh=mesh,
                batch_axis=batch_axis,
                schedule=schedule,
            )
            de_in, d_pos = h_vjp(dx)
            grads = {
                "embed": g_extra["embed"] + de_in,
                "pos": d_pos,
                "ln_f": g_extra["ln_f"],
                "stacked": g_stacked,
            }
            return loss, grads

        def step(p_, toks_):
            chunks = jnp.reshape(toks_, (grad_accum, sub, length))
            loss, grads = one_chunk(p_, chunks[0])
            for i in range(1, grad_accum):
                l2, g2 = one_chunk(p_, chunks[i])
                loss = loss + l2
                grads = jax.tree.map(jnp.add, grads, g2)
            inv = 1.0 / grad_accum
            new_p = jax.tree.map(
                lambda a, g: a - lr * (g * inv), p_, grads
            )
            return new_p, loss * inv

        step = jax.jit(step)

        def place_restored(p_):
            # restored leaves come back COMMITTED to a single device;
            # re-establish the pipeline placement (stacked slab over pp,
            # everything else replicated) or the jitted step sees mixed
            # device sets and refuses to compile
            rep = NamedSharding(mesh, P())
            return {
                "stacked": jax.device_put(
                    p_["stacked"], NamedSharding(mesh, P("pp"))
                ),
                **{
                    k: jax.tree.map(
                        lambda a: jax.device_put(a, rep), p_[k]
                    )
                    for k in ("embed", "pos", "ln_f")
                },
            }

        from ..utils.checkpoint import run_checkpointed_loop

        p, losses = run_checkpointed_loop(
            lambda p_: step(p_, toks),
            p,
            steps,
            resume=resume,
            checkpoint_every=checkpoint_every,
            on_step=on_step,
            place_restored=place_restored,
        )
        host = jax.device_get(p)
        n_layers = len(blocks)
        self.params = {
            "embed": host["embed"],
            "pos": host["pos"],
            "blocks": [
                jax.tree.map(lambda a: a[i], host["stacked"])
                for i in range(n_layers)
            ],
            "ln_f": host["ln_f"],
            "n_heads": n_heads,
        }
        return losses

    #: compiled decode programs kept per (shape, decode STRUCTURE); seeds,
    #: temperatures and top_p enter as traced arguments, so sweeps reuse
    #: one program. Bounded: oldest entry evicted beyond this.
    _GENERATE_CACHE_MAX = 16

    def generate(
        self,
        prompt,
        max_new_tokens: int,
        temperature: float = 0.0,
        seed: int = 0,
        moe_top_k: int = 1,
        top_k: int = 0,
        top_p: float = 1.0,
        prompt_lengths=None,
    ):
        """KV-cached autoregressive decode (:func:`transformer_generate`)
        as one jitted scan program, memoized per (prompt shape, decode
        STRUCTURE) in a bounded dict. The weights enter the program as an
        ARGUMENT, not as baked constants: a re-fit model reuses the same
        compiled program with its new params (nothing stale is pinned, no
        recompile). ``seed``, ``temperature`` and ``top_p`` are traced
        arguments too — sweeping them reuses ONE compiled program (greedy
        decodes ignore all three; they never enter the program).

        ``top_k`` / ``top_p`` truncate the sampling distribution (see
        :func:`filter_logits`). ``prompt_lengths`` enables ragged batches
        over LEFT-padded prompts (:func:`left_pad_prompts`)."""
        import jax

        prompt = np.asarray(prompt, dtype=np.int32)
        sampled = bool(temperature and temperature > 0)
        use_p = top_p is not None and top_p < 1.0
        ragged = prompt_lengths is not None
        if ragged:
            prompt_lengths = np.asarray(prompt_lengths, dtype=np.int32)
        key = (
            prompt.shape,
            int(max_new_tokens),
            sampled,
            int(top_k) if sampled else 0,
            use_p and sampled,
            int(moe_top_k),
            ragged,
        )
        cache = getattr(self, "_generate_cache", None)
        if cache is None:
            from collections import OrderedDict

            cache = self._generate_cache = OrderedDict()
        run = cache.get(key)
        if run is not None:
            cache.move_to_end(key)
        else:
            static = static_entries(self.params)

            def impl(p, prompt_arr, seed_arr, temp_arr, top_p_arr, lens):
                return transformer_generate(
                    {**p, **static},
                    prompt_arr,
                    max_new_tokens,
                    temperature=temp_arr if sampled else 0.0,
                    seed=seed_arr,
                    moe_top_k=moe_top_k,
                    top_k=top_k if sampled else 0,
                    top_p=top_p_arr if (sampled and use_p) else 1.0,
                    prompt_lengths=lens,
                )

            run = cache[key] = jax.jit(impl)
            while len(cache) > self._GENERATE_CACHE_MAX:
                cache.popitem(last=False)
        # one memoized device copy of the weights, replaced when fit
        # swaps the params object (the old copy is then collectable —
        # exactly one generation's weights are ever pinned)
        dev = getattr(self, "_generate_params", None)
        if dev is None or dev[0] is not self.params:
            dev = self._generate_params = (
                self.params,
                jax.device_put(device_tree(self.params)),
            )
        return np.asarray(
            run(
                dev[1],
                prompt,
                np.int32(seed),
                np.float32(temperature if sampled else 0.0),
                np.float32(top_p if use_p else 1.0),
                prompt_lengths,
            )
        )

    def score_frame(
        self,
        df,
        col: str,
        loss_col: str = "nll",
        attn_impl: str = "reference",
        moe_top_k: int = 1,
        moe_impl: str = "masked",
    ):
        """Per-row next-token NLL appended as a column: the transformer
        version of frozen-graph scoring through ``map_blocks``.

        Routing is call-time config, not stored in params: a model
        trained with ``moe_top_k=2`` must be SCORED with ``moe_top_k=2``
        or each token gets only its argmax expert — a different network
        than was trained."""
        import jax.numpy as jnp

        from ..engine import map_blocks

        # capture needs the concrete [L] cell shape (positional embeddings
        # are length-dependent); analyze is O(1) for dense columns
        df = df.analyze()
        params = self.params

        def fn(**cols):
            toks = cols[col].astype(jnp.int32)
            return {
                loss_col: token_nll(
                    params, toks, attn_impl=attn_impl,
                    moe_top_k=moe_top_k, moe_impl=moe_impl,
                ).mean(axis=-1)
            }

        import inspect

        fn.__signature__ = inspect.Signature(
            [inspect.Parameter(col, inspect.Parameter.POSITIONAL_OR_KEYWORD)]
        )
        return map_blocks(fn, df)
