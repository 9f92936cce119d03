"""Attention: online-softmax math + a Pallas TPU flash-attention kernel.

The reference has no attention or sequence axis at all (SURVEY §5: its only
scalable axis is rows). Long context is first-class here: this module is the
single-chip building block, and :mod:`tensorframes_tpu.ops.ring` scales the
sequence axis across chips with the same online-softmax update, so the two
compose into ring attention (blockwise parallel attention over a mesh).

Layout convention: ``[batch, heads, seq, head_dim]``.

The kernel tiles queries over the grid and streams key/value blocks through
an online-softmax accumulator (running max ``m``, normalizer ``l``, output
accumulator ``acc``) held in the loop carry — the standard FlashAttention
recurrence, shaped for the MXU: every contraction is a dense
``[block_q, d] x [d, block_k]`` / ``[block_q, block_k] x [block_k, d]``
matmul with ``preferred_element_type=f32``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.logging import get_logger

logger = get_logger("ops.attention")

__all__ = [
    "flash_attention",
    "attention_reference",
    "gather_pages",
    "paged_attention",
    "ragged_paged_attention",
    "paged_page_size_hint",
    "online_block_update",
    "flash_carry",
    "flash_bwd_pair",
]

_NEG_BIG = -0.7 * float(np.finfo(np.float32).max)  # mask value; exp() == 0
#: softmax runs in BASE 2 internally: s is pre-scaled by log2(e) (folded
#: into the existing qk scale multiply, so it costs nothing) and the
#: exponentials are bare exp2 — jnp.exp lowers to exp2(x * log2e) on TPU,
#: so this removes one full-tile VPU multiply per score element. The
#: probabilities 2^(s*log2e - m2) == e^(s - m) are IDENTICAL; only the
#: internal m/l/lse state lives in the scaled domain.
_LOG2E = float(np.log2(np.e))
#: log-sum-exp sentinel for rows that attend to nothing (causal with more
#: queries than keys): exp(s - _POS_BIG) underflows to exactly 0 for any
#: finite score, so the backward recomputation gives those rows p == 0
#: and zero gradient, matching the forward's zero output.
_POS_BIG = 0.7 * float(np.finfo(np.float32).max)


def _mxu_dtype(dt):
    """Matmul input dtype: low-precision inputs keep their native MXU mode
    (bf16/f16 run at the chip's high rate), everything else computes f32.
    Accumulation is always f32 via ``preferred_element_type``."""
    import jax.numpy as jnp

    return dt if dt in (jnp.bfloat16, jnp.float16) else jnp.float32


def _mxu_dot(a, b, contract, dt):
    """One kernel matmul: operands cast to the MXU input dtype ``dt``
    (:func:`_mxu_dtype`), contracting ``a``'s axis ``contract[0]`` with
    ``b``'s ``contract[1]``, f32 accumulation. Low-precision operands pin
    ``Precision.DEFAULT``: one pass already multiplies them exactly, and
    Mosaic rejects the fp32 contract precision that an ambient
    ``jax.default_matmul_precision("float32")`` would otherwise request
    for bf16 operands ("Bad lhs type"). f32 operands follow the ambient
    precision, so that context still buys true-f32 kernel math."""
    return jax.lax.dot_general(
        a.astype(dt),
        b.astype(dt),
        dimension_numbers=(((contract[0],), (contract[1],)), ((), ())),
        precision=None if dt == jnp.float32 else jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32,
    )


def online_block_update(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    m: jnp.ndarray,
    l: jnp.ndarray,
    acc: jnp.ndarray,
    scale: float,
    mask: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One online-softmax accumulation step over a key/value block.

    ``q``: [bq, d]; ``k``/``v``: [bk, d]; carry ``m``/``l``: [bq, 1],
    ``acc``: [bq, d] (all f32). ``mask``: optional [bq, bk] bool, True =
    attend. Fully-masked prefixes are handled: rows that have seen no valid
    key keep ``l == 0`` and contribute nothing. Shared verbatim by the
    Pallas kernel and the ring step so single-chip and distributed paths
    compute identically.

    The running max ``m`` lives in the BASE-2 domain (scores pre-scaled
    by log2(e); see ``_LOG2E``) — ``l``, ``acc``, and the finalized
    output are identical to the natural-base formulation.

    MXU precision follows the INPUT dtype: bf16/f16 q/k/v keep their
    matmuls in that dtype (the MXU's native high-rate mode; v5e runs bf16
    at ~4x its f32 rate) with ``preferred_element_type=f32`` so
    accumulation — and the whole softmax state — stays f32. f32 inputs
    compute exactly as before."""
    mxu_dt = _mxu_dtype(q.dtype)
    s = _mxu_dot(q, k, (1, 1), mxu_dt) * (scale * _LOG2E)
    if mask is not None:
        s = jnp.where(mask, s, _NEG_BIG)
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    # rows still fully masked keep m == _NEG_BIG; exp2(s - m) would be
    # exp2(0) = 1 for masked entries, so re-mask p explicitly
    p = jnp.exp2(s - m_new)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp2(m - m_new)
    l_new = alpha * l + p.sum(axis=-1, keepdims=True)
    pv_dt = _mxu_dtype(v.dtype)
    acc_new = alpha * acc + _mxu_dot(p, v, (1, 0), pv_dt)
    return m_new, l_new, acc_new


def _finalize(l: jnp.ndarray, acc: jnp.ndarray) -> jnp.ndarray:
    return acc / jnp.maximum(l, 1e-30)


def _lse_sentinel(m: jnp.ndarray, l: jnp.ndarray) -> jnp.ndarray:
    """Per-row log-sum-exp saved for the backward — in the BASE-2 domain
    (``m`` is the base-2 running max, so this is ``log2(sum exp)``;
    ``_bwd_tile_terms`` recomputes p with exp2 against it) — with the
    ``_POS_BIG`` sentinel on rows that attended to nothing (so the
    backward recomputes p == 0 and zero gradient there). The single
    source of this convention — the flash kernel's emit and the ring
    forward both use it; the backward's empty-row guarantee depends on
    them being bit-identical."""
    return jnp.where(
        l > 0.0, m + jnp.log2(jnp.maximum(l, 1e-30)), _POS_BIG
    )


#: measured-best (block_q, block_k) per (dtype kind, head_dim bucket,
#: min seq len) on v5e in the r04/r05 sweeps (records removed from the
#: tree in PR 21; `git show 742ccc6:BENCH_ALL_r04.json`). Not re-timed on
#: the current machine; every entry compiles under libtpu 0.0.34's 16 MB
#: scoped VMEM at default matmul precision (chip_smoke.py runs them).
#: 1024x1024 won every measured combo — bigger tiles (2048+) exceed VMEM
#: and fail to compile, 512-wide tiles lose 3-10% to per-tile overhead:
#:   bf16 D=128: L=8k 118.8 TF/s, L=16k 129.3, L=32k 127.5 (vs 100-117
#:   for 512x1024 / 1024x2048); bf16 D=64: 55-56 TF/s (half-width MXU
#:   contraction); f32 D=128: same ordering (f32 inputs ride the MXU's
#:   default bf16 pass, so tile behavior tracks bf16). Sweep predates
#:   the base-2 softmax (which lifted all rows ~4-6% uniformly; tile
#:   ordering unchanged).
#: The table keys exist so future chips/dtypes can diverge without an
#: API change; the lookup picks the largest-L entry <= L.
_BEST_BLOCKS = {
    # (is_lowp, d_bucket): [(min_L, (block_q, block_k)), ...] descending
    (True, 128): [(0, (1024, 1024))],
    (True, 64): [(0, (1024, 1024))],
    (False, 128): [(0, (1024, 1024))],
    (False, 64): [(0, (1024, 1024))],
}


def _static_best_blocks(dtype, d, l):
    """The measured-best table lookup alone (no tuner): the seed prior
    for the autotuner, and what ``paged_page_size_hint`` reads — the
    hint wants the table's block_k, which the tuning grid never varies,
    so consulting the tuner there would only burn a trial budget."""
    is_lowp = dtype in (jnp.bfloat16, jnp.float16)
    d_bucket = 128 if d > 64 else 64
    rows = _BEST_BLOCKS[(is_lowp, d_bucket)]
    static = rows[-1][1]
    for min_l, blocks in sorted(rows, reverse=True):
        if l >= min_l:
            static = blocks
            break
    return is_lowp, d_bucket, static


def _best_blocks(dtype, d, l):
    """Kernel tiles for this (dtype, head_dim, L): the autotuner's
    winner when one is installed (``tensorframes_tpu.tune``, surface
    ``flash.tiles``), else the measured-best static table
    ``_BEST_BLOCKS`` — which doubles as the tuner's seed prior (the
    default candidate every trial set measures first). Callers may
    always override explicitly."""
    is_lowp, d_bucket, static = _static_best_blocks(dtype, d, l)
    return _tuned_flash_blocks(is_lowp, d_bucket, l, static)


#: trial sequence cap: long-L signatures micro-benchmark at this length
#: (tile behavior is L-stable past a few k and interpret-mode trials on
#: CPU must stay sub-second); the WINNER still installs for the real L
#: bucket
_FLASH_TRIAL_L_CAP = 512


def _tuned_flash_blocks(is_lowp, d_bucket, l, static):
    """Consult the autotuner for the flash forward tiles.

    The candidate grid varies **block_q only**: the q tile sets grid
    parallelism and VMEM residency but leaves every query row's k-axis
    accumulation untouched, so each candidate is byte-identical to the
    static default — the tuning contract (docs/tuning.md). ``block_k``
    changes the online-softmax grouping (float associativity) and
    therefore stays at the table's measured value."""
    from .. import tune

    if tune.mode() == "off":
        return static
    sq, sk = static
    lb = 1 << max(7, (int(l) - 1).bit_length())  # pow2 bucket, >= 128
    sig = f"lowp={int(is_lowp)}|d={d_bucket}|L={lb}"
    default = {"block_q": int(sq), "block_k": int(sk)}
    lt = min(lb, _FLASH_TRIAL_L_CAP)
    # the default is measured CLAMPED to the trial length too, so any
    # candidate whose clamped trial equals the clamped default's would
    # run a byte-identical micro-benchmark — a coin-flip winner that
    # would then persist fleet-wide. Exclude by effective trial tile.
    eff_default = _fit_tile(min(int(sq), lt), lt)
    seen_eff = {eff_default}
    grid = []
    for bq in (256, 512, 1024, 2048):
        if bq > lt:
            # beyond trial fidelity: a candidate wider than the trial
            # sequence would measure identically to another clamped one
            # and the winner among them would be timing noise — only
            # offer what the micro-benchmark can genuinely distinguish
            continue
        fq = _fit_tile(bq, lb)
        if fq is None:
            continue
        eff = _fit_tile(min(int(fq), lt), lt)
        if eff in seen_eff:
            continue
        seen_eff.add(eff)
        cand = {"block_q": int(fq), "block_k": int(sk)}
        if cand != default:
            grid.append(cand)

    def feats(cand):
        # one forward tile does ~4*bq*bk*d MXU flops (qk^T + pv) and
        # touches the q/k/v/o tiles; tiles-per-sequence is the dispatch
        # count the overhead weight prices
        bq = min(cand["block_q"], lt)
        bk = min(cand["block_k"], lt)
        itemsize = 2 if is_lowp else 4
        tiles = max(1, lt // bq) * max(1, lt // bk)
        flops = 4.0 * bq * bk * d_bucket * tiles
        nbytes = (2 * bq + 2 * bk) * d_bucket * itemsize * tiles
        return flops, nbytes, tiles

    def trial(cand):
        rng = np.random.default_rng(0)
        dt = jnp.bfloat16 if is_lowp else jnp.float32
        q = jnp.asarray(
            rng.normal(size=(1, 1, lt, d_bucket)).astype(np.float32), dt
        )
        k = jnp.asarray(
            rng.normal(size=(1, 1, lt, d_bucket)).astype(np.float32), dt
        )
        v = jnp.asarray(
            rng.normal(size=(1, 1, lt, d_bucket)).astype(np.float32), dt
        )
        jax.block_until_ready(
            flash_attention(
                q, k, v,
                block_q=min(cand["block_q"], lt),
                block_k=min(cand["block_k"], lt),
            )
        )

    try:
        win = tune.lookup(
            "flash.tiles", sig, default, grid=grid, feats=feats,
            trial=trial,
        )
        bq, bk = int(win["block_q"]), int(win["block_k"])
        if bq >= 1 and bk >= 1:
            return (bq, bk)
    except Exception:
        logger.warning(
            "flash tile tuning lookup failed; using the static table",
            exc_info=True,
        )
    return static


def _check_tiles(block_q, lq, block_k, lk):
    """The public kernel entry points floor-divide the grid; a block that
    does not divide its sequence would silently drop the tail rows."""
    if lq % block_q or lk % block_k:
        raise ValueError(
            f"block sizes ({block_q}, {block_k}) must divide the sequence "
            f"lengths ({lq}, {lk}); see _fit_tile / flash_attention for "
            f"automatic fitting"
        )


def attention_reference(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, causal: bool = False
) -> jnp.ndarray:
    """Dense softmax attention oracle, [B, H, L, D] layout."""
    scale = 1.0 / float(np.sqrt(q.shape[-1]))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        lq, lk = q.shape[2], k.shape[2]
        qi = jnp.arange(lq)[:, None] + (lk - lq)
        ki = jnp.arange(lk)[None, :]
        valid = qi >= ki
        s = jnp.where(valid, s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    if causal:
        # lq > lk leaves early rows with no visible key at all; the kernels
        # return zeros for such rows (l == 0 finalize), so the oracle must
        # too rather than softmax-averaging over the mask fill
        p = jnp.where(valid.any(axis=-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(
        q.dtype
    )


def _check_paged_inputs(q, k_pages, v_pages, page_table, lengths, layer):
    """Shared validation for the paged decode reads (gather and fused).

    The position mask is ``arange(T) < lengths`` and the gather indexes
    with ``page_table`` directly, so a wrong dtype does not fail — it
    silently miscomputes (a float ``lengths`` compares almost-equal, an
    int64 table under x64 re-traces to a different layout). Serving
    correctness rides on these being right, so reject loudly at trace
    time instead."""
    if np.ndim(q) != 4:
        raise ValueError(
            f"q must be [slots, n_kv, group, head_dim]; got shape "
            f"{np.shape(q)}"
        )
    slots, n_kv, _, hd = np.shape(q)
    want_nd = 3 if layer is None else 4
    for name, arr in (("k_pages", k_pages), ("v_pages", v_pages)):
        if np.ndim(arr) != want_nd:
            raise ValueError(
                f"{name} must be [pool_pages, page_size, n_kv * head_dim]"
                f" (with a leading layer axis when layer= is given); got "
                f"shape {np.shape(arr)} and layer={layer!r}"
            )
    if np.shape(k_pages) != np.shape(v_pages):
        raise ValueError(
            f"k_pages and v_pages must share a shape; got "
            f"{np.shape(k_pages)} vs {np.shape(v_pages)}"
        )
    if np.shape(k_pages)[-1] != n_kv * hd:
        raise ValueError(
            f"page pool rows hold n_kv * head_dim = "
            f"{np.shape(k_pages)[-1]} lanes but q asks for "
            f"(n_kv={n_kv}, head_dim={hd}) = {n_kv * hd}"
        )
    if np.ndim(page_table) != 2 or np.shape(page_table)[0] != slots:
        raise ValueError(
            f"page_table must be [slots={slots}, max_pages]; got shape "
            f"{np.shape(page_table)}"
        )
    if np.shape(lengths) != (slots,):
        raise ValueError(
            f"lengths must be [slots={slots}]; got shape "
            f"{np.shape(lengths)}"
        )
    for name, arr in (("page_table", page_table), ("lengths", lengths)):
        dt = np.dtype(getattr(arr, "dtype", None) or np.asarray(arr).dtype)
        if dt != np.dtype(np.int32):
            raise ValueError(
                f"{name} must be int32 (got {dt}): the position mask and "
                f"the page gather consume it as-is, and a wrong dtype "
                f"miscomputes silently — cast with .astype(np.int32)"
            )


def _own_lanes(n_kv: int, hd: int):
    """``[n_kv, n_kv * hd]`` bool: lane ``c`` of a merged k/v row belongs
    to KV head ``c // hd``."""
    return (
        jnp.arange(n_kv * hd)[None, :] // hd == jnp.arange(n_kv)[:, None]
    )


def _heads_on_lanes(q):
    """``q`` [S, n_kv, group, hd] -> [S, n_kv * group, n_kv * hd]: each
    head's query laid over its KV head's lanes of a merged row, zero
    elsewhere. A product of this with merged rows IS the per-head score
    (the zeros add nothing), computed without ever splitting the rows'
    ``n_kv * hd`` lanes back into heads — which on the TPU would
    re-tile the whole gathered block (``serve/kv_pages.py``)."""
    slots, n_kv, group, hd = q.shape
    own = _own_lanes(n_kv, hd)[None, :, None, :]
    wide = jnp.where(own, jnp.tile(q, (1, 1, 1, n_kv)), 0.0)
    return wide.reshape(slots, n_kv * group, n_kv * hd)


def _heads_off_lanes(out, n_kv: int, hd: int):
    """The inverse read: ``out`` [S, n_kv * group, n_kv * hd] holds each
    head's context over EVERY lane; keep the head's own ``hd`` lanes ->
    [S, n_kv, group, hd]."""
    slots = out.shape[0]
    out = out.reshape(slots, n_kv, -1, n_kv, hd)
    own = jnp.eye(n_kv, dtype=bool)[None, :, None, :, None]
    return jnp.where(own, out, 0.0).sum(axis=3)


def gather_pages(pages, page_table, layer=None):
    """The pages ``page_table`` ``[..., max_pages]`` names, in position
    order: ``[..., max_pages * page_size, n_kv * hd]`` from ``pages``
    ``[pool_pages, page_size, n_kv * hd]``, or from layer ``layer`` of a
    whole pool ``[n_layers, pool_pages, ...]``. The layer index rides in
    the gather's own indices (``pages[layer][table]`` would first copy
    the layer's slice out of the pool), and merging ``(max_pages,
    page_size)`` is free because ``page_size`` is the sublane axis."""
    g = pages[page_table if layer is None else (layer, page_table)]
    lead = page_table.shape[:-1]
    return g.reshape(lead + (g.shape[-3] * g.shape[-2], g.shape[-1]))


def paged_attention(q, k_pages, v_pages, page_table, lengths, layer=None):
    """Single-token attention read over a PAGED KV cache — the decode-side
    gather for the serving engine (:mod:`tensorframes_tpu.serve`), where
    each sequence's keys/values live in fixed-size pages scattered through
    a static pool instead of one contiguous cache row.

    ``q`` [S, n_kv, group, hd] — one query token per slot, grouped-query
    layout (``group = n_heads / n_kv``; 1-sized slot batches and MHA both
    degenerate cleanly). ``k_pages``/``v_pages`` [pool_pages, page_size,
    n_kv * hd] — the shared page pool in the layout
    ``serve/kv_pages.py`` defines: a token's k (or v) is ONE row with the
    heads merged into the lane axis, because the TPU tiles an array's two
    minor dimensions in (8, 128) and (n_kv, hd) = (25, 64) minor would
    pad 2.56x or force a layout every access has to convert. With
    ``layer=li`` the arrays are the engine's whole pool ``[n_layers,
    pool_pages, page_size, n_kv * hd]`` and the read addresses layer
    ``li`` inside the gather (slicing the layer out first would copy it).
    ``page_table`` [S, max_pages] int32 — each slot's ordered page list
    (entries past the sequence's live pages may point anywhere valid; the
    position mask excludes them). ``lengths`` [S] int32 — valid positions
    per slot, INCLUDING the token just written.

    Every shape is static: the gather reads ``max_pages * page_size``
    positions per slot and masks ``t >= lengths`` to ``_NEG_BIG`` before
    the softmax (masked lanes underflow to exactly 0), so one compiled
    program serves every mix of sequence lengths and slot turnover — the
    no-recompile property continuous batching depends on.

    The products run ON the merged lane axis: the query is laid over its
    head's lanes (:func:`_heads_on_lanes`), ``scores[s, h, t] = sum_c
    Q[s, h, c] * K[s, t, c]`` contracts all ``n_kv * hd`` lanes, the
    context product yields every lane for every head and each head keeps
    its own. The extra terms are exact zeros, so per head this is the
    sum the dense decode-cache read in
    ``models.transformer.transformer_generate`` computes (same operands,
    same rounding of them, same mask value) and the two agree to float
    associativity; the gathered block is consumed in the order the
    gather wrote it. Precision follows the ambient matmul precision,
    like every other product of the step. Returns [S, n_kv, group, hd].

    This is the REFERENCE formulation: it materializes two
    ``[S, max_pages * page_size, n_kv * hd]`` gathered copies per call,
    so a ragged batch pays max-length bandwidth for every slot.
    :func:`ragged_paged_attention`, the serving engine's decode read,
    copies each slot's live pages instead; this gather is its oracle."""
    _check_paged_inputs(q, k_pages, v_pages, page_table, lengths, layer)
    slots, n_kv, group, hd = q.shape
    # [S, T, n_kv*hd]: pages in table order ARE position order (page i
    # holds positions i*ps..(i+1)*ps-1)
    kg = gather_pages(k_pages, page_table, layer)
    vg = gather_pages(v_pages, page_table, layer)
    t = kg.shape[1]
    scale = 1.0 / float(np.sqrt(hd))
    s = jnp.einsum("bhc,btc->bht", _heads_on_lanes(q), kg) * scale
    visible = jnp.arange(t)[None, :] < lengths[:, None]  # [S, T]
    s = jnp.where(visible[:, None, :], s, _NEG_BIG)
    out = jnp.einsum("bht,btc->bhc", jax.nn.softmax(s, axis=-1), vg)
    return _heads_off_lanes(out, n_kv, hd)


#: table rows :func:`paged_attention_live` walks at a time, about
LIVE_BLOCK_PAGES = 64

#: slots that share one trip count in :func:`paged_attention_live`'s
#: single-query walk of a table of several blocks
LIVE_GROUP_SLOTS = 8


def live_read_blocks(rows: int, block_pages: int = LIVE_BLOCK_PAGES):
    """``(blocks, rows per block)`` of a ``rows``-row page table under
    :func:`paged_attention_live`: blocks of about ``block_pages`` rows,
    all alike — a window's table (66 rows at a window of 1,024 and pages
    of 16) is one block."""
    n_blocks = max(1, rows // int(block_pages))
    return n_blocks, -(-rows // n_blocks)


def live_read_group(slots: int) -> int:
    """Slots that walk together under :func:`paged_attention_live`:
    :data:`LIVE_GROUP_SLOTS` where ``slots`` is several whole groups of
    that many, else all of them (one group: one trip count for the
    batch)."""
    group = LIVE_GROUP_SLOTS
    return group if slots > group and slots % group == 0 else slots


def live_read_trips(live, span: int, n_blocks: int, group: int):
    """``(order, trips)`` of the walk over slots holding ``live`` keys
    past their table's first row (``[S]`` integers, any order; a numpy
    array on the host, a traced one inside the program — the same
    arithmetic counts what the program reads): ``order`` ``[S]`` lists
    the slots longest first, slots ``order[g * group:(g + 1) * group]``
    are group ``g``, and ``trips[g]`` is the blocks of ``span`` positions
    that group walks — up to the one that holds its own longest slot's
    last key, at least one, at most the table's ``n_blocks``. The walk
    reads ``trips.sum() * group * span`` positions."""
    xp = np if isinstance(live, np.ndarray) else jnp
    order = xp.argsort(-live)
    lead = live[order[::group]]  # ordered: a group's first is its longest
    return order, xp.clip(-(-lead // span), 1, n_blocks)


def live_read_positions(lengths, slots: int, rows: int, page_size: int) -> int:
    """Key positions a layer's single-query walk of a ``rows``-row table
    touches for a batch of ``slots`` slots of which ``lengths`` hold
    that many keys and the others are idle (one key, the trash page):
    the host's account of what :func:`paged_attention_live` reads, by
    the arithmetic its trip counts come from."""
    n_blocks, bp = live_read_blocks(rows)
    together = live_read_group(slots)
    live = np.ones(slots, np.int64)
    live[: len(lengths)] = lengths
    _, trips = live_read_trips(live, bp * page_size, n_blocks, together)
    return int(trips.sum()) * together * bp * page_size


def paged_attention_live(
    q, k_pages, v_pages, table, first, q_pos, lengths, layer,
    window: int = 0, block_pages: int = LIVE_BLOCK_PAGES,
):
    """The paged read bounded by what is live, for one query per slot or
    a span of them: attention of ``q`` ``[S, C, n_kv, group, hd]`` at
    absolute positions ``q_pos`` ``[S, C]`` over the keys each slot's
    ``table`` ``[S, T]`` names in layer ``layer`` of the whole pool
    ``[n_layers, pool_pages, page_size, n_kv * hd]``.

    Row ``r`` of a slot's table is its position-page ``first[s] + r``
    (``first`` ``[S]``: 0 for a kind that keeps every page, the first
    page still held for a window kind, whose table is as wide as the
    window and no wider); key ``j`` of slot ``s`` exists iff ``j <
    lengths[s]`` and is visible to the query at ``p`` iff ``j <= p``
    and, with a ``window``, ``p - window < j``.

    The table is walked ``block_pages`` rows at a time under the
    online-softmax recurrence, and the trip counts are data, so one
    program serves every mix of lengths. With one query a slot and
    several whole groups of :data:`LIVE_GROUP_SLOTS` slots
    (:func:`live_read_group`) the slots are ordered by live length on
    the device, longest first, and each group of neighbours walks to the
    block that holds ITS longest slot's last live key
    (:func:`live_read_trips`): one loop over the ``(group, block)``
    pairs that hold a live key, each trip gathering its group's rows of
    the table and folding them into that group's rows of the carry; the
    result goes back through the inverse of the order. A slot's own
    blocks are visited in the same order with the same operands
    whoever shares its group, and a block past its end adds exact
    zeros, so its result does not depend on its neighbours. One query a
    slot in fewer slots than two groups, or over a table of one block,
    takes one trip count for the batch, the longest slot's. A span of
    queries (``C > 1``: a prefill chunk) takes one trip count too
    (:func:`live_span_trips`) and folds each gathered block into its
    carry with ONE fused kernel (:func:`live_span_fold`): a score tile,
    its mask, max, exponentials, sum and the product with V live in
    fast memory and only the carry crosses HBM between blocks; its walk
    is a jitted callee of its own, which takes the pool and ``layer``
    as arguments, so a program of many layers traces and lowers it once
    per cache kind (``window`` and the table's width), not once per
    layer. Either way a step moves the K and V of about the pages that
    are live (rows past a slot's own end name the trash page: one page,
    read again and again, and masked) instead of ``max_seq_len``
    positions for every slot. No ``[S, T * page_size]`` copy of a
    slot's whole table is ever made, and the scores of a span exist one
    tile at a time. Scores, softmax and the accumulator are float32
    whatever the pool holds. Returns ``[S, C, n_kv * group * hd]``
    float32."""
    slots, c, n_kv, group, hd = q.shape
    if c > 1:
        return _live_span_walk(
            q, k_pages, v_pages, table, first, q_pos, lengths, layer,
            window=window, block_pages=block_pages,
        )
    ps = k_pages.shape[-2]
    table, n_blocks, bp = _whole_blocks(table, block_pages)
    scale = 1.0 / float(np.sqrt(hd))
    span = bp * ps
    in_block = jnp.arange(span, dtype=jnp.int32)
    # the products run ON the rows' merged lanes, the query laid over
    # its head's lanes (``paged_attention``'s form: all heads against a
    # slot's block in one MXU product, and the gathered block is never
    # re-tiled from 512 lanes into 4 x 128)
    qc = _heads_on_lanes(q[:, 0]).astype(k_pages.dtype)  # [S, H, lanes]

    def block(j, carry, qc, table, first, q_pos, lengths):
        """Fold block ``j`` of these slots' tables into their carry."""
        m, l, acc = carry
        slots = table.shape[0]
        rows = jax.lax.dynamic_slice_in_dim(table, j * bp, bp, axis=1)
        kb = k_pages[layer, rows].reshape(slots, span, n_kv * hd)
        vb = v_pages[layer, rows].reshape(slots, span, n_kv * hd)
        k_pos = (first[:, None] + j * bp) * ps + in_block[None, :]  # [S, T]
        s = jnp.einsum(
            "shc,stc->sht", qc, kb, preferred_element_type=jnp.float32
        ).reshape(slots, 1, n_kv, group, span) * scale
        seen = (k_pos[:, None, :] <= q_pos[:, :, None]) & (
            k_pos < lengths[:, None]
        )[:, None, :]
        if window:
            seen &= k_pos[:, None, :] > q_pos[:, :, None] - window
        seen = seen[:, :, None, None, :]
        m_new = jnp.maximum(
            m, jnp.max(jnp.where(seen, s, _NEG_BIG), axis=-1)
        )
        p = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(axis=-1)
        # every lane for every head; each keeps its own
        ctx = _heads_off_lanes(
            jnp.einsum(
                "sht,stc->shc",
                p.reshape(slots, n_kv * group, span).astype(vb.dtype),
                vb, preferred_element_type=jnp.float32,
            ),
            n_kv, hd,
        )[:, None]
        return m_new, l, alpha[..., None] * acc + ctx

    stat = (slots, c, n_kv, group)
    carry = (
        jnp.full(stat, _NEG_BIG, jnp.float32),
        jnp.zeros(stat, jnp.float32),
        jnp.zeros(stat + (hd,), jnp.float32),
    )
    per_slot = (qc, table, first, q_pos, lengths)
    together = live_read_group(slots) if n_blocks > 1 else slots
    order = None  # the caller's slot numbering
    if n_blocks == 1:
        carry = block(0, carry, *per_slot)
    elif together == slots:
        trips = live_span_trips(lengths - first * ps, span, n_blocks)
        carry = jax.lax.fori_loop(
            0, trips, lambda j, carry: block(j, carry, *per_slot), carry
        )
    else:
        order, trips = live_read_trips(
            lengths - first * ps, span, n_blocks, together
        )
        per_slot = tuple(x[order] for x in per_slot)
        ends = jnp.cumsum(trips)

        def pair(i, carry):
            """Trip ``i`` is block ``i - (trips before group g)`` of the
            group ``g`` whose trips hold it."""
            g = jnp.sum(i >= ends)
            at = g * together

            def take(x):
                return jax.lax.dynamic_slice_in_dim(x, at, together)

            folded = block(
                i - (ends[g] - trips[g]),
                tuple(take(x) for x in carry),
                *(take(x) for x in per_slot),
            )
            return tuple(
                jax.lax.dynamic_update_slice_in_dim(x, y, at, 0)
                for x, y in zip(carry, folded)
            )

        carry = jax.lax.fori_loop(0, ends[-1], pair, carry)
    _, l, acc = carry
    out = acc / jnp.where(l > 0, l, 1.0)[..., None]
    if order is not None:  # back to the caller's slot numbering
        out = out[jnp.argsort(order)]
    return out.reshape(slots, c, n_kv * group * hd)


def _whole_blocks(table, block_pages: int):
    """``(table, blocks, rows per block)``: the table padded to whole
    blocks of :func:`live_read_blocks` (the tail rows name nothing)."""
    t = table.shape[1]
    n_blocks, bp = live_read_blocks(t, block_pages)
    if n_blocks * bp != t:
        table = jnp.pad(
            table, ((0, 0), (0, n_blocks * bp - t)), mode="edge"
        )
    return table, n_blocks, bp


#: the span fold's name in a device trace
LIVE_SPAN_KERNEL = "live_span_fold"

#: bytes of one head group's accumulator tile in :func:`live_span_fold`
#: (256 queries at 8 heads of 128 lanes): with a score tile of as many
#: rows the kernel's buffers stay well inside 16 MB of fast memory
_SPAN_TILE_BYTES = 1 << 20

#: most keys a grid step of :func:`live_span_fold` takes
_SPAN_KEY_TILE = 2048


def live_span_trips(live, span: int, n_blocks: int):
    """Blocks of ``span`` positions that a walk with one trip count for
    its slots (a span of queries; one query a slot in a single group)
    visits of a table of ``n_blocks`` blocks whose slots hold ``live``
    keys past their first row (``[S]`` integers; a numpy array on the
    host, a traced one inside the program — the same arithmetic counts
    what the program visits): up to the block that holds the longest
    slot's last key, at least one."""
    xp = np if isinstance(live, np.ndarray) else jnp
    return xp.clip(-(-xp.max(live) // span), 1, n_blocks)


def _span_tile(length: int, cap: int) -> int:
    """Rows a grid step of :func:`live_span_fold` takes of ``length``:
    the largest divisor of it within ``cap`` that is whole sublane tiles
    (16 rows: a bfloat16 tile); all of it when it has none."""
    fits = [t for t in range(16, min(length, cap) + 1, 16) if length % t == 0]
    return max(fits) if fits else length


def _live_span_kernel(
    k0_ref, len_ref, qlo_ref, qhi_ref,
    q_ref, qpos_ref, k_ref, v_ref, m_in, l_in, acc_in,
    m_out, l_out, acc_out,
    *, group, hd, window, scale,
):
    """Grid = (slots, query tiles, K/V heads, key tiles), the keys
    innermost: one step folds ``[tk, hd]`` keys and values of K/V head
    ``n`` into the carry of that head's ``group`` query heads for one
    tile of queries, one head a turn of a loop (rolled: the body is
    traced and lowered once, which is most of what a kernel costs a
    program's set-up), through :func:`online_block_update` (the flash
    kernels' recurrence). The query tile ``[tq, group * hd]`` and its
    accumulator are the ``n``-th lane block of their rows, the K and V
    tile the ``n``-th ``hd`` lanes of the gathered rows — picked by the
    block index, nothing is re-tiled. The accumulator tile stays
    resident across a head's key tiles and ``m`` and ``l`` ``[tq,
    heads]`` across a query tile's heads; a head's column goes in and
    out by a lane mask.

    The mask comes from positions: key ``t`` of the block sits at
    ``k0 + t``, visible to the query at ``q_pos`` iff it is at or
    before it, exists (``< length``) and, with a window, lies inside
    it. Three regimes a step, decided from scalars (the key tile's first
    position, the slot's length, the query tile's lowest and highest
    position): no query of the tile sees any key (nothing to do: the
    masked fold would add exact zeros), every query sees every key (no
    mask work), else the masked fold."""
    from jax.experimental import pallas as pl

    si, qi, n, ki = (pl.program_id(a) for a in range(4))
    tq, tk = q_ref.shape[1], k_ref.shape[1]
    heads = m_in.shape[2]

    @pl.when(jnp.logical_and(n == 0, ki == 0))
    def _():
        m_out[...] = m_in[...]
        l_out[...] = l_in[...]

    @pl.when(ki == 0)
    def _():
        acc_out[...] = acc_in[...]

    k_lo = k0_ref[si] + ki * tk
    k_hi = k_lo + (tk - 1)
    length = len_ref[si]
    q_lo, q_hi = qlo_ref[si, qi], qhi_ref[si, qi]
    visible = jnp.logical_and(k_lo <= q_hi, k_lo < length)
    interior = jnp.logical_and(k_hi <= q_lo, k_hi < length)
    if window:
        visible = jnp.logical_and(visible, k_hi > q_lo - window)
        interior = jnp.logical_and(interior, k_lo > q_hi - window)

    def fold(with_mask):
        mask = None
        if with_mask:
            k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
            q_pos = qpos_ref[0]  # [tq, 1]
            mask = jnp.logical_and(k_pos <= q_pos, k_pos < length)
            if window:
                mask = jnp.logical_and(mask, k_pos > q_pos - window)
        head = jax.lax.broadcasted_iota(jnp.int32, (tq, heads), 1)
        kj, vj = k_ref[0], v_ref[0]

        def member(g, stats):
            m_all, l_all = stats
            mine = head == n * group + g
            lanes = pl.ds(pl.multiple_of(g * hd, 128), hd)
            m, l, acc = online_block_update(
                q_ref[0, :, lanes], kj, vj,
                jnp.sum(jnp.where(mine, m_all, 0.0), axis=1, keepdims=True),
                jnp.sum(jnp.where(mine, l_all, 0.0), axis=1, keepdims=True),
                acc_out[0, :, lanes], scale, mask,
            )
            acc_out[0, :, lanes] = acc
            return jnp.where(mine, m, m_all), jnp.where(mine, l, l_all)

        m_out[0], l_out[0] = jax.lax.fori_loop(
            0, group, member, (m_out[0], l_out[0])
        )

    @pl.when(interior)
    def _():
        fold(with_mask=False)

    @pl.when(jnp.logical_and(visible, jnp.logical_not(interior)))
    def _():
        fold(with_mask=True)


def live_span_fold(
    q, q_pos, kb, vb, k0, lengths, m, l, acc, *, n_kv: int, window: int = 0,
    scale: Optional[float] = None, interpret: Optional[bool] = None,
):
    """Fold one gathered block of keys and values into the
    online-softmax carry of a span of queries, in one fused kernel.

    ``q`` ``[S, C, heads * hd]`` (the pool's dtype; head ``h`` = K/V
    head ``h // group`` x group member ``h % group`` on lanes ``h * hd
    ..``) at positions ``q_pos`` ``[S, C]`` int32; ``kb`` / ``vb`` ``[S,
    span, n_kv * hd]`` as gathered, key ``t`` of slot ``s`` at position
    ``k0[s] + t`` and real iff below ``lengths[s]`` (``k0``, ``lengths``
    ``[S]`` int32). The carry is float32 and dense on the lanes: ``m``
    (running max, base-2 domain like every carry of
    :func:`online_block_update`) and ``l`` ``[S, C, heads]``, ``acc``
    ``[S, C, heads * hd]``; start from ``m = _NEG_BIG``, ``l = acc =
    0`` and finish as ``acc / l``. Returns the updated ``(m, l, acc)``
    in the carry's own buffers. ``hd`` must be whole 128-lane tiles
    (the caller pads narrower heads); ``scale`` defaults to ``1 /
    sqrt(hd)``. ``interpret`` defaults to True off the TPU, so the CPU
    tests run the kernel itself."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, c, width = q.shape
    span = kb.shape[1]
    hd = kb.shape[2] // n_kv
    heads = width // hd
    group = heads // n_kv
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    tq = _span_tile(c, max(16, _SPAN_TILE_BYTES // (4 * group * hd)))
    tk = _span_tile(span, _SPAN_KEY_TILE)
    tiles = q_pos.reshape(slots, c // tq, tq)
    kernel = functools.partial(
        _live_span_kernel, group=group, hd=hd, window=window,
        scale=1.0 / float(np.sqrt(hd)) if scale is None else scale,
    )
    q_spec = pl.BlockSpec(
        (1, tq, group * hd), lambda s, i, n, j, *_: (s, i, n)
    )
    kv_spec = pl.BlockSpec((1, tk, hd), lambda s, i, n, j, *_: (s, j, n))
    stat_spec = pl.BlockSpec(
        (1, tq, heads), lambda s, i, n, j, *_: (s, i, 0)
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(slots, c // tq, n_kv, span // tk),
            in_specs=[
                q_spec,
                pl.BlockSpec((1, tq, 1), lambda s, i, n, j, *_: (s, i, 0)),
                kv_spec, kv_spec, stat_spec, stat_spec, q_spec,
            ],
            out_specs=[stat_spec, stat_spec, q_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(m.shape, jnp.float32),
            jax.ShapeDtypeStruct(l.shape, jnp.float32),
            jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        ],
        # the carry is updated in place (operands count the scalars)
        input_output_aliases={8: 0, 9: 1, 10: 2},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "arbitrary", "arbitrary",
            )
        ),
        interpret=interpret,
        name=LIVE_SPAN_KERNEL,
    )(
        k0, lengths, tiles.min(axis=-1), tiles.max(axis=-1),
        q, q_pos[..., None], kb, vb, m, l, acc,
    )


@functools.partial(jax.jit, static_argnames=("window", "block_pages"))
def _live_span_walk(
    q, k_pages, v_pages, table, first, q_pos, lengths, layer, *,
    window, block_pages,
):
    """:func:`paged_attention_live` for a span of queries: the walk of
    the table's blocks with one trip count (:func:`live_span_trips`),
    each block gathered by XLA as the rows lie and folded into the carry
    by :func:`live_span_fold`. Jitted on its own with the pool and
    ``layer`` as operands: every layer of one cache kind in a step
    program is the same callee, traced and lowered once."""
    slots, c, n_kv, group, hd = q.shape
    ps = k_pages.shape[-2]
    table, n_blocks, bp = _whole_blocks(table, block_pages)
    span = bp * ps
    heads = n_kv * group
    # heads narrower than a lane tile are padded to one (zeros add
    # nothing to a product), so that every shape tiles; at whole tiles
    # the gathered rows go to the kernel as they lie
    pad = -hd % 128
    wide = hd + pad

    def widen(x, n):  # [.., n * hd] -> [.., n * wide]
        if not pad:
            return x
        x = x.reshape(x.shape[:-1] + (n, hd))
        x = jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))
        return x.reshape(x.shape[:-2] + (n * wide,))

    qc = widen(q.astype(k_pages.dtype).reshape(slots, c, heads * hd), heads)

    def block(j, carry):
        rows = jax.lax.dynamic_slice_in_dim(table, j * bp, bp, axis=1)
        kb, vb = (
            widen(pages[layer, rows].reshape(slots, span, n_kv * hd), n_kv)
            for pages in (k_pages, v_pages)
        )
        return tuple(live_span_fold(
            qc, q_pos, kb, vb, (first + j * bp) * ps, lengths, *carry,
            n_kv=n_kv, window=window, scale=1.0 / float(np.sqrt(hd)),
        ))

    carry = (
        jnp.full((slots, c, heads), _NEG_BIG, jnp.float32),
        jnp.zeros((slots, c, heads), jnp.float32),
        jnp.zeros((slots, c, heads * wide), jnp.float32),
    )
    if n_blocks == 1:
        carry = block(0, carry)
    else:
        trips = live_span_trips(lengths - first * ps, span, n_blocks)
        carry = jax.lax.fori_loop(0, trips, block, carry)
    _, l, acc = carry
    out = acc.reshape(slots, c, heads, wide)[..., :hd]
    out = out / jnp.where(l > 0, l, 1.0)[..., None]
    return out.reshape(slots, c, heads * hd)


def paged_page_size_hint(dtype, head_dim: int) -> int:
    """The flash sweep's measured-best key tile (``_BEST_BLOCKS``'
    ``block_k``), the engine's default page size (clamped to
    ``max_seq_len``) when neither the caller nor the autotuner's
    ``serve.page_size`` winner names one. Larger pages mean fewer
    copies for the decode read (:func:`ragged_paged_attention` copies
    whole pages, as many a step as fit :data:`_DECODE_STEP_BYTES`),
    smaller ones a finer allocation and less of a last page read past a
    sequence's end."""
    return _static_best_blocks(dtype, head_dim, 0)[2][1]


#: the decode read's kernel's name in a device trace
DECODE_KERNEL = "ragged_paged_attention"

#: most bytes of K (and as many of V) one step of
#: :func:`ragged_paged_attention` folds: a step takes the most pages, a
#: power of two, that fit, so that its double-buffered K and V stay in
#: fast memory whatever the page
_DECODE_STEP_BYTES = 1 << 20


def decode_read_pages(lengths, page_size: int):
    """Pages of a slot holding ``lengths`` keys (``[S]`` or a scalar;
    numpy on the host, traced in a program) that
    :func:`ragged_paged_attention` copies: every page that holds a key
    of the slot, none past its end. The same arithmetic makes the
    kernel's schedule and the host's account
    (:func:`decode_read_positions`)."""
    return (lengths + (page_size - 1)) // page_size


def decode_block_pages(page_bytes: int, rows: int) -> int:
    """Pages one step of :func:`ragged_paged_attention` folds: the
    largest power of two whose bytes fit :data:`_DECODE_STEP_BYTES`, no
    more than a table's ``rows`` (gpt2-xl's pages of 16 x 1,600 float32,
    100 KB: 8)."""
    fit = max(1, _DECODE_STEP_BYTES // max(1, int(page_bytes)))
    return min(1 << (fit.bit_length() - 1), max(1, int(rows)))


def decode_read_positions(
    lengths, rows: int, page_size: int, page_bytes: int,
    block_pages: Optional[int] = None,
) -> int:
    """Key positions a layer's :func:`ragged_paged_attention` copies for
    slots holding ``lengths`` keys (an idle slot holds one, on the trash
    page) over tables of ``rows`` pages of ``page_bytes`` bytes, folding
    ``block_pages`` pages a step (:func:`decode_block_pages` when not
    given): each slot's :func:`decode_read_pages`, and, while no slot
    holds that many pages, one page more for each page buffer that none
    fills (its one copy, of a live page, at the walk's start)."""
    bp = block_pages or decode_block_pages(page_bytes, rows)
    pages = decode_read_pages(np.asarray(lengths, np.int64), page_size)
    idle = max(0, bp - int(pages.max(initial=0)))
    return (int(pages.sum()) + idle) * page_size


def _decode_schedule(table, lengths, page_size: int, bp: int, steps: int):
    """The walk of :func:`ragged_paged_attention` as scalar-prefetch data:
    ``(slot, trip, fetch, total)``. Step ``g < total`` folds trip
    ``trip[g]`` (pages ``trip * bp ..``) of slot ``slot[g]``, the slots
    in order, each over ``max(1, ceil(pages / bp))`` steps; the steps
    from ``total`` to the grid's static ``steps`` do nothing.
    ``fetch[g * bp + i]`` is the pool page the step's ``i``-th page
    buffer holds: the slot's page ``trip * bp + i`` where the slot has
    it, else the next page that buffer will hold (else the last it
    held), so that a buffer's page changes only to a page the walk
    needs, and the pipeline, which copies a block only when its index
    changes, copies each live page once and no page past a slot's end."""
    slots, rows = table.shape
    pages = decode_read_pages(lengths, page_size)
    trips = jnp.maximum(-(-pages // bp), 1)
    ends = jnp.cumsum(trips)
    total = ends[-1]
    g = jnp.arange(steps, dtype=jnp.int32)
    slot = jnp.minimum(jnp.searchsorted(ends, g, side="right"), slots - 1)
    trip = jnp.minimum(g - (ends[slot] - trips[slot]), trips[slot] - 1)
    page = trip[:, None] * bp + jnp.arange(bp, dtype=jnp.int32)[None, :]
    valid = (g[:, None] < total) & (page < pages[slot][:, None])
    row = table[slot[:, None], jnp.minimum(page, rows - 1)]  # [G, bp]
    at = g[:, None]
    nxt = jax.lax.cummin(jnp.where(valid, at, steps), axis=0, reverse=True)
    prev = jax.lax.cummax(jnp.where(valid, at, -1), axis=0)
    src = jnp.where(nxt < steps, nxt, jnp.maximum(prev, 0))
    fetch = jnp.take_along_axis(row, src, axis=0)
    # a buffer no slot fills holds the walk's first page
    fetch = jnp.where(valid.any(axis=0)[None, :], fetch, row[0, 0])
    return (
        slot.astype(jnp.int32), trip.astype(jnp.int32),
        fetch.reshape(-1).astype(jnp.int32), total.astype(jnp.int32),
    )


def _ragged_paged_kernel(
    meta_ref, slot_ref, trip_ref, fetch_ref, len_ref, q_ref, *refs,
    pages, scale,
):
    """Grid = (steps,), in order: step ``g`` folds ``pages`` pages of
    slot ``slot_ref[g]`` (:func:`_decode_schedule`) into the
    online-softmax state (``online_block_update``, the flash kernels'
    recurrence) of every head of the slot at once. The query block
    holds every head laid over its K/V head's lanes
    (``_heads_on_lanes``), so one ``[heads, lanes] x [pages *
    page_size, lanes]`` product scores them all and one ``[heads, pages
    * page_size] x [pages * page_size, lanes]`` product adds their
    contexts over every lane (the wrapper keeps each head's own); ``m``
    and ``l`` stay in fast memory and the output block, which is the
    slot's until its last step, is the accumulator.

    Each of the ``pages`` K (and V) inputs is one page of the pool in
    HBM, ``pool[layer, fetch]``, which the pipeline copies while the
    step before folds, and only where its page changes: each live page
    once. A slot's last step masks the positions at or past its
    ``length`` (their scores dropped, their values zeroed: a buffer the
    slot did not fill holds a page of the slot after it)."""
    from jax.experimental import pallas as pl

    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    o_ref, m_scr, l_scr = refs[2 * pages:]
    g = pl.program_id(0)
    span = pages * k_refs[0].shape[2]

    @pl.when(g < meta_ref[1])
    def _():
        trip = trip_ref[g]
        length = len_ref[slot_ref[g]]
        base = trip * span

        @pl.when(trip == 0)
        def _():
            m_scr[...] = jnp.full(m_scr.shape, _NEG_BIG, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

        def fold(with_mask):
            k, v = (
                jnp.concatenate([r[0, 0] for r in refs], axis=0)
                for refs in (k_refs, v_refs)
            )
            mask = None
            if with_mask:
                pos = base + jax.lax.broadcasted_iota(
                    jnp.int32, (q_ref.shape[1], span), 1
                )
                mask = pos < length
                real = base + jax.lax.broadcasted_iota(
                    jnp.int32, v.shape, 0
                ) < length
                v = jnp.where(real, v, 0)
            m, l, acc = online_block_update(
                q_ref[0], k, v, m_scr[...], l_scr[...], o_ref[0], scale, mask
            )
            m_scr[...] = m
            l_scr[...] = l
            o_ref[0] = acc

        @pl.when(base + span <= length)
        def _():
            fold(with_mask=False)

        @pl.when(base + span > length)
        def _():
            fold(with_mask=True)
            o_ref[0] = _finalize(l_scr[...], o_ref[0])

        @pl.when(base + span == length)
        def _():
            o_ref[0] = _finalize(l_scr[...], o_ref[0])


def ragged_paged_attention(
    q, k_pages, v_pages, page_table, lengths, layer=None, *,
    block_pages: Optional[int] = None, interpret: Optional[bool] = None,
):
    """The serving decode step's paged read: attention of one query a
    slot over the keys its page table names, in one Pallas kernel that
    copies each slot's live pages and nothing else (Ragged Paged
    Attention, PAPERS.md arXiv:2604.15464).

    Same contract as the gather oracle :func:`paged_attention` — ``q``
    [S, n_kv, group, hd], ``k_pages``/``v_pages`` [pool_pages,
    page_size, n_kv * hd] in the pool's merged-lane layout
    (``serve/kv_pages.py``), or the whole ``[n_layers, ...]`` pool with
    ``layer=li``, ``page_table`` [S, max_pages] int32, ``lengths`` [S]
    int32 (valid positions INCLUDING the token just written) — and
    agrees with it to float associativity (an online softmax against a
    one-shot one). Returns [S, n_kv, group, hd] in ``q``'s dtype.

    The gather copies ``max_pages`` pages of every slot whatever its
    length. The kernel (:func:`_ragged_paged_kernel`) walks each slot's
    :func:`decode_read_pages` pages, ``block_pages`` a step
    (:func:`decode_block_pages` of the page's bytes when not given), the
    next step's pages copied while a step folds, into an online-softmax
    carry in fast memory: a step's bytes follow the batch's live keys
    (:func:`decode_read_positions`). The grid has a static number of
    steps (a table's worth of every slot); the steps past the walk copy
    and compute nothing. A page is one block of the pool as
    it lies (a copy of a part of a page's 1,600 lanes, not whole
    128-lane tiles, is one the chip's copy engine does not take). The
    products take
    float32 operands as the ambient precision takes them (the gather
    arm's). The table, the lengths and ``layer`` are scalar-prefetch
    DATA to the kernel's jitted callee (:func:`_ragged_paged_read`),
    so every layer of a program calls one function: a 48-layer decode
    program traces and lowers it once. ``interpret`` defaults to True
    off the TPU, so the CPU tests run the kernel itself."""
    _check_paged_inputs(q, k_pages, v_pages, page_table, lengths, layer)
    if layer is None:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    return _ragged_paged_read(
        q, k_pages, v_pages, page_table, lengths, layer,
        block_pages=block_pages, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("block_pages", "interpret"))
def _ragged_paged_read(
    q, k_pages, v_pages, page_table, lengths, layer, *, block_pages,
    interpret,
):
    """:func:`ragged_paged_attention` over layer ``layer`` of the whole
    pool ``[n_layers, pool_pages, page_size, lanes]``, jitted on its own
    with the layer as data: every layer of a program is one callee."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, n_kv, group, hd = q.shape
    rows = page_table.shape[1]
    ps, lanes = k_pages.shape[-2:]
    bp = block_pages or decode_block_pages(
        ps * lanes * k_pages.dtype.itemsize, rows
    )
    steps = slots * -(-rows // bp)
    slot, trip, fetch, total = _decode_schedule(
        page_table, lengths, ps, bp, steps
    )
    n_heads = n_kv * group
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    block = pl.BlockSpec(
        (1, n_heads, lanes), lambda g, meta, slot, *_: (slot[g], 0, 0)
    )

    def page_spec(i):
        return pl.BlockSpec(
            (1, 1, ps, lanes),
            lambda g, meta, slot, trip, fetch, lens: (
                meta[0], fetch[g * bp + i], 0, 0
            ),
        )

    pages = [page_spec(i) for i in range(bp)]
    out = pl.pallas_call(
        functools.partial(
            _ragged_paged_kernel, pages=bp, scale=1.0 / float(np.sqrt(hd))
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(steps,),
            in_specs=[block] + pages + pages,
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM((n_heads, 1), jnp.float32),
                pltpu.VMEM((n_heads, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((slots, n_heads, lanes), jnp.float32),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name=DECODE_KERNEL,
    )(
        jnp.stack([jnp.asarray(layer, jnp.int32), total]),
        slot, trip, fetch, lengths, _heads_on_lanes(q),
        *[k_pages] * bp, *[v_pages] * bp,
    )
    return _heads_off_lanes(out, n_kv, hd).astype(q.dtype)


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, block_q, block_k, causal, offset, scale,
):
    """Grid = (batch*heads, q_blocks, k_blocks); the k axis is innermost and
    sequential on TPU, so the VMEM scratch carries the online-softmax state
    across k steps — only one [block_k, d] key/value tile is resident at a
    time (true streaming; context length is HBM-bound, not VMEM-bound).

    ``offset = lk - lq`` aligns the causal diagonal bottom-right, matching
    :func:`attention_reference` for cross-length attention."""
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def update(with_mask):
        # keep the INPUT dtype: bf16 q/k/v run their matmuls in the MXU's
        # native bf16 mode (online_block_update accumulates f32)
        q = q_ref[0]  # [block_q, d]
        kj = k_ref[0]
        vj = v_ref[0]
        mask = (
            _frontier_mask(iq, ik, block_q, block_k, offset)
            if with_mask
            else None
        )
        m, l, acc = online_block_update(
            q, kj, vj, m_scr[:], l_scr[:], acc_scr[:], scale, mask
        )
        m_scr[:] = m
        l_scr[:] = l
        acc_scr[:] = acc

    if causal:
        # three regimes per tile: fully in the masked future (skip), fully
        # visible interior (no mask work — most tiles at long L), and the
        # diagonal frontier (masked). Skipping the iota/where on interior
        # tiles removes VPU work from the hot path.
        visible, interior = _causal_tile_regimes(
            iq, ik, block_q, block_k, offset
        )

        @pl.when(interior)
        def _():
            update(with_mask=False)

        @pl.when(jnp.logical_and(visible, jnp.logical_not(interior)))
        def _():
            update(with_mask=True)

    else:
        update(with_mask=False)

    @pl.when(ik == nk - 1)
    def _emit():
        o_ref[0] = _finalize(l_scr[:], acc_scr[:]).astype(o_ref.dtype)
        # [bq, 1] rows saved for the backward pass
        lse_ref[0] = _lse_sentinel(m_scr[:], l_scr[:])


def _fit_tile(block, length):
    # largest tile <= the requested block that divides the sequence —
    # lane-aligned (multiple of 128) unless it is the whole sequence.
    # Keeps every length the old 128-tile default accepted working
    # (e.g. L=640 fits 128 when 512 does not divide it).
    cap = min(block, length)
    if length % cap == 0:
        return cap
    fits = [t for t in range(128, cap + 1, 128) if length % t == 0]
    return max(fits) if fits else None


def _dim_semantics(pltpu, interpret):
    # batch*heads and the non-innermost tile axis are independent; only
    # the innermost axis is a sequential reduction (the scratch carry)
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")
    )


def _flash_forward(q, k, v, causal, block_q, block_k, interpret):
    """The forward pallas call: returns ``(o [B,H,Lq,D], lse [B*H,Lq,1])``.
    ``lse`` (log-sum-exp per query row) is the one extra output the
    FlashAttention backward needs to recompute softmax tiles without the
    [L, L] matrix."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, lq, d = q.shape
    lk = k.shape[2]
    scale = 1.0 / float(np.sqrt(d))
    bh = b * h
    qf = q.reshape(bh, lq, d)
    kf = k.reshape(bh, lk, d)
    vf = v.reshape(bh, lk, d)
    # NOTE on D=64 (r05): head-pair packing per grid step was built and
    # measured — at its VMEM-safe tiles (two f32 score tiles cap it at
    # bq*bk <= 512k) it reached 24.1% MFU, LOSING to the single-head
    # kernel at full 1024x1024 tiles (28.7%); tile area beats head
    # packing, so the variant was removed. The D=64 ceiling itself is
    # hardware: the bare matmul pair measured 59.2% of peak (r05 sweeps,
    # `git show 742ccc6:flash_sweep_r05.json`).
    kernel = functools.partial(
        _flash_kernel,
        block_q=block_q,
        block_k=block_k,
        causal=causal,
        offset=lk - lq,
        scale=scale,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, lq // block_q, lk // block_k),
        in_specs=[
            pl.BlockSpec(
                (1, block_q, d), lambda bi, qi, ki: (bi, qi, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, block_k, d), lambda bi, qi, ki: (bi, ki, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, block_k, d), lambda bi, qi, ki: (bi, ki, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, block_q, d), lambda bi, qi, ki: (bi, qi, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, block_q, 1), lambda bi, qi, ki: (bi, qi, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, lq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_dim_semantics(pltpu, interpret),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, lq, d), lse


def _flash_carry_kernel(
    q_ref, k_ref, v_ref, m_in_ref, l_in_ref, acc_in_ref,
    m_out_ref, l_out_ref, acc_out_ref, m_scr, l_scr, acc_scr,
    *, block_q, block_k, causal, offset, scale,
):
    """Carry-mode forward: like :func:`_flash_kernel` but the online-softmax
    state STARTS from an incoming (m, l, acc) and is emitted un-finalized.
    This is the building block ring attention folds one visiting k/v chunk
    with — per-chip memory stays O(block), never O((L/n)^2), because the
    chunk streams through VMEM one [block_k, d] tile at a time exactly as
    in the single-chip kernel."""
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = m_in_ref[0]
        l_scr[:] = l_in_ref[0]
        acc_scr[:] = acc_in_ref[0]

    def update(with_mask):
        mask = (
            _frontier_mask(iq, ik, block_q, block_k, offset)
            if with_mask
            else None
        )
        m, l, acc = online_block_update(
            q_ref[0], k_ref[0], v_ref[0],
            m_scr[:], l_scr[:], acc_scr[:], scale, mask,
        )
        m_scr[:] = m
        l_scr[:] = l
        acc_scr[:] = acc

    if causal:
        visible, interior = _causal_tile_regimes(
            iq, ik, block_q, block_k, offset
        )

        @pl.when(interior)
        def _():
            update(with_mask=False)

        @pl.when(jnp.logical_and(visible, jnp.logical_not(interior)))
        def _():
            update(with_mask=True)

    else:
        update(with_mask=False)

    @pl.when(ik == nk - 1)
    def _emit():
        m_out_ref[0] = m_scr[:]
        l_out_ref[0] = l_scr[:]
        acc_out_ref[0] = acc_scr[:]


def flash_carry(
    q, k, v, m, l, acc, *, causal, offset, block_q, block_k, interpret
):
    """Fold one key/value span into an online-softmax carry with the flash
    kernel. Flat layout: ``q`` [BH, Lq, D]; ``k``/``v`` [BH, Lk, D]; carry
    ``m``/``l`` [BH, Lq, 1] and ``acc`` [BH, Lq, D], all f32. Returns the
    updated (m, l, acc), not finalized — callers chain spans (ring hops)
    and finalize once. ``offset`` is the static causal diagonal offset
    (``q_global - k_global`` of the first elements); only the diagonal
    ring hop is causal and there it is 0."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, lq, d = q.shape
    lk = k.shape[1]
    _check_tiles(block_q, lq, block_k, lk)
    scale = 1.0 / float(np.sqrt(d))
    kernel = functools.partial(
        _flash_carry_kernel,
        block_q=block_q,
        block_k=block_k,
        causal=causal,
        offset=offset,
        scale=scale,
    )
    q_spec = pl.BlockSpec(
        (1, block_q, d), lambda bi, qi, ki: (bi, qi, 0),
        memory_space=pltpu.VMEM,
    )
    k_spec = pl.BlockSpec(
        (1, block_k, d), lambda bi, qi, ki: (bi, ki, 0),
        memory_space=pltpu.VMEM,
    )
    row_spec = pl.BlockSpec(
        (1, block_q, 1), lambda bi, qi, ki: (bi, qi, 0),
        memory_space=pltpu.VMEM,
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, lq // block_q, lk // block_k),
        in_specs=[q_spec, k_spec, k_spec, row_spec, row_spec, q_spec],
        out_specs=[row_spec, row_spec, q_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lq, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, lq, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, lq, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_dim_semantics(pltpu, interpret),
        interpret=interpret,
    )(q, k, v, m, l, acc)


def _bwd_tile_terms(q, kj, vj, do, lse, dlt, scale, mask):
    """Shared per-tile recomputation for both backward kernels: softmax
    probabilities ``p`` and score gradient ``ds`` for one (q, k) tile pair.
    ``lse``/``dlt`` are [bq, 1] (``lse`` in the base-2 domain, matching
    :func:`_lse_sentinel`); fully-masked rows carry the ``_POS_BIG``
    lse sentinel, so ``p`` (and with it every gradient term) is exactly 0
    there. f32 throughout except the matmuls, which keep the input's MXU
    mode (bf16 tiles run the backward at the chip's high rate, like the
    forward)."""
    mxu_dt = _mxu_dtype(q.dtype)
    s = _mxu_dot(q, kj, (1, 1), mxu_dt) * (scale * _LOG2E)
    if mask is not None:
        s = jnp.where(mask, s, _NEG_BIG)
    p = jnp.exp2(s - lse)  # masked / empty-row entries underflow to 0
    dp = _mxu_dot(do, vj, (1, 1), mxu_dt)
    ds = p * (dp - dlt) * scale
    return p, ds, mxu_dt


def _causal_tile_regimes(q_block_idx, k_block_idx, block_q, block_k, offset):
    """(visible, interior) predicates for one (q, k) tile pair under the
    bottom-right-aligned causal mask — shared by all three kernels so the
    skip/frontier logic cannot diverge between forward and backward."""
    visible = k_block_idx * block_k <= offset + (q_block_idx + 1) * block_q - 1
    interior = (k_block_idx + 1) * block_k - 1 <= offset + q_block_idx * block_q
    return visible, interior


def _frontier_mask(q_block_idx, k_block_idx, block_q, block_k, offset):
    """The [block_q, block_k] causal mask for a frontier tile (True =
    attend), ``q_pos >= k_pos`` with the bottom-right offset — the other
    half of the shared causal logic (see :func:`_causal_tile_regimes`)."""
    q_pos = offset + q_block_idx * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = k_block_idx * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    return q_pos >= k_pos


def _frontier_mask_t(q_block_idx, k_block_idx, block_q, block_k, offset):
    """:func:`_frontier_mask` transposed — the [block_k, block_q] mask
    for the dkv kernel's transposed-score tiles (same predicate, iota
    axes swapped so no relayout is spent transposing the mask)."""
    q_pos = offset + q_block_idx * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 1
    )
    k_pos = k_block_idx * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 0
    )
    return q_pos >= k_pos


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
    *, block_q, block_k, causal, offset, scale,
):
    """dQ: grid (batch*heads, q_blocks, k_blocks), k innermost sequential;
    the dq tile accumulates in VMEM scratch across k steps (mirror of the
    forward's online accumulation)."""
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def compute(with_mask):
        qi = q_ref[0]
        kj = k_ref[0]
        doi = do_ref[0]
        mask = (
            _frontier_mask(iq, ik, block_q, block_k, offset)
            if with_mask
            else None
        )
        _, ds, mxu_dt = _bwd_tile_terms(
            qi, kj, v_ref[0], doi, lse_ref[0], delta_ref[0], scale, mask
        )
        dq_scr[:] += _mxu_dot(ds, kj, (1, 0), mxu_dt)

    if causal:
        visible, interior = _causal_tile_regimes(
            iq, ik, block_q, block_k, offset
        )

        @pl.when(interior)
        def _():
            compute(with_mask=False)

        @pl.when(jnp.logical_and(visible, jnp.logical_not(interior)))
        def _():
            compute(with_mask=True)

    else:
        compute(with_mask=False)

    @pl.when(ik == nk - 1)
    def _emit():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr, *, block_q, block_k, causal, offset, scale,
):
    """dK/dV: grid (batch*heads, k_blocks, q_blocks), q innermost
    sequential; one kernel owns one k tile and streams the q tiles that
    can see it, accumulating both gradients in VMEM scratch.

    The math runs in the TRANSPOSED-score formulation: ``s^T = K Q^T``
    ([bk, bq]) so that all four contractions — s^T, dp^T = V dO^T,
    dV += p^T dO, dK += ds^T Q — contract over their operands' MINOR
    axis. The direct formulation needed two axis-0 contractions
    (``P^T dO``, ``dS^T Q``) whose operand relayouts held this kernel at
    73% of the matmul ceiling while the dq kernel (all-natural
    contractions) ran at 93% (r05 per-kernel sweep, `git show
    742ccc6:flash_sweep2_r05.json`). The [bq, 1] lse/delta rows transpose to
    [1, bq] lane vectors once per tile — trivial next to the matmuls."""
    from jax.experimental import pallas as pl

    jk = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def compute(with_mask):
        qi = q_ref[0]
        kj = k_ref[0]
        vj = v_ref[0]
        doi = do_ref[0]
        mxu_dt = _mxu_dtype(qi.dtype)
        st = _mxu_dot(kj, qi, (1, 1), mxu_dt) * (scale * _LOG2E)
        if with_mask:
            st = jnp.where(
                _frontier_mask_t(iq, jk, block_q, block_k, offset),
                st,
                _NEG_BIG,
            )
        lse_row = lse_ref[0].reshape(1, block_q)
        pt = jnp.exp2(st - lse_row)  # [bk, bq]; masked rows underflow to 0
        dpt = _mxu_dot(vj, doi, (1, 1), mxu_dt)
        dst = pt * (dpt - delta_ref[0].reshape(1, block_q)) * scale
        dv_scr[:] += _mxu_dot(pt, doi, (1, 0), mxu_dt)
        dk_scr[:] += _mxu_dot(dst, qi, (1, 0), mxu_dt)

    if causal:
        visible, interior = _causal_tile_regimes(
            iq, jk, block_q, block_k, offset
        )

        @pl.when(interior)
        def _():
            compute(with_mask=False)

        @pl.when(jnp.logical_and(visible, jnp.logical_not(interior)))
        def _():
            compute(with_mask=True)

    else:
        compute(with_mask=False)

    @pl.when(iq == nq - 1)
    def _emit():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


#: measured-best backward tiles per (dtype kind, head_dim bucket) — the
#: r05 per-kernel sweep (not re-timed on the current machine): the dq kernel
#: (3 matmuls/tile, k innermost) and the dk/dv kernel (4 matmuls/tile, q
#: innermost) run different matmul mixes and need not share the
#: forward's optimum. Keys as in ``_BEST_BLOCKS``; values are
#: ((dq_block_q, dq_block_k), (dkv_block_q, dkv_block_k)).
_BEST_BLOCKS_BWD = {
    # dq (3 natural matmuls, k innermost) peaks at square 1024 tiles
    # (178 TF/s real rate = 93% of ceiling); the transposed-score dkv
    # kernel prefers narrow-q/wide-k (154 TF/s at 512x2048 vs 146 at
    # square). f32 inputs DOUBLE every score intermediate: dkv at
    # 512x2048 f32 needs 26.5 MB of scoped VMEM (measured compile
    # failure) — the f32 rows keep square tiles. Under an ambient
    # jax.default_matmul_precision("float32") the f32 rows no longer fit
    # (libtpu 0.0.34: dq at 1024x1024 and dkv at 512x1024 overrun the
    # 16 MB limit, dkv by 1.53 MB); callers that want true-f32 kernel
    # math pass block_q=block_k=512, which binds the backward too.
    (True, 128): ((1024, 1024), (512, 2048)),
    (True, 64): ((1024, 1024), (512, 2048)),
    (False, 128): ((1024, 1024), (512, 1024)),
    (False, 64): ((1024, 1024), (512, 1024)),
}


def _best_blocks_bwd(dtype, d, lq, lk):
    """Measured-best (dq, dkv) tile pairs, clamped so every tile divides
    its sequence (``_fit_tile``); falls back to the forward tiles when no
    lane-aligned fit exists."""
    is_lowp = dtype in (jnp.bfloat16, jnp.float16)
    d_bucket = 128 if d > 64 else 64
    (dq_q, dq_k), (kv_q, kv_k) = _BEST_BLOCKS_BWD[(is_lowp, d_bucket)]
    fit = (
        _fit_tile(dq_q, lq), _fit_tile(dq_k, lk),
        _fit_tile(kv_q, lq), _fit_tile(kv_k, lk),
    )
    if any(t is None for t in fit):
        return None
    return fit


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q, k, v, causal, block_q, block_k, interpret, tune_bwd):
    o, _ = _flash_forward(q, k, v, causal, block_q, block_k, interpret)
    return o


def _flash_core_fwd(q, k, v, causal, block_q, block_k, interpret, tune_bwd):
    o, lse = _flash_forward(q, k, v, causal, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_core_bwd(causal, block_q, block_k, interpret, tune_bwd, res, do):
    """FlashAttention-2 backward: recompute each softmax tile from q/k and
    the saved per-row log-sum-exp, never materializing [L, L]. Two pallas
    calls — dq accumulates over k tiles, dk/dv over q tiles — each with
    its OWN measured-best tiles (``_BEST_BLOCKS_BWD``; the forward tiles
    are only the fallback when no tuned tile divides the sequence), and
    the same causal skip/frontier regimes as the forward."""
    q, k, v, o, lse = res
    b, h, lq, d = q.shape
    lk = k.shape[2]
    bh = b * h
    qf = q.reshape(bh, lq, d)
    kf = k.reshape(bh, lk, d)
    vf = v.reshape(bh, lk, d)
    dof = do.reshape(bh, lq, d)
    # delta_i = rowsum(dO_i * O_i): one cheap fused elementwise pass
    delta = (
        dof.astype(jnp.float32) * o.reshape(bh, lq, d).astype(jnp.float32)
    ).sum(axis=-1, keepdims=True)
    # caller-supplied tiles are a VMEM knob and must stay binding (a
    # program sized to fit with small tiles must not OOM in its VJP);
    # only DEFAULTED tiles consult the tuned backward table
    tuned = _best_blocks_bwd(q.dtype, d, lq, lk) if tune_bwd else None
    if tuned is None:
        tuned = (block_q, block_k, block_q, block_k)
    dq_q, dq_k, kv_q, kv_k = tuned
    dq, dk, dv = flash_bwd_pair(
        qf, kf, vf, dof, lse, delta,
        causal=causal, offset=lk - lq,
        block_q=dq_q, block_k=dq_k, interpret=interpret,
        dkv_block_q=kv_q, dkv_block_k=kv_k,
        out_dtypes=(q.dtype, k.dtype, v.dtype),
    )
    return (
        dq.reshape(b, h, lq, d),
        dk.reshape(b, h, lk, d),
        dv.reshape(b, h, lk, d),
    )


def flash_bwd_pair(
    qf, kf, vf, dof, lse, delta, *,
    causal, offset, block_q, block_k, interpret, out_dtypes,
    dkv_block_q=None, dkv_block_k=None,
):
    """The two FlashAttention-2 backward pallas calls for one q-span/k-span
    pair, flat [BH, L, D] layout, with the causal diagonal at static
    ``offset``. Shared by the single-chip VJP (offset = lk - lq) and the
    ring backward (per-hop gradients; offset 0 on the diagonal hop).
    ``out_dtypes`` picks the emitted (dq, dk, dv) dtypes — the ring passes
    f32 so cross-hop accumulation never truncates. ``dkv_block_*``
    override the dk/dv kernel's tiles (it prefers wide-q/narrow-k, the
    transpose of the dq kernel's optimum — see ``_BEST_BLOCKS_BWD``);
    they default to the dq tiles."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, lq, d = qf.shape
    lk = kf.shape[1]
    dkv_block_q = dkv_block_q or block_q
    dkv_block_k = dkv_block_k or block_k
    _check_tiles(block_q, lq, block_k, lk)
    _check_tiles(dkv_block_q, lq, dkv_block_k, lk)
    scale = 1.0 / float(np.sqrt(d))
    dq_dt, dk_dt, dv_dt = out_dtypes

    q_spec = pl.BlockSpec(
        (1, block_q, d), lambda bi, qi, ki: (bi, qi, 0),
        memory_space=pltpu.VMEM,
    )
    k_spec = pl.BlockSpec(
        (1, block_k, d), lambda bi, qi, ki: (bi, ki, 0),
        memory_space=pltpu.VMEM,
    )
    row_spec = pl.BlockSpec(
        (1, block_q, 1), lambda bi, qi, ki: (bi, qi, 0),
        memory_space=pltpu.VMEM,
    )
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel,
            block_q=block_q,
            block_k=block_k,
            causal=causal,
            offset=offset,
            scale=scale,
        ),
        grid=(bh, lq // block_q, lk // block_k),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), dq_dt),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_dim_semantics(pltpu, interpret),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    # k-major grid: index maps swap which grid axis picks the q vs k tile
    qk_q_spec = pl.BlockSpec(
        (1, dkv_block_q, d), lambda bi, ki, qi: (bi, qi, 0),
        memory_space=pltpu.VMEM,
    )
    qk_k_spec = pl.BlockSpec(
        (1, dkv_block_k, d), lambda bi, ki, qi: (bi, ki, 0),
        memory_space=pltpu.VMEM,
    )
    qk_row_spec = pl.BlockSpec(
        (1, dkv_block_q, 1), lambda bi, ki, qi: (bi, qi, 0),
        memory_space=pltpu.VMEM,
    )
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel,
            block_q=dkv_block_q,
            block_k=dkv_block_k,
            causal=causal,
            offset=offset,
            scale=scale,
        ),
        grid=(bh, lk // dkv_block_k, lq // dkv_block_q),
        in_specs=[
            qk_q_spec, qk_k_spec, qk_k_spec, qk_q_spec,
            qk_row_spec, qk_row_spec,
        ],
        out_specs=[qk_k_spec, qk_k_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lk, d), dk_dt),
            jax.ShapeDtypeStruct((bh, lk, d), dv_dt),
        ],
        scratch_shapes=[
            pltpu.VMEM((dkv_block_k, d), jnp.float32),
            pltpu.VMEM((dkv_block_k, d), jnp.float32),
        ],
        compiler_params=_dim_semantics(pltpu, interpret),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    return dq, dk, dv


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Tiled attention, [B, H, L, D] layout. DIFFERENTIABLE: a custom VJP
    runs the FlashAttention-2 backward as two more pallas kernels (dq over
    k tiles; dk/dv over q tiles), recomputing softmax tiles from the saved
    per-row log-sum-exp — long-context training never materializes [L, L]
    in either direction.

    Default tiles come from the measured-best table ``_BEST_BLOCKS``
    (chain-differential timed per dtype/head_dim/L on v5e; 1024x1024 on
    every current entry, clamped to the sequence) — bigger tiles amortize
    the online-softmax rescale and keep the MXU on larger matmuls, and
    2048+ tiles exceed VMEM. bf16 inputs run the matmuls in the MXU's
    native bf16 mode with f32 accumulation (see
    :func:`online_block_update`), forward and backward.

    One grid step owns one (query block, key block) pair; the online-softmax
    state lives in VMEM scratch across the key axis, so K/V stream through
    VMEM one tile at a time. Sequence lengths must be multiples of the block
    sizes (callers pad; the ring layer shards to equal chunks anyway).
    Causal masking aligns the diagonal bottom-right when ``lq != lk`` (same
    convention as :func:`attention_reference`). ``interpret`` defaults to
    True off-TPU so tests run on CPU."""
    b, h, lq, d = q.shape
    lk = k.shape[2]
    # explicit tiles are a VMEM knob: they bind the backward too (the
    # tuned _BEST_BLOCKS_BWD table applies only when tiles defaulted)
    tune_bwd = block_q is None and block_k is None
    if block_q is None or block_k is None:
        tuned_q, tuned_k = _best_blocks(q.dtype, d, max(lq, lk))
        block_q = block_q or tuned_q
        block_k = block_k or tuned_k
    block_q = _fit_tile(block_q, lq)
    block_k = _fit_tile(block_k, lk)
    if block_q is None or block_k is None:
        raise ValueError(
            f"sequence lengths ({lq}, {lk}) admit no lane-aligned tile; "
            f"pad to a multiple of 128 (callers pad; the ring layer shards "
            f"to equal chunks anyway)"
        )
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return _flash_core(
        q, k, v, causal, block_q, block_k, interpret, tune_bwd
    )


#: the one-pass prefill's kernel's name in a device trace
PROMPT_KERNEL = "prompt_attention"

#: most queries and most keys a grid step of :func:`prompt_attention`
#: takes of a row (``_fit_tile``'s rule: a lane-aligned divisor of the
#: row, else the whole row)
_PROMPT_Q_TILE = 256
_PROMPT_K_TILE = 512

#: most bytes of a grid step's float32 query tile (the accumulator is as
#: large): a step takes as many heads as fit, so that the grid is not
#: one head a step, and its buffers stay inside the kernel's fast memory
_PROMPT_TILE_BYTES = 2 << 20

#: ambient matmul precisions under which the chip multiplies float32
#: operands in one bfloat16 pass (XLA's default for an einsum)
_ONE_BF16_PASS = (None, "default", "bfloat16", "BF16_BF16_F32")


def _online_lane_update(q, k, v, m, l, acc, scale, mask):
    """:func:`online_block_update` with the state ``m`` and ``l`` held
    ``[bq, 128]``, each row's value in every lane: the state's
    arithmetic and the rescale of ``acc`` (``[bq, whole lane tiles]``)
    then take no broadcast out of one lane, and the scores take whole
    tiles of it (a 48-layer gpt2-xl prefill on a TPU v5e: 34.0 → 31.2
    ms of device time against the ``[bq, 1]`` state)."""
    tk, wide = k.shape[0], acc.shape[1]
    s = _mxu_dot(q, k, (1, 1), _mxu_dtype(q.dtype)) * (scale * _LOG2E)
    if mask is not None:
        s = jnp.where(mask, s, _NEG_BIG)
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    if tk % 128 == 0:
        m_row = jnp.tile(m_new, (1, tk // 128))
    else:  # a whole row narrower than a lane tile, or not whole tiles
        m_row = jnp.broadcast_to(m_new[:, :1], s.shape)
    p = jnp.exp2(s - m_row)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp2(m - m_new)
    l_new = alpha * l + p.sum(axis=-1, keepdims=True)
    pv = _mxu_dot(p, v, (1, 0), _mxu_dtype(v.dtype))
    return m_new, l_new, jnp.tile(alpha, (1, wide // 128)) * acc + pv


def _prompt_tiles(rows: int) -> tuple:
    """``(queries, keys)`` a grid step of :func:`prompt_attention` takes
    of a row of ``rows`` positions."""
    return (
        _fit_tile(_PROMPT_Q_TILE, rows) or rows,
        _fit_tile(_PROMPT_K_TILE, rows) or rows,
    )


def _prompt_tile_regimes(q_tile, k_tile, tq, tk, length, window=0):
    """``(visible, interior)`` of the pair of query tile ``q_tile`` (of
    ``tq`` positions) and key tile ``k_tile`` (of ``tk``) of a causal
    prompt row whose first ``length`` positions are real: visible — some
    real query of the tile may see some real key of it (causal, keys
    ``< length``, with a ``window`` inside it); interior — every query
    of the tile sees every key of it (no mask work). Scalars inside the
    kernel, numpy arrays on the host: the same arithmetic decides what
    the kernel computes and what the engine counts
    (:func:`prompt_attention_tiles`)."""
    visible, interior = _causal_tile_regimes(q_tile, k_tile, tq, tk, 0)
    q_lo, k_lo = q_tile * tq, k_tile * tk
    q_hi, k_hi = q_lo + (tq - 1), k_lo + (tk - 1)
    visible = visible & (k_lo < length) & (q_lo < length)
    if window:
        visible = visible & (k_hi > q_lo - window)
        interior = interior & (k_lo > q_hi - window)
    return visible, interior & visible & (k_hi < length)


def prompt_attention_tiles(length: int, rows: int, windows) -> tuple:
    """``(computed, skipped)``: the pairs of a query tile and a key tile
    that :func:`prompt_attention` computes and skips for a prompt of
    ``length`` real tokens in a row of ``rows`` positions, summed over
    layers whose windows are ``windows`` (0: a full layer) —
    :func:`_prompt_tile_regimes` over every pair."""
    tq, tk = _prompt_tiles(rows)
    qt, kt = np.meshgrid(
        np.arange(rows // tq), np.arange(rows // tk), indexing="ij"
    )
    windows = list(windows)
    computed = sum(
        windows.count(w)
        * int(_prompt_tile_regimes(qt, kt, tq, tk, length, w)[0].sum())
        for w in set(windows)
    )
    return computed, len(windows) * qt.size - computed


def _prompt_kv_tiles(q_tile, tq, tk, length, window):
    """The first and the last key tile that query tile ``q_tile`` may
    see (the K/V index maps clamp to them, so that a skipped step copies
    nothing); a tile of padding queries keeps the last real key tile."""
    q_lo = q_tile * tq
    hi = jnp.minimum(q_lo + (tq - 1), length - 1) // tk
    lo = jnp.maximum(q_lo - window + 1, 0) // tk if window else 0
    return jnp.where(q_lo < length, lo, hi), hi


def _prompt_attention_kernel(
    len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
    *, hd, group, window, scale,
):
    """Grid = (rows, head blocks, query tiles, key tiles), the keys
    innermost: one step folds a tile of keys and values into the
    online-softmax state of every query head of its block for one tile
    of queries through :func:`_online_lane_update`, one lane tile a turn
    of a rolled loop (the body is traced and lowered once). The tiles
    are lane blocks of the rows as the projection laid them, heads side
    by side: a head of ``hd >= 128`` lanes is whole lane tiles, and
    heads narrower than 128 share a tile (multi-head only: a query head
    and its K/V head then sit in the same lanes) and are folded side by
    side in one turn, each picking its lanes by a mask — the other
    heads' query lanes go in as zeros, and only its own lanes of the
    product with V are kept. ``m`` and ``l``
    ``[heads, tq, 128]`` stay in fast memory across the key tiles, and so
    does the output tile, which is the accumulator; the last key step
    divides it by ``l``.

    Three regimes a step, from the row's ``length`` and the tiles'
    positions (:func:`_prompt_tile_regimes`): nothing visible (skipped:
    above the diagonal, past ``length``, outside a window, or a tile of
    padding queries), everything visible (no mask work), else the fold
    under the mask made from positions."""
    from jax.experimental import pallas as pl

    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    tq, tk = q_ref.shape[1], k_ref.shape[1]
    share = max(1, 128 // hd)  # heads a lane tile holds
    tiles = m_scr.shape[0] // share  # lane tiles (or whole heads) a step
    unit = max(hd, 128)  # lanes of one
    slot = jax.lax.broadcasted_iota(jnp.int32, (tq, unit), 1) // hd
    length = len_ref[b]

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_BIG, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    visible, interior = _prompt_tile_regimes(i, j, tq, tk, length, window)

    def lanes(t):
        return pl.ds(pl.multiple_of(t * unit, 128), unit)

    def fold(with_mask):
        mask = None
        if with_mask:
            q_pos = i * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
            k_pos = j * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
            mask = jnp.logical_and(k_pos <= q_pos, k_pos < length)
            if window:
                mask = jnp.logical_and(mask, k_pos > q_pos - window)
            # a padding key's value goes in as zeros: its weight is an
            # exact zero, and zero times what padding holds need not be
            real = j * tk + jax.lax.broadcasted_iota(
                jnp.int32, (tk, unit), 0
            ) < length

        def tile(t, carry):
            acc = o_ref[0, :, lanes(t)]
            q = q_ref[0, :, lanes(t)]
            kv = lanes(t // group)
            k, v = k_ref[0, :, kv], v_ref[0, :, kv]
            if with_mask:
                v = jnp.where(real, v, 0)
            new = acc
            for r in range(share):  # the heads of the tile, side by side
                h = t * share + r
                m, l, upd = _online_lane_update(
                    q if share == 1 else jnp.where(slot == r, q, 0), k, v,
                    m_scr[h], l_scr[h], acc, scale, mask,
                )
                m_scr[h], l_scr[h] = m, l
                new = upd if share == 1 else jnp.where(slot == r, upd, new)
            o_ref[0, :, lanes(t)] = new
            return carry

        jax.lax.fori_loop(0, tiles, tile, 0)

    @pl.when(interior)
    def _():
        fold(with_mask=False)

    @pl.when(jnp.logical_and(visible, jnp.logical_not(interior)))
    def _():
        fold(with_mask=True)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        def emit(t, carry):
            acc = new = o_ref[0, :, lanes(t)]
            for r in range(share):
                out = _finalize(
                    jnp.tile(l_scr[t * share + r], (1, unit // 128)), acc
                )
                new = out if share == 1 else jnp.where(slot == r, out, new)
            o_ref[0, :, lanes(t)] = new
            return carry

        jax.lax.fori_loop(0, tiles, emit, 0)


def prompt_attention(
    q, k, v, lengths, *, n_heads: int, n_kv: int, hd: int, window: int = 0,
    scale: Optional[float] = None, interpret: Optional[bool] = None,
):
    """Causal attention of a prompt row's queries over its own keys, in
    one fused kernel: score tiles, their mask, the online-softmax state
    and the product with V live in fast memory, and only q, k, v and the
    context cross HBM.

    ``q`` ``[B, P, lanes]`` holds ``n_heads`` heads of ``hd`` lanes side
    by side (head ``h`` = K/V head ``h // group`` x group member ``h %
    group``), ``k`` / ``v`` ``[B, P, lanes]`` ``n_kv`` heads; ``lanes``
    is whole 128-lane tiles (the caller pads the tail). ``hd`` is whole
    tiles, or divides 128 in a multi-head model (heads then share
    tiles). Row ``b``'s first ``lengths[b]`` positions are real (``[B]``
    int32): the query at position ``p`` sees the real keys at or before
    it and, with a ``window``, after ``p - window``. Pairs of a query
    and a key tile (:func:`_prompt_tiles`) that no real query sees are
    skipped and their keys not copied; padding positions' outputs are
    whatever the kernel gives them, and no real position depends on
    them.

    The products take their operands as the chip's default precision
    takes float32 ones, rounded to bfloat16 with float32 accumulation:
    on the chip, at an ambient precision of one bfloat16 pass, float32
    q, k and v are rounded once before the kernel (the same bits the MXU
    would round them to: fast memory holds half the bytes), and
    elsewhere they go in as they are. Scores, ``m``, ``l`` and the
    accumulator are float32; ``scale`` defaults to ``1 / sqrt(hd)``.
    Returns ``[B, P, lanes]`` float32. ``interpret`` defaults to True
    off the TPU, so the CPU tests run the kernel itself."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, rows, width = q.shape
    group = n_heads // n_kv
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    if (
        not interpret and q.dtype == jnp.float32
        and jax.config.jax_default_matmul_precision in _ONE_BF16_PASS
    ):
        q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    tq, tk = _prompt_tiles(rows)
    if hd < 128:  # the heads share tiles: one block of all of them
        nb, q_lanes, kv_lanes = n_kv, width, k.shape[2]
        heads = width // 128 * (128 // hd)  # the last tile's spares too
    else:
        nb = max(
            d for d in range(1, n_kv + 1)
            if n_kv % d == 0
            and (d == 1 or d * group * hd * tq * 4 <= _PROMPT_TILE_BYTES)
        )
        q_lanes, kv_lanes = nb * group * hd, nb * hd
        heads = nb * group
    kernel = functools.partial(
        _prompt_attention_kernel, hd=hd, group=group, window=window,
        scale=1.0 / float(np.sqrt(hd)) if scale is None else scale,
    )
    q_spec = pl.BlockSpec((1, tq, q_lanes), lambda b, h, i, j, _: (b, i, h))

    def kv_block(b, h, i, j, len_ref):
        lo, hi = _prompt_kv_tiles(i, tq, tk, len_ref[b], window)
        return b, jnp.clip(j, lo, hi), h

    kv_spec = pl.BlockSpec((1, tk, kv_lanes), kv_block)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, n_kv // nb, rows // tq, rows // tk),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((heads, tq, 128), jnp.float32),
                pltpu.VMEM((heads, tq, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary",
            ),
        ),
        interpret=interpret,
        name=PROMPT_KERNEL,
    )(lengths, q, k, v)


@functools.partial(jax.jit, static_argnames=("window",))
def causal_prompt_attention(q, k, v, lengths, *, window=0):
    """The one-pass prefill's attention of a layer: ``q`` ``[B, P, n_kv,
    group, hd]`` and ``k`` / ``v`` ``[B, P, n_kv, hd]`` as the block
    hands them over, ``lengths`` ``[B]`` int32 real tokens a row ->
    context ``[B, P, n_kv * group * hd]`` in ``q``'s dtype, through
    :func:`prompt_attention` on the heads' merged lanes. Heads of 64
    lanes share lane tiles, two a tile, in a multi-head model whose
    query tile fits (the tail of the row is padded to a whole tile);
    any other head narrower than a tile is padded to one (zeros add
    nothing to a product), so that a turn of the kernel's loop folds at
    most two heads. Jitted on
    its own: every layer of one window in a program is the same callee,
    traced and lowered once."""
    bsz, rows, n_kv, group, hd = q.shape
    heads = n_kv * group
    shared = (
        hd * 2 == 128 and group == 1
        and _prompt_tiles(rows)[0] * heads * hd * 4 <= _PROMPT_TILE_BYTES
    )
    pad = 0 if shared else -hd % 128

    def merged(x):  # [B, P, n, hd] -> [B, P, whole lane tiles]
        if pad:
            x = jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))
        x = x.reshape(bsz, rows, -1)
        tail = -x.shape[-1] % 128
        return jnp.pad(x, ((0, 0), (0, 0), (0, tail))) if tail else x

    out = prompt_attention(
        merged(q.reshape(bsz, rows, heads, hd)), merged(k), merged(v),
        jnp.asarray(lengths, jnp.int32), n_heads=heads, n_kv=n_kv,
        hd=hd + pad, window=window, scale=1.0 / float(np.sqrt(hd)),
    )
    out = out[..., : heads * (hd + pad)].reshape(bsz, rows, heads, -1)
    return out[..., :hd].reshape(bsz, rows, heads * hd).astype(q.dtype)
