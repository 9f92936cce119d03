"""Ring attention: sequence/context parallelism over the device mesh.

Long sequences are sharded along the sequence axis, one chunk per chip on
the ``sp`` mesh axis. Each chip keeps its query chunk resident and the
key/value chunks rotate around the ring with ``lax.ppermute`` (ICI
neighbor exchange), one hop per step. Every hop streams the visiting
chunk through the flash kernel in carry mode
(:func:`tensorframes_tpu.ops.attention.flash_carry`): the online-softmax
state (m, l, acc) enters the kernel, the chunk passes through VMEM one
[block_k, d] tile at a time, and the updated state comes back. Per-chip
memory is O(chunk + block) — no [L/n, L/n] score matrix ever exists, so
the path scales to the chunk sizes ring attention is for (32k+ per chip).

Causality is resolved per hop at trace level: a visiting chunk is either
entirely in the past (full unmasked kernel), entirely in the future
(skipped — no FLOPs, which is where causal ring wins its 2x), or the
diagonal (causal kernel at offset 0). ``lax.switch`` picks the regime
from the ring-rotated source index, so the math matches a dense causal
mask exactly.

Differentiation is a custom VJP implementing the ring backward: the
forward saves only the output and the per-row log-sum-exp; the backward
re-rotates k/v around the ring, accumulating dq locally while dk/dv ride
the ring with their chunks (n hops return them to their home chip), each
hop running the same two FlashAttention-2 backward kernels the
single-chip VJP uses (:func:`tensorframes_tpu.ops.attention.flash_bwd_pair`).

This is the blockwise/ring formulation (cf. Ring Attention; see PAPERS.md)
— the reference has nothing comparable (no attention, no sequence axis,
SURVEY §5); its closest mechanism, the rows-axis pairwise reduce, shaped
the same "local partials + rotating merge" design used here.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .attention import (
    _NEG_BIG,
    _finalize,
    _fit_tile,
    _lse_sentinel,
    flash_bwd_pair,
    flash_carry,
)
from .seq_common import (
    SEQ_AXIS,
    check_divisible,
    pcast_varying,
    resolve_sp_mesh,
)

__all__ = ["ring_attention", "ring_attention_sharded"]


def _hop_regime(step, my):
    """0 = diagonal (causal kernel), 1 = fully visible (unmasked kernel),
    2 = entirely future (skip). With equal chunk lengths, the chunk
    visiting at ``step`` has source index ``(my - step) % n``; it is fully
    in the past iff ``step <= my`` and the diagonal iff ``step == 0``."""
    return jnp.where(step == 0, 0, jnp.where(step <= my, 1, 2))


def _ring_setup(q, k, axis_name, batch_axis, block_q, block_k):
    """Shared fwd/bwd prologue: ring geometry, fitted tiles, rotation
    permutation, and the variance-marking helper — one source of truth so
    the two loops cannot drift apart."""
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    b, h, lq, d = q.shape
    lc = k.shape[2]
    bq = _fit_tile(block_q, lq)
    bk = _fit_tile(block_k, lc)
    if bq is None or bk is None:
        raise ValueError(
            f"per-chip chunk lengths ({lq}, {lc}) admit no lane-aligned "
            f"tile; pad the sequence to a multiple of 128 per chip"
        )
    perm = [(i, (i + 1) % n) for i in range(n)]

    def _vary(x):
        # the carry inherits q's variance: sp always, plus the batch axis
        # when the batch dim is sharded too (dp x sp composition)
        x = pcast_varying(x, axis_name)
        if batch_axis is not None:
            x = pcast_varying(x, batch_axis)
        return x

    return n, my, (b, h, lq, lc, d), bq, bk, perm, _vary


def _fwd_hop_branches(q, bq, bk, interpret):
    """The three forward hop bodies for ``lax.switch`` (diagonal, fully
    visible, skip); each takes and returns the (m, l, acc) carry with the
    visiting chunk closed in via the operand tuple."""

    def fold(causal):
        def run(args):
            m, l, acc, kc, vc = args
            return flash_carry(
                q, kc, vc, m, l, acc,
                causal=causal, offset=0, block_q=bq, block_k=bk,
                interpret=interpret,
            )

        return run

    def skip(args):
        m, l, acc, _, _ = args
        return m, l, acc

    return (fold(True), fold(False), skip)


def _ring_fwd_loop(
    q, k, v, causal, axis_name, batch_axis, block_q, block_k, interpret
):
    """Run the forward ring. Returns the finalized local output chunk
    ``[B, H, Lq, D]`` and the per-row log-sum-exp ``[BH, Lq, 1]`` the
    backward needs."""
    n, my, (b, h, lq, lc, d), bq, bk, perm, _vary = _ring_setup(
        q, k, axis_name, batch_axis, block_q, block_k
    )
    bh = b * h
    qf = q.reshape(bh, lq, d)
    kf = k.reshape(bh, lc, d)
    vf = v.reshape(bh, lc, d)
    m0 = _vary(jnp.full((bh, lq, 1), _NEG_BIG, dtype=jnp.float32))
    l0 = _vary(jnp.zeros((bh, lq, 1), dtype=jnp.float32))
    acc0 = _vary(jnp.zeros((bh, lq, d), dtype=jnp.float32))
    branches = _fwd_hop_branches(qf, bq, bk, interpret)

    def body(step, carry):
        m, l, acc, kc, vc = carry
        if causal:
            m, l, acc = jax.lax.switch(
                _hop_regime(step, my), branches, (m, l, acc, kc, vc)
            )
        else:
            m, l, acc = branches[1]((m, l, acc, kc, vc))
        # rotate k/v to the next chip (ICI neighbor hop)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return m, l, acc, kc, vc

    m, l, acc, _, _ = jax.lax.fori_loop(0, n, body, (m0, l0, acc0, kf, vf))
    o = _finalize(l, acc).astype(q.dtype).reshape(b, h, lq, d)
    # same sentinel convention as the flash kernel: rows that saw no valid
    # key carry _POS_BIG so the backward recomputes p == 0 for them
    return o, _lse_sentinel(m, l)


def _bwd_hop_branches(qf, dof, lse, delta, bq, bk, interpret, d):
    """The three backward hop bodies: each returns this hop's
    (dq, dk, dv) contributions in f32 (zeros for the skipped regime)."""
    from .attention import _best_blocks_bwd

    f32 = (jnp.float32, jnp.float32, jnp.float32)

    # the dkv kernel's own measured-best tiles (the transposed-score
    # kernel prefers narrow-q/wide-k — _BEST_BLOCKS_BWD) when they fit
    # the hop spans; the hop's fitted tiles otherwise
    def _kv_tiles(lc):
        tuned = _best_blocks_bwd(qf.dtype, d, qf.shape[1], lc)
        return (tuned[2], tuned[3]) if tuned is not None else (bq, bk)

    def pair(causal):
        def run(args):
            kc, vc = args
            dkv_q, dkv_k = _kv_tiles(kc.shape[1])
            return flash_bwd_pair(
                qf, kc, vc, dof, lse, delta,
                causal=causal, offset=0, block_q=bq, block_k=bk,
                dkv_block_q=dkv_q, dkv_block_k=dkv_k,
                interpret=interpret, out_dtypes=f32,
            )

        return run

    def skip(args):
        kc, _ = args
        bh, lq, _ = qf.shape
        lc = kc.shape[1]
        z = jnp.zeros((bh, lq, d), jnp.float32)
        zk = jnp.zeros((bh, lc, d), jnp.float32)
        return z, zk, zk

    return (pair(True), pair(False), skip)


def _ring_bwd_loop(
    q, k, v, o, lse, do, causal, axis_name, batch_axis,
    block_q, block_k, interpret,
):
    """The ring backward: dq accumulates on the home chip; dk/dv for each
    chunk accumulate in a carry that rotates WITH the chunk, so after n
    hops every chunk's gradient has visited every chip that attended to it
    and is back home."""
    n, my, (b, h, lq, lc, d), bq, bk, perm, _vary = _ring_setup(
        q, k, axis_name, batch_axis, block_q, block_k
    )
    bh = b * h
    qf = q.reshape(bh, lq, d)
    kf = k.reshape(bh, lc, d)
    vf = v.reshape(bh, lc, d)
    dof = do.reshape(bh, lq, d)
    delta = (
        dof.astype(jnp.float32) * o.reshape(bh, lq, d).astype(jnp.float32)
    ).sum(axis=-1, keepdims=True)
    dq0 = _vary(jnp.zeros((bh, lq, d), jnp.float32))
    dk0 = _vary(jnp.zeros((bh, lc, d), jnp.float32))
    dv0 = _vary(jnp.zeros((bh, lc, d), jnp.float32))
    branches = _bwd_hop_branches(qf, dof, lse, delta, bq, bk, interpret, d)

    def body(step, carry):
        dq, kc, vc, dkc, dvc = carry
        if causal:
            dq_h, dk_h, dv_h = jax.lax.switch(
                _hop_regime(step, my), branches, (kc, vc)
            )
        else:
            dq_h, dk_h, dv_h = branches[1]((kc, vc))
        dq = dq + dq_h
        dkc = dkc + dk_h
        dvc = dvc + dv_h
        # the visiting chunk AND its gradient hop together
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        dkc = jax.lax.ppermute(dkc, axis_name, perm)
        dvc = jax.lax.ppermute(dvc, axis_name, perm)
        return dq, kc, vc, dkc, dvc

    dq, _, _, dk, dv = jax.lax.fori_loop(
        0, n, body, (dq0, kf, vf, dk0, dv0)
    )
    return (
        dq.astype(q.dtype).reshape(b, h, lq, d),
        dk.astype(k.dtype).reshape(b, h, lc, d),
        dv.astype(v.dtype).reshape(b, h, lc, d),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_core(
    q, k, v, causal, axis_name, batch_axis, block_q, block_k, interpret
):
    o, _ = _ring_fwd_loop(
        q, k, v, causal, axis_name, batch_axis, block_q, block_k, interpret
    )
    return o


def _ring_core_fwd(
    q, k, v, causal, axis_name, batch_axis, block_q, block_k, interpret
):
    o, lse = _ring_fwd_loop(
        q, k, v, causal, axis_name, batch_axis, block_q, block_k, interpret
    )
    return o, (q, k, v, o, lse)


def _ring_core_bwd(
    causal, axis_name, batch_axis, block_q, block_k, interpret, res, do
):
    q, k, v, o, lse = res
    return _ring_bwd_loop(
        q, k, v, o, lse, do, causal, axis_name, batch_axis,
        block_q, block_k, interpret,
    )


_ring_core.defvjp(_ring_core_fwd, _ring_core_bwd)


def ring_attention_sharded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    axis_name: str = SEQ_AXIS,
    batch_axis=None,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
):
    """The per-shard body: call inside ``shard_map`` with q/k/v sequence
    chunks ``[B, H, L/n, D]`` sharded over ``axis_name``. Returns the local
    output chunk. Differentiable (ring-backward custom VJP).

    Causal mode requires equal q/k chunk lengths (the hop regimes assume
    aligned diagonals). ``interpret=None`` follows the DEFAULT backend's
    platform — when your shard_map targets a non-default backend (e.g. a
    virtual CPU mesh on a TPU box), pass ``interpret`` explicitly;
    :func:`ring_attention` derives it from the mesh for you."""
    if causal and q.shape[2] != k.shape[2]:
        raise ValueError(
            f"causal ring attention requires equal q/k chunk lengths "
            f"(got {q.shape[2]} and {k.shape[2]})"
        )
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return _ring_core(
        q, k, v, causal, axis_name, batch_axis, block_q, block_k, interpret
    )


@functools.lru_cache(maxsize=64)
def _ring_program(
    mesh, causal: bool, axis_name: str, batch_axis, block_q, block_k,
    interpret,
):
    """One jitted shard_map program per (mesh, causal, axis, tiles) —
    cached so repeated calls (every transformer layer, every step) hit the
    jit cache instead of retracing."""
    from jax.sharding import PartitionSpec as P

    spec = P(batch_axis, None, axis_name, None)
    return jax.jit(
        jax.shard_map(
            functools.partial(
                ring_attention_sharded,
                causal=causal,
                axis_name=axis_name,
                batch_axis=batch_axis,
                block_q=block_q,
                block_k=block_k,
                interpret=interpret,
            ),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            # pallas_call results carry no VMA annotation, so the checker
            # cannot type the carry kernel's outputs (same setting as
            # ulysses/moe/pipeline); collective correctness is covered by
            # the oracle tests instead
            check_vma=False,
        )
    )


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh=None,
    causal: bool = False,
    axis_name: str = SEQ_AXIS,
    batch_axis=None,
    block_q: int = 1024,
    block_k: int = 1024,
):
    """Full-array entry point: shards ``[B, H, L, D]`` inputs over the
    mesh's ``axis_name`` axis, runs the ring, and returns the assembled
    ``[B, H, L, D]`` output. ``L`` must divide by the axis size.
    ``batch_axis`` additionally shards the batch dim over another mesh
    axis (dp x sp composition in one program; the ring body is batch-
    agnostic, so only the specs change).

    Per-chip chunk lengths must admit a lane-aligned kernel tile (be a
    multiple of 128, or short enough to be a single tile) — unlike the
    pre-blockwise implementation, which accepted any length but built the
    full [L/n, L/n] score matrix per hop and could not reach long
    contexts at all. Pad the sequence when this errors."""
    mesh = resolve_sp_mesh(mesh, axis_name)
    check_divisible(
        mesh.shape[axis_name], axis_name,
        q_seq_len=q.shape[2], k_seq_len=k.shape[2],
    )
    if causal and q.shape[2] != k.shape[2]:
        raise ValueError(
            f"causal ring attention requires equal q/k sequence lengths "
            f"(got {q.shape[2]} and {k.shape[2]}); use flash_attention "
            f"for cross-length causal decoding"
        )
    if batch_axis is not None:
        if batch_axis not in mesh.shape:
            raise ValueError(
                f"batch_axis {batch_axis!r} is not a mesh axis; mesh has "
                f"{tuple(mesh.shape)}"
            )
        check_divisible(
            mesh.shape[batch_axis], batch_axis, batch=q.shape[0]
        )
    # interpret must follow the MESH's devices, not the default backend:
    # the multichip dryrun runs this over virtual CPU devices on a box
    # whose default platform is a TPU
    interpret = mesh.devices.flat[0].platform != "tpu"
    return _ring_program(
        mesh, causal, axis_name, batch_axis, block_q, block_k, interpret
    )(q, k, v)
