"""Ulysses (all-to-all) sequence parallelism.

The second of the two standard long-context shardings (the other is the
ring, :mod:`tensorframes_tpu.ops.ring`; the reference has neither — its
only scalable axis is rows, SURVEY §5 "long context: absent"):

- **ring**: K/V chunks rotate around the ``sp`` axis via ``ppermute``
  (neighbor hops on ICI); communication overlaps compute, memory per chip
  stays O(L/n), and any head count works.
- **ulysses**: two ``all_to_all`` exchanges re-shard the activations from
  sequence-sharded ``[B, H, L/n, D]`` to head-sharded ``[B, H/n, L, D]``,
  run ordinary (flash) attention on the FULL sequence for a subset of
  heads, and shard back. Communication is two collective transposes total
  (vs n ppermute hops), and the attention itself is the plain kernel —
  but it needs ``H % n == 0`` and O(L) sequence memory per chip.

Use ulysses when heads are plentiful and the sequence fits per-chip after
the exchange; use the ring when the sequence itself must stay sharded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .attention import flash_attention
from .seq_common import (
    SEQ_AXIS,
    check_divisible,
    resolve_sp_mesh,
)

__all__ = ["ulysses_attention", "ulysses_attention_sharded"]


def ulysses_attention_sharded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    axis_name: str = SEQ_AXIS,
    interpret=None,
):
    """Per-shard body: call inside ``shard_map`` with q/k/v sequence chunks
    ``[B, H, L/n, D]`` sharded over ``axis_name``; returns the local output
    chunk. Heads must divide by the axis size."""
    n = jax.lax.axis_size(axis_name)
    h = q.shape[1]
    if h % n:
        raise ValueError(
            f"ulysses needs heads ({h}) divisible by the {axis_name!r} "
            f"axis size ({n}); use ring attention otherwise"
        )

    def seq_to_heads(t):
        # [B, H, L/n, D] -> [B, H/n, L, D]: split the head axis n ways,
        # exchange, concatenate the received pieces along the sequence
        return jax.lax.all_to_all(
            t, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    def heads_to_seq(t):
        return jax.lax.all_to_all(
            t, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # full sequence per chip for H/n heads: plain flash attention, and the
    # causal mask needs no offset bookkeeping (unlike the ring)
    oh = flash_attention(qh, kh, vh, causal=causal, interpret=interpret)
    return heads_to_seq(oh)


@functools.lru_cache(maxsize=64)
def _ulysses_program(mesh, causal: bool, axis_name: str, batch_axis=None):
    from jax.sharding import PartitionSpec as P

    # interpret must follow the MESH's devices, not the default backend:
    # the multichip dryrun runs this over virtual CPU devices on a box
    # whose default platform is a TPU
    interpret = mesh.devices.flat[0].platform != "tpu"
    spec = P(batch_axis, None, axis_name, None)
    return jax.jit(
        jax.shard_map(
            functools.partial(
                ulysses_attention_sharded,
                causal=causal,
                axis_name=axis_name,
                interpret=interpret,
            ),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            # the pallas flash kernel does not annotate varying-mesh-axes
            # on its out_shape; every input/output here is uniformly
            # sharded by construction, so the check adds nothing
            check_vma=False,
        )
    )


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh=None,
    causal: bool = False,
    axis_name: str = SEQ_AXIS,
    batch_axis=None,
):
    """Full-array entry point: shards ``[B, H, L, D]`` over the mesh's
    ``axis_name`` axis, re-shards to heads with one collective transpose,
    attends, and shards back. ``L`` and ``H`` must divide by the axis
    size. ``batch_axis`` additionally shards the batch dim over another
    mesh axis (dp x sp composition in one program, like the ring — the
    all_to_all exchanges ride the sp axis only, so the body is
    batch-agnostic)."""
    mesh = resolve_sp_mesh(mesh, axis_name)
    n = mesh.shape[axis_name]
    check_divisible(
        n, axis_name, q_seq_len=q.shape[2], k_seq_len=k.shape[2]
    )
    if q.shape[1] % n:
        raise ValueError(
            f"head count {q.shape[1]} must divide by the {axis_name} axis "
            f"size {n}; use ring_attention for head counts < the axis size"
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
    return _ulysses_program(mesh, causal, axis_name, batch_axis)(q, k, v)
