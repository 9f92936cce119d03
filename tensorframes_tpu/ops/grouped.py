"""Grouped matrix product: rows that belong to different experts against
each expert's own weight, as one Pallas kernel.

The rows arrive in TILES of ``tile_rows``, every tile belonging to one
expert (the caller pads each expert's run of rows up to whole tiles,
:func:`tile_layout`), so the kernel needs no mask inside a tile: grid
step ``i`` multiplies tile ``i`` by ``w[tile_expert[i]]``. The expert
index is a scalar-prefetch input, so the weight's BlockSpec chases it
and the pipeline fetches an expert's ``[K, N]`` matrix when the expert
changes and not again for its further tiles: every held expert's weight
crosses HBM->VMEM once, which is the least a layer can read. Tiles past
the last real one repeat the last expert (no fetch) and skip the
product.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["grouped_matmul", "tile_layout", "KERNEL_NAME"]

#: the kernel's name in a device trace
KERNEL_NAME = "grouped_expert_matmul"


def tile_layout(expert_of_pair, n_experts: int, tile_rows: int):
    """Where each token-expert pair's row goes when every expert's rows
    are padded up to whole tiles.

    ``expert_of_pair`` ``[P]`` int32 (``n_experts`` = held by nobody
    here). Returns ``(row_of_pair [P], tile_expert [T], n_active,
    sizes [n_experts])`` with ``T = ceil(P / tile_rows) + n_experts``
    tiles in all (an upper bound on ``sum(ceil(size / tile_rows))``):
    pair ``p`` sits at row ``row_of_pair[p]`` of the ``T * tile_rows``
    padded rows (``T * tile_rows`` itself, one past the end, for a pair
    nobody here holds), tile ``i`` belongs to ``tile_expert[i]``, and
    tiles ``>= n_active`` hold no row."""
    pairs = expert_of_pair.shape[0]
    n_tiles = -(-pairs // tile_rows) + n_experts
    sizes = jnp.zeros((n_experts + 1,), jnp.int32).at[expert_of_pair].add(1)
    sizes = sizes[:n_experts]
    tiles = -(-sizes // tile_rows)
    tile_end = jnp.cumsum(tiles)
    tile_start = tile_end - tiles
    # rank of a pair among its expert's pairs, in pair order
    order = jnp.argsort(expert_of_pair, stable=True)
    sorted_pos = jnp.zeros((pairs,), jnp.int32).at[order].set(
        jnp.arange(pairs, dtype=jnp.int32)
    )
    group_start = jnp.cumsum(sizes) - sizes
    safe = jnp.minimum(expert_of_pair, n_experts - 1)
    rank = sorted_pos - group_start[safe]
    row = tile_start[safe] * tile_rows + rank
    row = jnp.where(expert_of_pair < n_experts, row, n_tiles * tile_rows)
    n_active = tile_end[-1]
    tile_expert = jnp.searchsorted(
        tile_end, jnp.arange(n_tiles, dtype=jnp.int32), side="right"
    ).astype(jnp.int32)
    last = jnp.take(tile_expert, jnp.maximum(n_active - 1, 0))
    tile_expert = jnp.where(
        jnp.arange(n_tiles) < n_active, tile_expert, last
    )
    return row.astype(jnp.int32), tile_expert, n_active.astype(jnp.int32), sizes


def _kernel(tile_expert_ref, n_active_ref, x_ref, w_ref, o_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) < n_active_ref[0])
    def _():
        o_ref[...] = jnp.dot(
            x_ref[...], w_ref[0], preferred_element_type=jnp.float32
        ).astype(o_ref.dtype)


def grouped_matmul(
    x, w, tile_expert, n_active, tile_rows: int,
    interpret: Optional[bool] = None,
):
    """``x`` ``[T * tile_rows, K]`` (tile ``i`` = rows ``i * tile_rows
    ..``, all of expert ``tile_expert[i]``) times ``w`` ``[E, K, N]`` ->
    ``[T * tile_rows, N]`` float32. Rows of tiles ``>= n_active`` are
    left as they are (never read by the caller). ``interpret`` defaults
    to True off-TPU so tests run on the CPU."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, k = x.shape
    n = w.shape[-1]
    n_tiles = rows // tile_rows
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile_rows, k), lambda i, te, na: (i, 0)),
            pl.BlockSpec((1, k, n), lambda i, te, na: (te[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_rows, n), lambda i, te, na: (i, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.float32),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=48 * 1024 * 1024,
        ),
        interpret=interpret,
        name=KERNEL_NAME,
    )(
        tile_expert, jnp.reshape(n_active, (1,)), x.astype(w.dtype), w
    )
