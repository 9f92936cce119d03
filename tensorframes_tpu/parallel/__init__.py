"""Distributed execution over TPU device meshes.

Replaces the reference's Spark plane (partitions/broadcast/shuffle/driver
funnel, SURVEY §2.5) with ``shard_map`` programs and XLA collectives.
"""

from .mesh import make_mesh, default_mesh, data_axis
from .distributed import map_blocks, map_rows, reduce_blocks, reduce_rows, aggregate
from .training import ShardedSGDTrainer
from .moe import (
    init_moe,
    moe_apply,
    moe_dispatch_apply,
    moe_ffn,
    moe_load_balance_loss,
)
from .pipeline import pipeline_apply, pipeline_reference
from . import multihost

__all__ = [
    "multihost",
    "init_moe",
    "moe_apply",
    "moe_dispatch_apply",
    "moe_ffn",
    "moe_load_balance_loss",
    "pipeline_apply",
    "pipeline_reference",
    "make_mesh",
    "default_mesh",
    "data_axis",
    "map_blocks",
    "map_rows",
    "reduce_blocks",
    "reduce_rows",
    "aggregate",
    "ShardedSGDTrainer",
]
