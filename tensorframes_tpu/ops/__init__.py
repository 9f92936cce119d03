"""Hot-op kernels: Pallas flash attention + ring/Ulysses sequence
parallelism."""

from .attention import (
    attention_reference,
    flash_attention,
    online_block_update,
    paged_attention,
    paged_attention_live,
    paged_page_size_hint,
    ragged_paged_attention,
)
from .ring import ring_attention, ring_attention_sharded
from .ulysses import ulysses_attention, ulysses_attention_sharded

__all__ = [
    "flash_attention",
    "attention_reference",
    "paged_attention",
    "paged_attention_live",
    "ragged_paged_attention",
    "paged_page_size_hint",
    "online_block_update",
    "ring_attention",
    "ring_attention_sharded",
    "ulysses_attention",
    "ulysses_attention_sharded",
]
