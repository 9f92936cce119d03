"""Self-tuning performance layer: online autotuner + persisted cost
model serving tuned configs fleet-wide (ROADMAP item 3).

Every perf-critical constant in the stack used to be a hand-measured
static — the ``_BEST_BLOCKS`` tile tables, ``paged_page_size_hint``'s
serving default, ``Config.transfer_chunk_bytes`` / ``transfer_streams``,
``serve_prefill_chunk_tokens``, the map-rows block-row budget — and the
r05 bench rounds showed those go stale the moment the link or the
shapes change. This package replaces them with three cooperating
pieces:

- :mod:`.search` — the online autotuner: on first sight of a (shape,
  dtype, op-kind) signature it micro-benchmarks a candidate grid inside
  the existing retry/chaos envelopes, picks the winner by median wall,
  and installs it for all subsequent dispatches of that signature;
- :mod:`.store` — the persisted tuning database: JSONL next to the XLA
  compile cache, atomic-rename writes, schema-versioned, keyed by
  signature + device kind; winners survive restarts and are shared
  fleet-wide through the same file (mtime re-read);
- :mod:`.model` — the learned cost predictor (ridge/analytic hybrid
  over the observatory's per-program FLOP/byte/wall records) that ranks
  the grid so measured trials cover only the top-K predicted configs.

Consumers: ``ops/attention.py`` (tile lookup — the static tables become
the seed prior), ``frame/transfer.py`` (chunk bytes × streams),
``serve/engine.py`` (page size + prefill chunk), ``engine/ops.py``
(block-row budget). Knobs: ``Config.autotune`` /
``Config.tune_mode="off"|"cached"|"online"`` / ``Config.tune_budget_s``
and the ``TFT_TUNE=0`` kill switch. The contract: tuning changes which
config runs, **never** what it computes — every tuned surface is
byte-identity-tested against its static default. See docs/tuning.md.
"""

from .model import (
    CostModel,
    default_model,
    load_cost_records,
    per_chip_records,
)
from .search import (
    Tuner,
    clear,
    in_trial,
    jobs_signature,
    lookup,
    mode,
    pin,
    rank_tp_layouts,
    render_table,
    reset,
    serve_signature,
    snapshot,
    tune_serve_knobs,
    tuner,
)
from .store import SCHEMA_VERSION, TuneStore, device_kind, store_path

__all__ = [
    "CostModel",
    "SCHEMA_VERSION",
    "TuneStore",
    "Tuner",
    "clear",
    "default_model",
    "device_kind",
    "in_trial",
    "jobs_signature",
    "load_cost_records",
    "lookup",
    "mode",
    "per_chip_records",
    "pin",
    "rank_tp_layouts",
    "render_table",
    "reset",
    "serve_signature",
    "snapshot",
    "store_path",
    "tune_serve_knobs",
    "tuner",
]
