"""Precision / device configuration.

The reference has no config system at all (SURVEY §5: per-op configuration is
the ``ShapeDescription`` hint object; the UDAF buffer size is a hard-coded
``10``, ``DebugRowOps.scala:573``). Knobs live here only once something
consumes them.
"""

from __future__ import annotations

import dataclasses
import os
import threading

__all__ = [
    "Config",
    "get_config",
    "set_config",
    "ensure_x64",
    "compilation_cache_dir",
    "enable_compilation_cache",
]


@dataclasses.dataclass(frozen=True)
class Config:
    #: columns whose host size exceeds this are fed to the engine one
    #: partition block at a time instead of being memoized whole on device —
    #: bounds HBM use for frames larger than device memory
    #: (consumed by engine/ops.py and parallel/distributed.py).
    device_cache_bytes: int = 4 << 30
    #: upper bound on rows per vmapped device call in ``map_rows`` shape
    #: buckets; a bucket larger than this executes in chunks so activation
    #: memory stays bounded (conv/attention programs can blow up HBM far
    #: beyond the input bytes). Consumed by engine/ops.py.
    max_rows_per_device_call: int = 8192
    #: the device-resident ``map_rows`` fast path may RAISE its chunk above
    #: ``max_rows_per_device_call`` until a chunk's input+output bytes
    #: reach this bound — tiny rows (scalars, small vectors) dispatch in a
    #: few large calls instead of hundreds of row-capped ones (each
    #: dispatch costs link latency; an OOM on a raised chunk halves it
    #: back toward the row cap without leaving the device-resident path).
    #: Consumed by engine/ops.py.
    max_bytes_per_device_call: int = 64 << 20
    #: chunk size for the streaming host↔device transfer layer
    #: (``frame/transfer.py``): column-sized payloads cross the link as
    #: row chunks of at most this many bytes, several in flight at once,
    #: so consumers overlap compute with the chunks still in the air.
    #: ``<= 0`` restores the monolithic single-``device_put`` path
    #: (still retried and counted). See docs/ingest.md for tuning.
    transfer_chunk_bytes: int = 64 << 20
    #: width of the transfer thread pool: how many chunks are in flight
    #: concurrently, per direction. A single stream cannot fill a
    #: high-latency link; more streams pipeline against each other until
    #: the link saturates (guidance in docs/ingest.md).
    transfer_streams: int = 4
    #: optional WIRE cast for float32 payloads: ``"bf16"`` crosses the
    #: link as bfloat16 (half the link bytes) and upcasts back to
    #: float32 on device — schemas, programs, and device dtypes are
    #: untouched, only the values round to bf16 precision (the accuracy
    #: trade the bf16 bench mode measures; see docs/ingest.md caveats).
    #: ``""`` (default) transfers verbatim — the byte-identity mode.
    transfer_dtype: str = ""
    #: retries for transient device-runtime failures (UNAVAILABLE /
    #: DEADLINE_EXCEEDED / dropped connection); see utils/failures.py. The
    #: reference rode Spark's task retry instead (SURVEY §5).
    max_retries: int = 2
    #: base of the exponential retry backoff, seconds.
    retry_backoff_s: float = 0.5
    #: master switch for the observability layer (``tensorframes_tpu.obs``):
    #: False makes every counter increment, histogram observation, and
    #: span a no-op. ``TFT_OBS=0`` in the environment forces the same off
    #: state regardless of this field (read once at import).
    observability: bool = True
    #: cadence of the time-series sampler (``obs/timeseries.py``): while
    #: the sampler is running (a live ``ScoringServer`` holds it, or
    #: ``obs.timeseries.acquire_sampler()``), every registered gauge,
    #: counter-derived rate, and histogram p50/p99 is snapshotted into
    #: the in-process ring-buffer store — and ``GET /varz`` / the SLO
    #: monitors read from it — once per this many seconds. ``<= 0``
    #: parks the sampler (the store only moves via explicit
    #: ``sample_once()`` calls). Re-read every tick, so retunes apply
    #: without a restart.
    obs_sample_interval_s: float = 1.0
    #: how long synchronous consumers of a generation handle wait before
    #: declaring the stream lost: ``GenerationEngine.generate`` and the
    #: HTTP ``POST /generate`` endpoint both call
    #: ``handle.result(timeout=this)``. With the serving supervisor a
    #: doomed stream is failed within a step, so this is a last-resort
    #: backstop, not the primary failure path (docs/serving_llm.md).
    serve_result_timeout_s: float = 300.0
    #: chunked prefill: prompts longer than this many tokens prefill in
    #: fixed chunks of this size, one chunk per engine step, interleaved
    #: with decode steps — bounding the stall one long prompt imposes on
    #: the whole decode batch. ``0`` (default) prefills every prompt in
    #: one pass. Per-engine override:
    #: ``GenerationEngine(prefill_chunk_tokens=...)``.
    serve_prefill_chunk_tokens: int = 0
    #: shared-prefix KV caching (``serve/kv_pages.py:PrefixCache``):
    #: finished prefills register their prompt's complete pages, and new
    #: requests with an identical page-aligned prefix share those pages
    #: (refcounted, copy-on-write on in-page divergence) and skip
    #: prefilling the shared span. Per-engine override:
    #: ``GenerationEngine(prefix_cache=...)``.
    serve_prefix_cache: bool = False
    #: fault-injection (chaos) schedule spec, e.g.
    #: ``"seed=7;serve.decode_step=transient:p=0.2;kv_pages.alloc=pool:every=9"``.
    #: Empty (the default) disables every injection site down to a single
    #: module-global check; the ``TFT_CHAOS`` environment variable
    #: supplies the spec when this field is empty. Grammar and site list:
    #: ``utils/chaos.py`` and docs/fault_tolerance.md.
    chaos: str = ""
    #: root directory for durable batch-job journals
    #: (``engine/jobs.py``). Empty means ``$TFT_JOB_DIR`` or
    #: ``~/.cache/tensorframes_tpu/jobs``; each job gets its own
    #: subdirectory named by its job id.
    job_dir: str = ""
    #: whether :func:`tensorframes_tpu.engine.jobs.run_job` journals by
    #: default. ``run_job(..., journal=False)`` (or this field False)
    #: keeps the job's block loop and quarantine semantics but writes
    #: nothing to disk — the overhead-comparison / test mode.
    journal_batch_jobs: bool = True
    #: distributed batch jobs (``engine/dist_jobs.py``): how long a
    #: worker's block lease stays valid without a heartbeat renewal.
    #: The liveness-vs-safety knob — a crashed worker's blocks are
    #: reclaimable only after this long, but a *live* worker whose
    #: heartbeats stall longer than this is presumed dead and its block
    #: stolen (the late write is then fence-rejected). Must comfortably
    #: exceed worst-case heartbeat jitter + filesystem latency + clock
    #: skew between workers. Per-worker override: ``run_worker(lease_ttl_s=)``.
    job_lease_ttl_s: float = 30.0
    #: heartbeat renewal interval for held leases. ``0`` (default)
    #: means ``job_lease_ttl_s / 3`` — three chances to renew before
    #: expiry. Per-worker override: ``run_worker(heartbeat_s=)``.
    job_heartbeat_s: float = 0.0
    #: serving-fleet membership lease TTL (``serve/membership.py``): a
    #: member whose registry heartbeats stall longer than this is
    #: presumed dead, fenced by the router (epoch tombstone — its late
    #: registry writes raise ``StaleLeaseError``), and its in-flight
    #: streams are replayed on survivors. Shorter than the job TTL:
    #: serving failover is latency-sensitive where batch reclamation is
    #: not. Per-member override: ``MemberRegistry(ttl_s=)``.
    member_lease_ttl_s: float = 10.0
    #: membership heartbeat renewal interval. ``0`` (default) means
    #: ``member_lease_ttl_s / 3``. Per-member override:
    #: ``MemberRegistry(heartbeat_s=)``.
    member_heartbeat_s: float = 0.0
    #: directory for the flight recorder's debug bundles
    #: (``obs/flight.py``: the JSON dumped on an engine fatal,
    #: ``restart()``, block quarantine, or write-fence reject). Empty
    #: means ``$TFT_DEBUG_DIR`` or ``~/.cache/tensorframes_tpu/debug``.
    debug_bundle_dir: str = ""
    #: default quarantine policy for batch jobs: True returns partial
    #: results (``JobResult.completed`` + ``.quarantined``) when a block
    #: fails deterministically; False (strict) raises
    #: ``QuarantinedBlocksError`` at job end instead. Per-job override:
    #: ``run_job(..., strict=)``.
    quarantine_blocks: bool = True
    #: master switch for the lazy logical-plan layer (``engine/plan.py``):
    #: chained frame ops record plan nodes and are optimized once, then
    #: lowered to the ordinary dispatch when a fetch forces them. False
    #: restores strict op-at-a-time execution everywhere (the rewrite
    #: passes below are then moot). See docs/pipelines.md.
    plan_lazy_ops: bool = True
    #: plan rewrite pass 1 — **map fusion**: a chain of ``map_rows`` /
    #: ``map_blocks`` ops collapses into one jitted composite body, so N
    #: chained maps cost one compiled program and one pass over the data.
    plan_fuse_maps: bool = True
    #: plan rewrite pass 2 — **column pruning**: ops none of whose fetches
    #: are demanded downstream (by a ``select`` / ``reduce_blocks`` /
    #: ``aggregate`` consumer) are dropped from the plan, so the source
    #: columns only they bound never cross the host→device link.
    plan_prune_columns: bool = True
    #: plan rewrite pass 3 — **reduction hoisting**: a ``reduce_blocks``
    #: over a pending map chain folds into the map program's per-block
    #: epilogue — one program computes map outputs AND the block partial;
    #: partials still merge through the reduce's own ``[2, ...]`` program.
    plan_hoist_reduce: bool = True
    #: master switch for the self-tuning performance layer
    #: (``tensorframes_tpu.tune``): False makes every tuned surface
    #: (attention tiles, transfer chunk/streams, serve page size +
    #: prefill chunk, map-rows block-row budget) fall straight back to
    #: its static default. ``TFT_TUNE=0`` in the environment forces the
    #: same off state regardless of this field (checked live, per
    #: call, not once at import). See docs/tuning.md.
    autotune: bool = True
    #: tuning mode when ``autotune`` is on: ``"cached"`` (default)
    #: serves winners from the persisted tuning store but never runs a
    #: measurement trial; ``"online"`` additionally micro-benchmarks the
    #: candidate grid on first sight of an unseen signature and installs
    #: + persists the winner; ``"off"`` equals ``autotune=False``.
    tune_mode: str = "cached"
    #: wall-clock budget for one signature's online tuning pass,
    #: seconds: candidates are measured in predicted-cost order until
    #: the budget runs out, and the winner is picked among whatever was
    #: measured (the static default is always measured first, so a
    #: budget too small for the grid degrades to "keep the default").
    tune_budget_s: float = 2.0
    #: timed repeats per measured candidate (the winner is the
    #: median-wall candidate; one untimed warmup per candidate pays any
    #: compile cost outside the measurement).
    tune_trials: int = 3
    #: cap on candidates measured per signature AFTER the learned cost
    #: model ranks the grid — measured trials cover only the top-K
    #: predicted configs, and never more than half the full grid.
    tune_top_k: int = 4
    #: path of the persisted tuning store (JSONL). Empty means
    #: ``$TFT_TUNE_FILE``, else ``tune.jsonl`` inside the compile-cache
    #: directory (:func:`compilation_cache_dir`).
    tune_file: str = ""
    #: shared directory for the fleet telemetry plane
    #: (``obs/export.py``): every process with a live sampler snapshots
    #: its metric registry + time-series store to
    #: ``<dir>/<proc-id>.json`` (atomic rename), and the read side
    #: (``obs/aggregate.py``, ``GET /varz?scope=fleet``) merges whatever
    #: snapshots it finds there. Empty means ``$TFT_TELEMETRY_DIR``;
    #: empty both ways disables export entirely.
    telemetry_dir: str = ""
    #: minimum seconds between telemetry snapshot writes. The exporter
    #: rides the time-series sampler tick, so the effective cadence is
    #: ``max(obs_sample_interval_s, this)``. Re-read every tick.
    obs_export_interval_s: float = 2.0
    #: a telemetry snapshot whose file mtime is older than this many
    #: seconds marks its process ``stale`` in every merged fleet view —
    #: flagged, never dropped, so a kill -9'd worker's last counters
    #: stay visible (docs/observability.md "Fleet telemetry").
    telemetry_stale_after_s: float = 15.0
    #: per-tenant QoS policies (``serve/tenancy.py``): a tuple of plain
    #: dicts, one per tenant, each shaped like ``{"tenant": "acme",
    #: "priority": "batch"|"standard"|"interactive", "max_active": N,
    #: "max_queued": N, "requests_per_s": R, "tokens_per_s": T,
    #: "ttft_slo_s": S}`` — every field but ``tenant`` optional, 0/absent
    #: = unlimited/none. The EMPTY default means the whole QoS plane is
    #: off: no admission checks, FIFO scheduling, preempt-youngest —
    #: byte-identical to the pre-tenancy engine at zero per-step cost
    #: (the on/off gate is a module global refreshed by the set_config
    #: callback hook, the TFT_OBS/chaos pattern). Also settable at
    #: runtime via ``POST /admin/tenants``. See docs/serving_llm.md
    #: "Multi-tenancy".
    tenants: tuple = ()
    #: master switch for the router's durable request plane
    #: (``serve/router_ha.py``): the per-request WAL, request_id
    #: dedupe/stream resume on ``POST /generate``, and standby
    #: takeover resubmission. The FALSE default means the whole plane
    #: is off — no WAL writes, no per-request tracker, streams
    #: byte-identical to the pre-WAL serving path at zero per-token
    #: cost (the on/off gate is a module global refreshed by the
    #: set_config callback hook, the tenancy/chaos pattern). See
    #: docs/fault_tolerance.md "Router HA".
    router_wal: bool = False
    #: TTL of the router-election lease (``serve/router_ha.py``): a
    #: standby detects active-router death after at most this long and
    #: takes over at epoch+1. Shorter than the member TTL — router
    #: takeover is on the client-visible path where member fencing
    #: already hides behind stream replay. Per-router override:
    #: ``RouterHA(ttl_s=)``.
    router_lease_ttl_s: float = 3.0
    #: first-token tier handoff (``serve/tiers.py`` +
    #: ``serve/fleet.py``): in a fleet with prefill/decode tier labels,
    #: a request prefills on prefill capacity and its KV pages migrate
    #: to a decode replica once the first token is out. False keeps
    #: tier labels as a routing preference only (streams stay where
    #: they prefilled). Irrelevant when every replica is ``mixed``.
    tier_handoff: bool = True
    #: pool-pressure rebalancing: before the scheduler preempts a
    #: victim for pages, the fleet tries migrating the victim's KV
    #: pages to the least-loaded decode-capable replica instead
    #: (``Scheduler.on_pressure``). False restores pure
    #: preempt-youngest. Preemption always remains the fallback.
    tier_rebalance: bool = True


_lock = threading.Lock()
_config = Config()

#: callbacks run after every set_config — lets hot paths cache derived
#: flags (e.g. the observability on/off gate) as plain module globals
#: instead of re-deriving them per call
_on_change: list = []


def register_on_change(cb) -> None:
    """Run ``cb()`` now and after every future :func:`set_config`."""
    _on_change.append(cb)
    cb()


def get_config() -> Config:
    return _config


def set_config(**kwargs) -> Config:
    global _config
    with _lock:
        _config = dataclasses.replace(_config, **kwargs)
    for cb in _on_change:
        cb()
    return _config


#: where the compile cache lives when ``JAX_COMPILATION_CACHE_DIR`` does
#: not place it: one fixed, git-ignored directory at the root of the
#: checkout this package was imported from. The path is part of nothing
#: jax keys on, but a directory that moves between processes never hits.
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

_cache_enabled_dir: "str | None" = None


def compilation_cache_dir() -> str:
    """The compile-cache directory this process uses (or would use, with
    the cache disabled): ``$JAX_COMPILATION_CACHE_DIR`` when set, else
    ``<checkout>/.jax_cache``. The tuning store keeps ``tune.jsonl`` in
    the same directory, so one variable places both."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_CACHE_DIR


def enable_compilation_cache() -> "str | None":
    """Turn on XLA's persistent compilation cache for this process.

    The reference pays zero compile cost — a TF 1.x session executes its
    GraphDef immediately (``TensorFlowOps.scala:76-95``) — while every
    fresh JAX process re-traces and re-compiles each program from
    scratch. With the cache on, compiles are keyed on (HLO, compile
    options, backend) and later processes reload the serialized
    executables instead of compiling.

    Called on ``import tensorframes_tpu`` (opt out with
    ``TFT_NO_COMPILE_CACHE=1``). Idempotent; returns the directory in
    use (:func:`compilation_cache_dir`), or ``None`` when disabled.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already points at
    that directory and this function sets no other; where it is not,
    the cache goes to the fixed in-checkout directory.

    Either way jax's two admission thresholds are lowered: entries are
    kept from 0.1 s of compile time (jax's default is 1.0 s) and with no
    size floor, because engine passes and the serving steps dispatch
    many sub-second programs (fold programs, vmap buckets, page-pool
    rewrites) whose re-compiles dominate a short job's start-up.
    Entries are content-addressed, so a directory shared by concurrent
    processes is safe."""
    global _cache_enabled_dir
    if os.environ.get("TFT_NO_COMPILE_CACHE", "") not in ("", "0"):
        return None
    with _lock:
        if _cache_enabled_dir is not None:
            return _cache_enabled_dir
        import jax

        path = compilation_cache_dir()
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        try:
            os.makedirs(path, exist_ok=True)
            writable = os.access(path, os.W_OK)
        except OSError:
            writable = False
        if not writable:
            # this function runs its body once per process, so this
            # warns once: jax would otherwise skip every write silently
            from .logging import get_logger

            get_logger("config").warning(
                "compile cache directory %s is not writable; every "
                "process will compile from scratch", path,
            )
        _cache_enabled_dir = path
        return path


_x64_done = False


def ensure_x64() -> None:
    """Enable jax 64-bit types on demand.

    The reference's parity dtype set includes float64/int64
    (``datatypes.scala:265-267``) and its README examples round-trip doubles;
    JAX disables x64 by default, so the engine flips it lazily the first time
    a 64-bit column reaches a device computation."""
    global _x64_done
    if _x64_done:
        return
    with _lock:
        if not _x64_done:
            import jax

            jax.config.update("jax_enable_x64", True)
            _x64_done = True
