"""Per-program cost registry: what every compiled program costs, forever.

Compiled programs are this engine's unit of spend — the graph-memo
programs of ``engine/ops.py``, the ≤ 3 serving step programs of
``serve/engine.py``, and the fused plan composites of
``engine/plan.py`` — yet until now nothing recorded what any of them
cost to build or to run: the bench trajectory measures end-to-end
passes, and the future autotuner (ROADMAP item 3, the learned-cost-model
line: Kaufman et al. arXiv:2008.01040, TpuGraphs arXiv:2308.13490)
needs exactly the per-program (features → cost) pairs that were being
thrown away. This registry keeps them:

- every instrumented program registers ONE :class:`ProgramRecord` at
  build time: **compile wall-time** (the first dispatch, which pays
  trace + XLA compile) and its **load split** — Python tracing, lowering
  to MLIR, and then either the backend compile or the read from the
  persistent compilation cache, from jax's own monitoring events
  (:func:`_on_jax_duration`); **FLOP / byte estimates** — XLA's own
  ``Lowered.cost_analysis()`` where available, with a jaxpr-walking
  fallback (:func:`jaxpr_costs`) — and a free-form ``meta`` of
  shape/dtype features;
- every later dispatch accumulates **invocation count + cumulative
  dispatch wall-time** (a ~1 µs wrapper; with ``TFT_OBS=0`` the wrapper
  is a pass-through). Programs whose call sites do not synchronize
  (the batch engine's pipelined chunk dispatches) accumulate *enqueue*
  wall — an understatement on async backends, exact on the synced
  serving steps;
- :func:`table` derives the **roofline view**: achieved FLOP/s over the
  dispatched time, arithmetic intensity (FLOPs/byte), and utilization
  against the device's peak (:func:`peak_flops` — known TPU
  generations, or the ``TFT_PEAK_FLOPS`` / ``TFT_PEAK_BYTES_PER_S``
  overrides; ``None`` off TPU, an error for a TPU with no table
  entry). It is
  what ``GET /statusz`` serves and ``explain(analyze=True)`` renders;
- :func:`persist` appends the records as JSONL next to the batch-job
  journal root (``<job root>/programs.jsonl``, or
  ``TFT_PROGRAM_COSTS_FILE``), so the r01→r05 bench trajectory gains
  per-program ground truth across processes; the time-series sampler
  (:mod:`.timeseries`) calls the throttled :func:`autopersist` on its
  tick.
"""

from __future__ import annotations

import json
import os
import socket
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ..utils.logging import get_logger
from .metrics import enabled, gauge as _gauge

__all__ = [
    "ProgramRecord",
    "autopersist",
    "costs_path",
    "estimate_costs",
    "initialized_device",
    "instrument",
    "jaxpr_costs",
    "peak_bytes_per_s",
    "peak_flops",
    "persist",
    "program",
    "programs",
    "reset",
    "table",
]

logger = get_logger("obs.programs")

_g_registered = _gauge(
    "obs.programs_registered",
    "Compiled programs currently tracked by the cost registry",
)

_lock = threading.Lock()
_records: Dict[str, "ProgramRecord"] = {}
_last_persist = 0.0
#: bound on registry size — a pathological caller minting a program per
#: request must saturate, not leak
_MAX_PROGRAMS = 4096


class ProgramRecord:
    """One compiled program's ledger entry."""

    __slots__ = (
        "key", "name", "kind", "created_ts", "compile_s", "trace_s",
        "lower_s", "backend_compile_s", "cache_load_s", "cache_hit",
        "flops", "bytes_accessed", "cost_source", "invocations",
        "dispatches", "dispatch_s", "last_dispatch_ts", "meta", "_lock",
        "_persisted_inv",
    )

    def __init__(self, key: str, name: str, kind: str, **meta):
        self.key = key
        self.name = name
        self.kind = kind
        self.created_ts = time.time()
        self.compile_s: Optional[float] = None
        #: where ``compile_s`` went, from jax's own events while this
        #: program's call ran (:func:`_on_jax_duration`): Python tracing
        #: to a jaxpr and lowering to MLIR — paid on a cache hit too —
        #: then the backend stage, booked as ``backend_compile_s`` when
        #: XLA compiled and as ``cache_load_s`` when the persistent
        #: cache had the executable. ``None`` until jax reports one.
        self.trace_s: Optional[float] = None
        self.lower_s: Optional[float] = None
        self.backend_compile_s: Optional[float] = None
        self.cache_load_s: Optional[float] = None
        #: whether the newest backend stage was a persistent-cache hit
        self.cache_hit: Optional[bool] = None
        self.flops: Optional[float] = None
        self.bytes_accessed: Optional[float] = None
        self.cost_source: Optional[str] = None  # "xla" | "jaxpr"
        self.invocations = 0
        #: dispatches EXCLUDING the compile-paying first call — the
        #: denominator pair for the roofline (flops * dispatches /
        #: dispatch_s)
        self.dispatches = 0
        self.dispatch_s = 0.0
        self.last_dispatch_ts: Optional[float] = None
        self.meta: Dict[str, Any] = dict(meta)
        self._lock = threading.Lock()
        self._persisted_inv = -1  # autopersist dirtiness watermark

    # -- accumulation ------------------------------------------------------

    def note_compile(self, seconds: float) -> None:
        with self._lock:
            self.invocations += 1
            self.last_dispatch_ts = time.time()
            if self.compile_s is None:
                self.compile_s = seconds
            else:  # a second signature recompiled under the same record
                self.compile_s += seconds

    def note_load(self, field: str, seconds: float) -> None:
        """Add one compile-stage duration to ``field`` (``trace_s``,
        ``lower_s``, ``backend_compile_s`` or ``cache_load_s``)."""
        with self._lock:
            setattr(self, field, (getattr(self, field) or 0.0) + seconds)
            if field == "backend_compile_s":
                self.cache_hit = False
            elif field == "cache_load_s":
                self.cache_hit = True

    def add_dispatch(self, seconds: float) -> None:
        with self._lock:
            self.invocations += 1
            self.dispatches += 1
            self.dispatch_s += seconds
            self.last_dispatch_ts = time.time()

    def set_costs(
        self, flops: Optional[float], bytes_accessed: Optional[float],
        source: Optional[str],
    ) -> None:
        with self._lock:
            self.flops = flops
            self.bytes_accessed = bytes_accessed
            self.cost_source = source

    # -- derived view ------------------------------------------------------

    def as_dict(self) -> Dict[str, Any]:
        with self._lock:
            d: Dict[str, Any] = {
                "key": self.key,
                "name": self.name,
                "kind": self.kind,
                "compile_s": _round(self.compile_s),
                "trace_s": _round(self.trace_s),
                "lower_s": _round(self.lower_s),
                "backend_compile_s": _round(self.backend_compile_s),
                "cache_load_s": _round(self.cache_load_s),
                "cache_hit": self.cache_hit,
                "flops": self.flops,
                "bytes": self.bytes_accessed,
                "cost_source": self.cost_source,
                "invocations": self.invocations,
                "dispatches": self.dispatches,
                "dispatch_s": _round(self.dispatch_s),
                "meta": dict(self.meta),
            }
            flops, disp, dt = self.flops, self.dispatches, self.dispatch_s
            bytes_ = self.bytes_accessed
        achieved = (
            flops * disp / dt if flops and disp and dt > 0 else None
        )
        d["achieved_flops_per_s"] = _round(achieved)
        d["intensity_flops_per_byte"] = _round(
            flops / bytes_ if flops and bytes_ else None
        )
        peak = peak_flops()
        d["roofline_utilization"] = _round(
            achieved / peak if achieved and peak else None
        )
        return d


def _round(v: Optional[float], digits: int = 6) -> Optional[float]:
    return None if v is None else round(float(v), digits)


def program(key: str, name: str, kind: str, **meta) -> ProgramRecord:
    """Get-or-create the record for ``key`` (idempotent — the build-time
    registration point)."""
    with _lock:
        rec = _records.get(key)
        if rec is None:
            if len(_records) >= _MAX_PROGRAMS:
                # saturated: hand back a detached record so callers keep
                # working; it simply is not listed
                return ProgramRecord(key, name, kind, **meta)
            rec = _records[key] = ProgramRecord(key, name, kind, **meta)
            _g_registered.set(float(len(_records)))
        return rec


def programs() -> List[ProgramRecord]:
    with _lock:
        return list(_records.values())


def table() -> List[Dict[str, Any]]:
    """Every program's ledger row, heaviest (cumulative dispatch time)
    first — the ``/statusz`` programs table."""
    rows = [r.as_dict() for r in programs()]
    rows.sort(key=lambda r: (-(r["dispatch_s"] or 0.0), r["name"]))
    return rows


def reset() -> None:
    """Drop every record (test isolation)."""
    global _last_persist
    with _lock:
        _records.clear()
        _last_persist = 0.0
    _g_registered.set(0.0)


def render_table() -> str:
    """Plain-text programs table for ``explain(analyze=True)``."""
    rows = table()
    if not rows:
        return "== Programs ==\n (no compiled programs registered)"
    lines = ["== Programs =="]
    for r in rows:
        util = r["roofline_utilization"]
        lines.append(
            f" {r['name']} [{r['kind']}] "
            f"compile={_fmt_s(r['compile_s'])} "
            f"(trace={_fmt_s(r['trace_s'])} lower={_fmt_s(r['lower_s'])} "
            + (
                f"cache_load={_fmt_s(r['cache_load_s'])}) "
                if r["cache_hit"]
                else f"backend={_fmt_s(r['backend_compile_s'])}) "
            )
            + f"flops={_fmt_num(r['flops'])} "
            f"bytes={_fmt_num(r['bytes'])} "
            f"inv={r['invocations']} "
            f"dispatch={_fmt_s(r['dispatch_s'])} "
            f"achieved={_fmt_num(r['achieved_flops_per_s'])}F/s "
            + (f"roofline={util:.2%}" if util is not None else "roofline=n/a")
        )
    return "\n".join(lines)


def _fmt_s(v: Optional[float]) -> str:
    return "n/a" if v is None else f"{v:.4f}s"


def _fmt_num(v: Optional[float]) -> str:
    if v is None:
        return "n/a"
    for scale, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "k")):
        if abs(v) >= scale:
            return f"{v / scale:.2f}{suffix}"
    return f"{v:.0f}"


# ---------------------------------------------------------------------------
# cost estimation
# ---------------------------------------------------------------------------


_estimate_warned = False


def _warn_estimate_once(what: str) -> None:
    global _estimate_warned
    if not _estimate_warned:
        _estimate_warned = True
        logger.warning("%s (logged once)", what, exc_info=True)


def estimate_costs(
    fn, *args, **kwargs
) -> Tuple[Optional[float], Optional[float], Optional[str]]:
    """``(flops, bytes, source)`` for one program at one signature.

    Tries XLA's analysis off the jit's ``lower()`` artifact first (no
    compile — lowering only), then falls back to walking the jaxpr
    (:func:`jaxpr_costs`). Both read only the arguments' shapes and
    dtypes, so they work on arguments the call being described has
    already donated. ``(None, None, None)`` when both fail — cost
    accounting must never break a dispatch; the first failure is
    logged, because a registry that silently holds no costs reads as a
    program that costs nothing."""
    ca = {}
    try:
        ca = fn.lower(*args, **kwargs).cost_analysis() or {}
    except Exception:  # a backend may offer no analysis for a program
        _warn_estimate_once("XLA cost analysis failed; walking the jaxpr")
    flops = ca.get("flops")
    nbytes = ca.get("bytes accessed")
    if flops is not None or nbytes is not None:
        return (
            float(flops) if flops is not None else None,
            float(nbytes) if nbytes is not None else None,
            "xla",
        )
    try:
        import jax

        flops, nbytes = jaxpr_costs(jax.make_jaxpr(fn)(*args, **kwargs))
        return flops, nbytes, "jaxpr"
    except Exception:  # the walk meets whatever primitives fn holds
        _warn_estimate_once(
            "jaxpr cost walk failed; the record keeps no FLOP/byte costs"
        )
        return None, None, None


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", v)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * dtype.itemsize


def _aval_size(v) -> int:
    aval = getattr(v, "aval", v)
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _eqn_flops(eqn) -> float:
    """FLOPs for one jaxpr equation — exact for ``dot_general`` (2MNK),
    kernel-shaped for convolutions, operand-sized for reductions,
    output-sized for everything else (the elementwise approximation).
    Inner jaxprs (pjit / scan / while / custom derivatives / remat)
    recurse; ``scan`` multiplies by its trip count."""
    prim = eqn.primitive.name
    params = eqn.params
    if prim == "dot_general":
        (lhs_contract, _), _ = params["dimension_numbers"]
        out_size = sum(_aval_size(v) for v in eqn.outvars)
        lhs = eqn.invars[0].aval
        k = 1
        for ax in lhs_contract:
            k *= int(lhs.shape[ax])
        return 2.0 * out_size * k
    if prim == "conv_general_dilated":
        out = eqn.outvars[0].aval
        rhs = eqn.invars[1].aval
        dn = params.get("dimension_numbers")
        out_feature_axis = dn.out_spec[1] if dn is not None else 1
        out_channels = max(int(out.shape[out_feature_axis]), 1)
        rhs_size = 1
        for d in rhs.shape:
            rhs_size *= int(d)
        return 2.0 * _aval_size(out) * (rhs_size / out_channels)
    inner = params.get("jaxpr") or params.get("call_jaxpr")
    if inner is not None:
        body = getattr(inner, "jaxpr", inner)
        flops = _jaxpr_flops(body)
        if prim == "scan":
            flops *= max(int(params.get("length", 1)), 1)
        return flops
    if params.get("body_jaxpr") is not None:  # while: one iteration
        f = _jaxpr_flops(params["body_jaxpr"].jaxpr)
        if params.get("cond_jaxpr") is not None:
            f += _jaxpr_flops(params["cond_jaxpr"].jaxpr)
        return f
    if prim == "cond":
        return max(
            (
                _jaxpr_flops(b.jaxpr)
                for b in params.get("branches", ())
            ),
            default=0.0,
        )
    if prim.startswith(("reduce_", "arg")) or prim in ("cumsum", "cumprod"):
        return float(sum(_aval_size(v) for v in eqn.invars))
    return float(sum(_aval_size(v) for v in eqn.outvars))


def _jaxpr_flops(jaxpr) -> float:
    return float(sum(_eqn_flops(e) for e in jaxpr.eqns))


def jaxpr_costs(closed_jaxpr) -> Tuple[float, float]:
    """``(flops, bytes)`` from a closed jaxpr: FLOPs summed over
    equations (see :func:`_eqn_flops`), bytes as program inputs +
    outputs + consts — the memory-traffic LOWER bound the roofline
    wants (intermediates that stay in registers/cache are not link
    traffic)."""
    jaxpr = closed_jaxpr.jaxpr
    nbytes = float(
        sum(_aval_bytes(v) for v in jaxpr.invars)
        + sum(_aval_bytes(v) for v in jaxpr.outvars)
        + sum(_aval_bytes(c) for c in closed_jaxpr.consts)
    )
    return _jaxpr_flops(jaxpr), nbytes


# ---------------------------------------------------------------------------
# device peaks (roofline denominators)
# ---------------------------------------------------------------------------

#: per-chip peaks by the EXACT ``device_kind`` string jax reports:
#: (dense bf16 matmul FLOP/s, HBM bytes/s) — the roofline denominators
#: when no TFT_PEAK_FLOPS / TFT_PEAK_BYTES_PER_S override is set. Exact
#: keys, because kinds share prefixes ("TPU v5" is the v5p, "TPU v5 lite"
#: the v5e) and a neighbour's peak is worse than none.
_TPU_PEAKS = {
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v5": (459e12, 2765e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v3": (123e12, 900e9),
    "TPU v2": (45e12, 700e9),
}


def initialized_device():
    """Device 0 of the default backend, or ``None`` while this process
    has not initialized one. Never initializes: a chip belongs to one
    process, and one that only routes, scrapes or labels itself must
    not take it from the process that computes."""
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return None
    import jax

    return jax.devices()[0]


def _peak(env: str, column: int) -> Optional[float]:
    override = os.environ.get(env, "")
    if override:
        try:
            return float(override)
        except ValueError:
            logger.warning("malformed %s=%r ignored", env, override)
    dev = initialized_device()
    if dev is None or dev.platform != "tpu":
        return None
    try:
        return _TPU_PEAKS[dev.device_kind][column]
    except KeyError:
        raise LookupError(
            f"no peak on record for TPU device_kind {dev.device_kind!r}: "
            f"add it to obs.programs._TPU_PEAKS or set {env}"
        ) from None


def peak_flops() -> Optional[float]:
    """This host's peak FLOP/s for roofline utilization:
    ``TFT_PEAK_FLOPS`` override, else the known-TPU table. ``None`` off
    TPU (utilization renders as n/a — honest on CPU hosts) and before
    this process has initialized a backend; a TPU whose ``device_kind``
    is not in the table raises ``LookupError``."""
    return _peak("TFT_PEAK_FLOPS", 0)


def peak_bytes_per_s() -> Optional[float]:
    """Peak memory bandwidth (``TFT_PEAK_BYTES_PER_S`` override, else
    the known-TPU HBM table); ``None`` / ``LookupError`` as for
    :func:`peak_flops`."""
    return _peak("TFT_PEAK_BYTES_PER_S", 1)


# ---------------------------------------------------------------------------
# the dispatch wrapper
# ---------------------------------------------------------------------------

#: jax's compile-stage events (``jax/_src/dispatch.py``,
#: ``jax/_src/compiler.py``)
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

#: ``loading``: the record whose instrumented call is running on this
#: thread (jax compiles on the calling thread and fires its events
#: there); ``trace_s``: the longest trace reported since the last stage
#: was booked; ``cache_hit``: the backend stage in progress found its
#: executable in the persistent cache
_tls = threading.local()
_listening = False


def _on_jax_duration(event: str, duration: float, **_kw) -> None:
    """The one ``jax.monitoring`` listener: book a compile-stage duration
    to the program whose call is running on this thread. Stages of any
    other compile (an uninstrumented jit, a cost estimate's re-lowering)
    find no record and are dropped.

    Tracing reports once per jitted function, the callees inside the
    program (``softmax``, ``where``) before the program itself, whose
    wall holds theirs: the longest report is the program's. The backend
    event wraps the persistent-cache lookup, so on a hit it is the cache
    read that it timed."""
    rec = getattr(_tls, "loading", None)
    if rec is None:
        return
    if event == _TRACE_EVENT:
        _tls.trace_s = max(getattr(_tls, "trace_s", 0.0), float(duration))
    elif event == _LOWER_EVENT:
        _book_trace(rec)
        rec.note_load("lower_s", float(duration))
    elif event == _CACHE_READ_EVENT:
        # fires inside the backend stage, before that stage's own event
        _tls.cache_hit = True
    elif event == _BACKEND_EVENT:
        hit, _tls.cache_hit = getattr(_tls, "cache_hit", False), False
        rec.note_load(
            "cache_load_s" if hit else "backend_compile_s", float(duration)
        )


def _book_trace(rec: "ProgramRecord") -> None:
    traced, _tls.trace_s = getattr(_tls, "trace_s", 0.0), 0.0
    if traced:
        rec.note_load("trace_s", traced)


def _listen() -> None:
    """Register :func:`_on_jax_duration` once per process, at the first
    enabled instrumented call (never at import, never under the kill
    switch)."""
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


class InstrumentedProgram:
    """Transparent callable around a jitted program: the first enabled
    call registers the :class:`ProgramRecord` and records compile
    wall-time + cost estimates; every later call accumulates invocation
    + dispatch wall-time. Registration is LAZY so that with the kill
    switch on (``TFT_OBS=0``) wrapping and calling leave the registry —
    and the persisted JSONL — completely untouched (``record`` stays
    ``None``). Attribute access (``.lower`` for ``precompile``)
    delegates to the wrapped jit."""

    __slots__ = (
        "_fn", "_sync", "_after_issue", "_estimated", "record", "_key",
        "_name", "_kind", "_meta", "_cache_size",
    )

    def __init__(
        self, fn, key: str, name: str, kind: str, meta, sync,
        after_issue=None,
    ):
        self._fn = fn
        self._sync = sync
        self._after_issue = after_issue
        self._estimated = False
        self.record: Optional[ProgramRecord] = None
        self._key = key
        self._name = name
        self._kind = kind
        self._meta = meta
        #: the jit's executable-cache depth at our last look: a call
        #: that GREW it paid a trace+compile, so its wall belongs in
        #: compile_s, not dispatch_s — booking a later-signature
        #: recompile (map_rows' final partial chunk, a new padded
        #: prefill width) as a dispatch would poison achieved-FLOP/s
        #: with seconds of compile wall
        self._cache_size = -1

    def __call__(self, *args, **kwargs):
        after_issue = self._after_issue
        if not enabled():
            out = self._fn(*args, **kwargs)
            if after_issue is not None:
                after_issue()
            return out
        rec = self.record
        if rec is None:
            rec = self.record = program(
                self._key, self._name, self._kind, **self._meta
            )
        if not _listening:
            _listen()
        _tls.loading = rec
        t0 = time.perf_counter()
        try:
            out = self._fn(*args, **kwargs)
            if after_issue is not None:
                after_issue()
            if self._sync:
                import jax

                out = jax.block_until_ready(out)
        finally:
            _tls.loading = None
            _book_trace(rec)  # a trace that no lowering followed
        dt = time.perf_counter() - t0
        try:
            size = self._fn._cache_size()
        except Exception:
            size = None
        if size is None:  # no cache introspection: first call only
            compiled = not self._estimated
        else:
            compiled = size != self._cache_size
            self._cache_size = size
        if compiled:
            rec.note_compile(dt)
            if not self._estimated:
                # first observed call: its args pin the signature the
                # cost estimate describes
                self._estimated = True
                flops, nbytes, source = estimate_costs(
                    self._fn, *args, **kwargs
                )
                rec.set_costs(flops, nbytes, source)
        else:
            rec.add_dispatch(dt)
        return out

    def __getattr__(self, name):
        return getattr(self._fn, name)


def instrument(
    fn, *, key: str, name: str, kind: str, sync: bool = False,
    after_issue=None, **meta
) -> InstrumentedProgram:
    """Wrap a jitted callable so its costs land in the registry.

    ``sync=True`` blocks on the outputs inside the timing window —
    correct only where the call site synchronizes anyway (the serving
    step dispatches); pipelined call sites (the batch engine's chunk
    loops) keep ``sync=False`` and accumulate enqueue wall.

    ``after_issue`` is called with no arguments on the calling thread
    each time the jitted call has returned — the program is issued and
    the device works — and before the wrapper waits for it: host work
    that needs nothing of this program's result runs there beside the
    device instead of between two programs (the serving engine delivers
    the last step's tokens there). It runs inside the timed window and
    under the kill switch too, and what it raises fails the call."""
    return InstrumentedProgram(fn, key, name, kind, meta, sync, after_issue)


# ---------------------------------------------------------------------------
# persistence
# ---------------------------------------------------------------------------


def costs_path() -> str:
    """Where program records persist: ``TFT_PROGRAM_COSTS_FILE``, else
    ``programs.jsonl`` next to the batch-job journal root
    (``Config.job_dir`` / ``$TFT_JOB_DIR`` /
    ``~/.cache/tensorframes_tpu/jobs``) — the same trajectory directory
    the bench artifacts and journals live in, so the autotuner's
    training data accumulates in one place."""
    explicit = os.environ.get("TFT_PROGRAM_COSTS_FILE", "")
    if explicit:
        return explicit
    from ..utils.config import get_config

    root = (
        get_config().job_dir
        or os.environ.get("TFT_JOB_DIR")
        or os.path.join(
            os.path.expanduser("~"), ".cache", "tensorframes_tpu", "jobs"
        )
    )
    return os.path.join(root, "programs.jsonl")


def persist(path: Optional[str] = None) -> int:
    """Append one JSONL line per record whose stats moved since the
    last persist; returns lines written. Failures log and return 0 —
    cost accounting must never take down the path it measures."""
    try:
        target = path or costs_path()
        dirty: List[Tuple[ProgramRecord, int]] = []
        for rec in programs():
            with rec._lock:
                if rec.invocations != rec._persisted_inv:
                    dirty.append((rec, rec.invocations))
        if not dirty:
            return 0
        os.makedirs(os.path.dirname(target) or ".", exist_ok=True)
        ts = time.time()
        host, pid = socket.gethostname(), os.getpid()
        with open(target, "a") as f:
            for rec, _ in dirty:
                row = rec.as_dict()
                row.update(ts=round(ts, 3), host=host, pid=pid)
                f.write(json.dumps(row, default=str) + "\n")
        # watermarks advance only AFTER the write landed: a failed
        # write (disk full, read-only path) must leave the records
        # dirty so the next successful persist still captures their
        # final state — that state is the autotuner's training data
        for rec, inv in dirty:
            with rec._lock:
                rec._persisted_inv = inv
        return len(dirty)
    except Exception:
        logger.warning("program-cost persist failed", exc_info=True)
        return 0


#: minimum seconds between autopersist writes (the sampler calls it
#: every tick; disk sees it at most this often)
_AUTOPERSIST_S = 30.0


def autopersist() -> int:
    """Throttled :func:`persist` for the sampler tick. No-op under the
    kill switch — TFT_OBS=0 must never touch the disk."""
    global _last_persist
    if not enabled():
        return 0
    now = time.monotonic()
    if now - _last_persist < _AUTOPERSIST_S:
        return 0
    _last_persist = now
    return persist()
