"""Distributed span tracing: trace ids, nested spans, JSONL event log.

``span(name, **attrs)`` is the one primitive. It nests via a thread-local
stack (each serving connection / decode worker gets its own tree), records
wall duration and — when a pytree is attached via the ``sync`` argument or
``Span.sync`` — a device-synchronized duration as well, and forwards to
``jax.profiler.TraceAnnotation`` so spans appear as named slices inside
Perfetto/TensorBoard traces captured by ``utils.profiling.trace()``.

**Identity** (the distributed layer, PR 10): every span carries a
``trace_id`` (32 lowercase hex chars — one END-TO-END request or job),
a ``span_id`` (16 hex chars, unique across processes), and a
``parent_id`` (the enclosing span, or the remote parent the trace was
adopted from). A :class:`TraceContext` names a position in a trace and
propagates it:

- **in-process, across threads** via a contextvar: a request thread
  wraps work in ``with use_trace(ctx): ...`` and every span opened on
  that thread (engine stepping, journal writers given the ctx) joins the
  trace;
- **across HTTP** via the W3C ``traceparent`` header
  (``00-<trace_id>-<span_id>-01``): ``interop/serving.py`` accepts it on
  ``POST /generate`` and echoes it back;
- **across processes** via the batch-job journal: ``engine/jobs.py``
  stamps the trace into ``manifest.json`` and every ledger record, so a
  distributed worker (``engine/dist_jobs.py``) continues the job's trace
  in another process — and the whole story is reconstructible
  post-mortem from ``ledger.jsonl`` plus the JSONL sink alone.

Completed spans are appended to a JSONL sink (one JSON object per line)
configured with :func:`set_trace_sink` or the ``TFT_TRACE_FILE``
environment variable. Event schema (stable; documented in
``docs/observability.md``)::

    {"name": str, "trace_id": "32hex", "span_id": "16hex",
     "parent_id": "16hex" | null, "depth": int,
     "ts": float epoch-seconds at entry,
     "t_mono": float time.monotonic() at entry, "dur_s": float wall,
     "dur_synced_s": float (only when a sync tree was attached),
     "thread": str, "attrs": {str: json-value}}

Events are written when a span CLOSES, so children appear before their
parents — consumers reconstruct the tree from ``parent_id`` and group
requests by ``trace_id``. ``t_mono`` is the system-wide monotonic clock
at entry — the clock load generators and benchmarks stamp, so a span
lies beside their times without a conversion (``ts`` stays for readers
that want wall-clock time). :func:`event` emits a point event (``dur_s``
0, written immediately) — the record a crash cannot destroy, used by the
distributed-job lease claims so a kill -9'd worker's claim is still in
the trace.

A path-configured sink **rotates by size**: when the file would exceed
``max_bytes`` (default 64 MiB, ``TFT_TRACE_FILE_MAX_BYTES``), it is
renamed to ``<path>.1`` (replacing any previous ``.1``) and a fresh file
is started — the sink holds the last ~1–2 × ``max_bytes`` instead of
growing unbounded.

Everything honors the observability kill switch (``TFT_OBS=0`` /
``Config(observability=False)``): a disabled ``span()`` yields ``None``
and touches nothing.
"""

from __future__ import annotations

import contextvars
import itertools
import json
import os
import threading
import time
from typing import Any, Dict, Optional

from ..utils.logging import get_logger
from .metrics import enabled

__all__ = [
    "Span",
    "SpanChain",
    "TraceContext",
    "current_span",
    "current_trace",
    "event",
    "new_trace",
    "set_annotations",
    "set_trace_sink",
    "span",
    "trace_sink",
    "use_trace",
]

logger = get_logger("obs.tracing")

_tls = threading.local()
_ids = itertools.count(1)
#: per-process id prefix: span ids must not collide across the worker
#: processes that share one trace (the distributed-jobs story), so each
#: process mints ids as <8 random hex><8 hex counter>
_PROC_PREFIX = os.urandom(4).hex()


def _new_span_id() -> str:
    return f"{_PROC_PREFIX}{next(_ids) & 0xFFFFFFFF:08x}"


def _new_trace_id() -> str:
    return os.urandom(16).hex()


class TraceContext:
    """A position inside one distributed trace: ``(trace_id, span_id)``.
    ``span_id`` is the id new child spans parent to — the W3C
    ``parent-id``. Immutable and tiny; safe to hand across threads and
    serialize into headers, manifests, and ledger records."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str):
        self.trace_id = trace_id
        self.span_id = span_id

    def __repr__(self) -> str:
        return f"TraceContext({self.trace_id!r}, {self.span_id!r})"

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, TraceContext)
            and other.trace_id == self.trace_id
            and other.span_id == self.span_id
        )

    def __hash__(self) -> int:
        return hash((self.trace_id, self.span_id))

    def child(self) -> "TraceContext":
        """Same trace, fresh span id (a synthetic intermediate node)."""
        return TraceContext(self.trace_id, _new_span_id())

    # -- W3C traceparent ---------------------------------------------------

    def traceparent(self) -> str:
        """This position as a W3C ``traceparent`` header value
        (version 00, sampled flag set)."""
        return f"00-{self.trace_id}-{self.span_id}-01"

    @classmethod
    def from_traceparent(cls, header: Optional[str]) -> Optional["TraceContext"]:
        """Parse a ``traceparent`` header; ``None`` for a missing or
        malformed value (a bad header must never fail the request —
        tracing degrades to a fresh trace instead)."""
        if not header:
            return None
        parts = header.strip().lower().split("-")
        if len(parts) < 4:
            return None
        version, trace_id, span_id = parts[0], parts[1], parts[2]
        if (
            len(version) != 2
            or len(trace_id) != 32
            or len(span_id) != 16
            or version == "ff"
            or trace_id == "0" * 32
            or span_id == "0" * 16
        ):
            return None
        try:
            int(trace_id, 16), int(span_id, 16)
        except ValueError:
            return None
        return cls(trace_id, span_id)


def new_trace() -> TraceContext:
    """A fresh root trace context (new trace_id, synthetic root span id).
    Spans opened under ``use_trace(new_trace())`` parent to the synthetic
    root — the same shape as adopting a remote parent."""
    return TraceContext(_new_trace_id(), _new_span_id())


#: the ambient trace position for code with no open span on its thread —
#: how a request's identity crosses into worker threads (the engine's
#: stepping loop, journal writers). A contextvar rather than a
#: thread-local so async frameworks layered on top inherit it naturally.
_ctx_var: "contextvars.ContextVar[Optional[TraceContext]]" = (
    contextvars.ContextVar("tft_trace_ctx", default=None)
)


class use_trace:
    """Install ``ctx`` as the ambient trace for the block::

        with use_trace(ctx):
            ...           # spans opened here join ctx's trace

    ``None`` is a no-op (propagating an absent trace must cost nothing
    and not mask an outer one)."""

    __slots__ = ("_ctx", "_token")

    def __init__(self, ctx: Optional[TraceContext]):
        self._ctx = ctx
        self._token = None

    def __enter__(self) -> Optional[TraceContext]:
        if self._ctx is not None:
            self._token = _ctx_var.set(self._ctx)
        return self._ctx

    def __exit__(self, *exc) -> bool:
        if self._token is not None:
            _ctx_var.reset(self._token)
        return False


def current_trace() -> Optional[TraceContext]:
    """The calling thread's trace position: the innermost OPEN span if
    one exists, else the ambient :class:`use_trace` context, else
    ``None``. This is what crosses boundaries — stamp it into a header /
    manifest / submit call on one side, ``use_trace`` it on the other."""
    stack = getattr(_tls, "stack", None)
    if stack:
        top = stack[-1]
        return TraceContext(top.trace_id, top.span_id)
    return _ctx_var.get()


_sink_lock = threading.Lock()
_sink = None
_sink_owned = False  # we opened it (path arg) and must close it

#: rotation default for path sinks: ~64 MiB, env-overridable
_DEFAULT_MAX_BYTES = 64 << 20


def _env_max_bytes() -> int:
    try:
        return int(
            os.environ.get("TFT_TRACE_FILE_MAX_BYTES", _DEFAULT_MAX_BYTES)
        )
    except ValueError:
        return _DEFAULT_MAX_BYTES


class _RotatingFile:
    """Append sink with size-based rotation: when a write would push the
    file past ``max_bytes``, the current file is renamed to ``<path>.1``
    (dropping the previous ``.1``) and a fresh file begins — the JSONL
    sink keeps the last ~``max_bytes``..2×``max_bytes`` of spans instead
    of growing without bound (``TFT_TRACE_FILE`` used to). Rotation is
    line-atomic: events are whole lines and a rotation happens only
    between writes. ``max_bytes <= 0`` disables rotation."""

    def __init__(self, path: str, max_bytes: int):
        self.path = os.fspath(path)
        self.max_bytes = int(max_bytes)
        self._f = open(self.path, "a", buffering=1)
        try:
            self._size = os.path.getsize(self.path)
        except OSError:
            self._size = 0

    def write(self, data: str) -> int:
        if self.max_bytes > 0:
            # multiple PROCESSES may share one TFT_TRACE_FILE (the
            # distributed-jobs workers do): if another process rotated
            # the path out from under us, our O_APPEND fd now follows
            # the renamed .1 inode — re-attach to the live path instead
            # of writing into (and later clobbering) the archive. The
            # same stat's st_size is the authoritative file size (a
            # process-local byte counter misses the siblings' appends
            # and would let the shared file grow to K x max_bytes).
            try:
                st = os.stat(self.path)
                if st.st_ino != os.fstat(self._f.fileno()).st_ino:
                    self._reopen()
                else:
                    self._size = st.st_size
            except OSError:
                self._reopen()
            if self._size and self._size + len(data) > self.max_bytes:
                self._rotate()
        n = self._f.write(data)
        self._size += len(data)
        return n

    def _rotate(self) -> None:
        try:
            # last-instant re-check: a sibling PROCESS may have rotated
            # between our size check and here — renaming our stale view
            # over its fresh archive would destroy up to max_bytes of
            # just-preserved spans; re-attach instead
            if (
                os.stat(self.path).st_ino
                != os.fstat(self._f.fileno()).st_ino
            ):
                self._reopen()
                return
            self._f.close()
            os.replace(self.path, self.path + ".1")
        except OSError:
            logger.warning("trace sink rotation failed", exc_info=True)
        self._reopen()

    def _reopen(self) -> None:
        try:
            self._f.close()
        except OSError:
            pass
        self._f = open(self.path, "a", buffering=1)
        try:
            self._size = os.path.getsize(self.path)
        except OSError:
            self._size = 0

    def flush(self) -> None:
        self._f.flush()

    def close(self) -> None:
        self._f.close()


def set_trace_sink(sink, max_bytes: Optional[int] = None) -> None:
    """Route span events: a path (opened append, line-buffered, with
    size rotation — ``max_bytes`` defaults to ~64 MiB or
    ``TFT_TRACE_FILE_MAX_BYTES``; ``<= 0`` disables rotation), a
    file-like object (used as-is, not closed, never rotated), or
    ``None`` to disable. Replacing a path-opened sink closes it."""
    global _sink, _sink_owned, _consumers_gen
    _consumers_gen += 1
    with _sink_lock:
        if _sink_owned and _sink is not None:
            try:
                _sink.close()
            except OSError:
                pass
        if sink is None:
            _sink, _sink_owned = None, False
        elif isinstance(sink, (str, os.PathLike)):
            limit = _env_max_bytes() if max_bytes is None else int(max_bytes)
            _sink, _sink_owned = _RotatingFile(sink, limit), True
        else:
            _sink, _sink_owned = sink, False


def trace_sink():
    """The active sink file object (``None`` when disabled)."""
    return _sink


#: bumped whenever a span consumer attaches or detaches (sink,
#: annotations, flight capture): a :class:`SpanChain` link made under
#: another generation may have a consumer-less stretch behind it, over
#: which ``span()`` ran nothing and the chain stood still
_consumers_gen = 0


class SpanChain:
    """Back-to-back leaf spans over one thread's loop: a span opened with
    ``span(name, chain=c)`` begins where the last span on ``c`` ended, so
    the walls of consecutive phases add up to the wall of the stretch
    they cover — the span machinery between two ``with`` blocks (closing
    one event, opening the next) and whatever else ran there is inside
    the phase that follows, not in a hole between two spans. Only the
    sink event is backdated; a ``TraceAnnotation`` starts when entered.
    The owner calls :meth:`reset` where the loop was not running (a
    caller's pause between two steps)."""

    __slots__ = ("t", "gen")

    def __init__(self):
        self.t: Optional[float] = None  # perf_counter at the last end
        self.gen = -1

    def reset(self) -> None:
        self.t = None


class Span:
    """One live span (its own context manager — the generator-based
    ``contextlib`` route costs ~2 µs per use, real money at engine-dispatch
    frequency). Mutate ``attrs`` (or assign ``sync``) inside the ``with``
    block to enrich the event before it is emitted."""

    __slots__ = (
        "name", "trace_id", "span_id", "parent_id", "depth", "attrs",
        "sync", "ts", "t_mono", "_t0", "_ann", "_chain",
    )

    def __init__(self, name, sync, attrs, chain=None):
        self.name = name
        self.trace_id: Optional[str] = None  # resolved at __enter__
        self.span_id = _new_span_id()
        self.parent_id: Optional[str] = None
        self.depth = 0
        self.attrs: Dict[str, Any] = attrs
        self.sync = sync
        self.ts = 0.0
        self.t_mono = 0.0
        self._t0 = 0.0
        self._ann = None
        self._chain: Optional[SpanChain] = chain

    def __enter__(self) -> "Span":
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        if stack:
            parent = stack[-1]
            self.trace_id = parent.trace_id
            self.parent_id = parent.span_id
            self.depth = len(stack)
        else:
            ctx = _ctx_var.get()
            if ctx is not None:
                self.trace_id = ctx.trace_id
                self.parent_id = ctx.span_id
            else:
                self.trace_id = _new_trace_id()
        stack.append(self)
        if _annotations_on:
            ann_cls = _annotation_cls()
            if ann_cls is not None:
                self._ann = ann_cls(self.name)
                self._ann.__enter__()
        self.ts = time.time()
        self.t_mono = time.monotonic()
        self._t0 = time.perf_counter()
        chain = self._chain
        if (
            chain is not None
            and chain.t is not None
            and chain.gen == _consumers_gen
        ):
            back = self._t0 - chain.t
            if back > 0.0:  # begin where the chain's last span ended
                self.ts -= back
                self.t_mono -= back
                self._t0 = chain.t
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        end = time.perf_counter()
        wall = end - self._t0
        if self._chain is not None:
            self._chain.t = end
            self._chain.gen = _consumers_gen
        synced = None
        if self.sync is not None:
            try:
                import jax

                jax.block_until_ready(self.sync)
                synced = time.perf_counter() - self._t0
            except Exception:
                pass  # sync is best-effort diagnostics, never a failure
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        stack = getattr(_tls, "stack", None)
        if stack and stack[-1] is self:
            stack.pop()
        _emit(self, wall, synced)
        return False


def current_span() -> Optional[Span]:
    """The innermost open span on this thread, or ``None``."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


#: mirror of ``flight.capture_spans``'s state, kept as a plain module
#: global here so the disabled ``span()`` fast path stays one predicate
#: (``obs/flight.py`` flips it via :func:`_set_flight_capture`)
_flight_spans_on = False


def _set_flight_capture(on: bool) -> None:
    global _flight_spans_on, _consumers_gen
    _flight_spans_on = bool(on)
    _consumers_gen += 1


def _emit(s: Span, wall: float, synced: Optional[float]) -> None:
    if _flight_spans_on:
        from . import flight as _flight

        _flight.record_span(s.name, s.trace_id, s.span_id, wall, s.attrs)
    if _sink is None:
        return
    event = {
        "name": s.name,
        "trace_id": s.trace_id,
        "span_id": s.span_id,
        "parent_id": s.parent_id,
        "depth": s.depth,
        "ts": s.ts,
        "t_mono": s.t_mono,
        "dur_s": wall,
        "thread": threading.current_thread().name,
        "attrs": s.attrs,
    }
    if synced is not None:
        event["dur_synced_s"] = synced
    _write_event(event)


def _write_event(event: Dict[str, Any]) -> None:
    try:
        line = json.dumps(event, default=str) + "\n"
    except (TypeError, ValueError):  # pathological attrs must not raise
        event["attrs"] = {k: str(v) for k, v in event["attrs"].items()}
        line = json.dumps(event, default=str) + "\n"
    with _sink_lock:
        sink = _sink
        if sink is None:
            return
        try:
            sink.write(line)
        except (OSError, ValueError):
            logger.warning("span sink write failed; disabling sink")
            globals()["_sink"] = None
            globals()["_sink_owned"] = False


def event(name: str, **attrs) -> Optional[TraceContext]:
    """Emit a POINT event into the current trace: a zero-duration span
    record written to the sink immediately (and mirrored into the flight
    recorder). This is the record a crash cannot destroy — the
    distributed-job lease claim uses it so a worker kill -9'd mid-block
    still left its claim in the trace. Returns the event's own
    :class:`TraceContext` (for chaining), or ``None`` when disabled."""
    if not enabled():
        return None
    ctx = current_trace()
    sid = _new_span_id()
    trace_id = ctx.trace_id if ctx is not None else _new_trace_id()
    parent_id = ctx.span_id if ctx is not None else None
    if _flight_spans_on:
        from . import flight as _flight

        _flight.record_span(name, trace_id, sid, 0.0, attrs)
    if _sink is not None:
        _write_event(
            {
                "name": name,
                "trace_id": trace_id,
                "span_id": sid,
                "parent_id": parent_id,
                "depth": 0,
                "ts": time.time(),
                "t_mono": time.monotonic(),
                "dur_s": 0.0,
                "thread": threading.current_thread().name,
                "attrs": attrs,
                "kind": "event",
            }
        )
    return TraceContext(trace_id, sid)


_ann_cls = None
_ann_tried = False
#: forward spans to jax.profiler.TraceAnnotation only while someone is
#: actually capturing a trace: an annotation inside a dispatching pass
#: measures ~5-10 µs (TraceMe + pybind crossing on a cold cache), which is
#: pure waste when no Perfetto session exists to receive it.
#: ``utils.profiling.trace()`` flips this automatically; direct
#: ``jax.profiler.start_trace`` users call :func:`set_annotations`.
_annotations_on = False


def set_annotations(on: bool) -> None:
    """Enable/disable TraceAnnotation forwarding for spans (normally
    managed by ``utils.profiling.trace()``)."""
    global _annotations_on, _consumers_gen
    _annotations_on = bool(on)
    _consumers_gen += 1


def _annotation_cls():
    """``jax.profiler.TraceAnnotation`` resolved once (or ``None`` when
    jax/its profiler is unavailable — spans must work without it)."""
    global _ann_cls, _ann_tried
    if not _ann_tried:
        _ann_tried = True
        try:
            import jax

            _ann_cls = jax.profiler.TraceAnnotation
        except Exception:
            _ann_cls = None
    return _ann_cls


class _NullSpan:
    """Context manager for the disabled state: ``as`` binds ``None``."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


_NULL = _NullSpan()


def span(name: str, sync=None, chain: Optional[SpanChain] = None, **attrs):
    """Open a nested span::

        with span("engine.map_blocks", partitions=4) as sp:
            out = run()
            sp.sync = out          # optional: device-synced duration
            sp.attrs["rows"] = n   # optional: enrich the event

    Binds the :class:`Span` (or ``None`` when observability is off).
    ``sync`` / ``Span.sync`` holds a pytree passed to
    ``jax.block_until_ready`` before the synced duration is taken — only
    attach work the caller is about to materialize anyway; syncing a
    deliberately device-resident result would serialize the pipeline.

    Spans are event producers: with no JSONL sink configured, no
    profiler trace listening, and flight-recorder span capture off, a
    span has no observable effect, so the whole mechanism is skipped
    (engine dispatch loops then pay one predicate per op instead of
    allocation + clock reads). Consumers attach by setting a sink /
    opening ``utils.profiling.trace()`` / enabling
    ``flight.capture_spans`` BEFORE the work they want to see.

    ``chain`` (a :class:`SpanChain`) makes this one of a loop's
    back-to-back phase spans: it begins where the chain's last span
    ended.
    """
    if not enabled() or (
        _sink is None and not _annotations_on and not _flight_spans_on
    ):
        return _NULL
    return Span(name, sync, dict(attrs), chain)


if os.environ.get("TFT_TRACE_FILE"):
    try:
        set_trace_sink(os.environ["TFT_TRACE_FILE"])
    except OSError:
        logger.warning(
            "TFT_TRACE_FILE=%r could not be opened; span sink disabled",
            os.environ["TFT_TRACE_FILE"],
        )
