"""TPU-host scoring service: executors stream Arrow, the chip's host runs.

The reference ran its native engine INSIDE every Spark executor
(per-task sessions, ``DebugRowOps.scala:377-391``) — compute went to the
partitions because every executor had a CPU TensorFlow. On TPU the
hardware inverts that: executors don't have chips, so the partitions
come to the compute. This module is that pattern as a shim:

- :class:`ScoringServer` runs on the TPU host. Each client connection
  carries one partition as an Arrow IPC stream; the server runs the
  captured program through the local engine (same ``map_blocks``
  semantics as :func:`~tensorframes_tpu.interop.spark.arrow_batch_mapper`
  — the whole connection's rows form one logical partition, so cross-row
  block ops see the partition, not the wire chunking) and streams the
  result back as Arrow.
- :func:`remote_arrow_mapper` builds the EXECUTOR-side function for
  ``DataFrame.mapInArrow``: a self-contained closure over (host, port)
  that imports only ``socket`` and ``pyarrow`` — Spark workers need
  neither jax nor this package installed.
- :func:`remote_map_in_arrow` wires the two into a Spark DataFrame
  transform, completing the story: Spark-scale data reaches the TPU
  without a driver-side collect; the driver never materializes the
  table.

Wire protocol (deliberately boring): the client writes one Arrow IPC
stream and half-closes its send side; the server reads to end-of-stream,
computes, writes one Arrow IPC stream back, and closes. Results are
buffered host-side until the request stream ends — full-duplex streaming
would deadlock clients (like Spark's mapInArrow generator) that write
everything before reading anything. ``streaming=True`` still bounds the
server's FRAME memory by running row-local programs per incoming batch.

Observability: the same port doubles as a Prometheus scrape target. A
connection whose first bytes are ``GET `` or ``POST`` is answered as a
plain HTTP request — ``GET /metrics`` returns the process-wide registry
in exposition format (an Arrow IPC stream can never start with those
bytes, so the two protocols cannot be confused). Each scoring connection
increments ``serving.requests_total{kind,status}``, the byte counters,
and the ``serving.request_seconds`` latency histogram; concurrent load
shows up on the ``serving.active_connections`` gauge. See
``docs/observability.md``.

Generation: constructed with ``engine=`` (a
:class:`~tensorframes_tpu.serve.GenerationEngine` or a replicated
:class:`~tensorframes_tpu.serve.Fleet`), the same port also serves
``POST /generate`` — JSON in (``{"prompt": [ids],
"max_new_tokens": n, "temperature"?, "top_p"?, "seed"?, "session"?}``),
JSON out (``{"request_id": ..., "tokens": [ids]}``) — backed by the
engine's continuous-batching loop, so concurrent connections share one
decode batch and one page pool (see ``docs/serving_llm.md``). With a
fleet, each request is placed on a healthy replica and survives replica
deaths via request replay; ``"session"`` keys opt into replica affinity.
A full admission queue answers 503 (backpressure) with an ADAPTIVE
``Retry-After`` — queue depth × observed p50 inter-token latency,
clamped to [1, 30] seconds, 1 until latency samples exist — an
infeasible request 400. Unknown paths get 404; known paths with the
wrong verb get 405 + ``Allow``.
"""

from __future__ import annotations

import socket
import threading
import time
from typing import Any, Dict, Optional, Tuple

from ..obs import (
    TraceContext as _TraceContext,
    flight as _flight,
    new_trace as _new_trace,
    span as _span,
    use_trace as _use_trace,
)
from ..utils import chaos as _chaos
from ..obs.metrics import (
    counter as _counter,
    enabled as _obs_enabled,
    gauge as _gauge,
    histogram as _histogram,
    render_prometheus as _render_prometheus,
)

__all__ = ["ScoringServer", "remote_arrow_mapper", "remote_map_in_arrow"]

_m_requests = _counter(
    "serving.requests_total",
    "Connections served, by kind "
    "(score|metrics|healthz|statusz|varz|generate|http) and terminal "
    "status",
    labels=("kind", "status"),
)
_m_bytes_in = _counter(
    "serving.bytes_in_total", "Request payload bytes read off the wire"
)
_m_bytes_out = _counter(
    "serving.bytes_out_total", "Response payload bytes written to the wire"
)
_m_latency = _histogram(
    "serving.request_seconds",
    "Scoring request wall time, accept to response flush (seconds)",
)
_m_active = _gauge(
    "serving.active_connections", "Connections currently being served"
)
_m_stream_resumes = _counter(
    "serve.stream_resumes_total",
    "Generate requests served from the router WAL's tracker instead of "
    "a fresh generation: duplicate request_id dedupe, and client "
    "reconnects resuming a stream with from=<offset>",
)


def _adaptive_retry_after(engine) -> str:
    """The 503 ``Retry-After`` value: aggregate queue depth × observed
    p50 inter-token latency (how long the backlog ahead of a retry
    plausibly takes to drain one slot), clamped to [1, 30] seconds.
    Falls back to ``"1"`` while no latency samples exist (cold engine)
    or anything in the estimate is unavailable — a wrong hint must never
    break the shed path."""
    import math

    try:
        depth = 0
        if engine is not None:
            depth = int(engine.health().get("queue_depth", 0) or 0)
        from ..obs.metrics import registry

        p50 = registry().get("serve.inter_token_seconds").quantile(0.5)
        if p50 is None:
            return "1"
        return str(int(min(30, max(1, math.ceil(depth * p50)))))
    except Exception:
        return "1"


class _CountingFile:
    """File-object wrapper that counts bytes through ``read``/``write``
    into a counter; everything else delegates. pyarrow's IPC reader/writer
    drive Python file-likes through exactly these two calls."""

    def __init__(self, f, counter):
        self._f = f
        self._c = counter

    def read(self, *args, **kwargs):
        b = self._f.read(*args, **kwargs)
        if b:
            self._c.inc(len(b))
        return b

    def write(self, data):
        n = self._f.write(data)
        self._c.inc(len(data) if n is None else n)
        return n

    def __getattr__(self, name):
        return getattr(self._f, name)


class ScoringServer:
    """Serve a captured program over Arrow IPC on the host that owns the
    accelerator.

    >>> with ScoringServer(lambda x: {"y": x * 2.0}) as addr:
    ...     # hand `addr` ("host:port") to executors / pipelines
    ...     df.mapInArrow(remote_arrow_mapper(addr), schema)

    One connection = one partition (the
    :func:`~tensorframes_tpu.interop.spark.arrow_batch_mapper` contract);
    concurrent connections are served by a bounded thread pool, and the
    engine's program caches are shared across them, so every partition
    after the first reuses the compiled XLA program. ``precompile`` +
    the persistent compile cache (``tft.enable_compilation_cache``) make the
    first one cheap too."""

    def __init__(
        self,
        fetches=None,
        *,
        trim: bool = False,
        feed_dict: Optional[Dict[str, str]] = None,
        decoders: Optional[Dict[str, Any]] = None,
        constants: Optional[Dict[str, Any]] = None,
        streaming: bool = False,
        batch_rows: int = 0,
        host: str = "127.0.0.1",
        port: int = 0,
        max_connections: int = 8,
        engine=None,
        readiness=None,
        lifecycle=None,
        router_epoch_fn=None,
    ):
        if fetches is None and engine is None:
            raise ValueError(
                "ScoringServer needs a scoring program (fetches) and/or a "
                "generation engine (engine=)"
            )
        if fetches is not None:
            from .spark import arrow_batch_mapper

            #: the same executor-side mapper the in-Spark path uses — the
            #: server is "an executor that happens to own the chip"
            self._mapper = arrow_batch_mapper(
                fetches,
                trim=trim,
                feed_dict=feed_dict,
                decoders=decoders,
                constants=constants,
                batch_rows=batch_rows,
                streaming=streaming,
            )
        else:
            self._mapper = None
        #: optional continuous-batching generation engine backing
        #: ``POST /generate`` (tensorframes_tpu.serve.GenerationEngine)
        self._engine = engine
        self._engine_started_here = False
        #: readiness probe for ``GET /readyz``: ``() -> (ready, state)``
        #: — a serving member (serve/membership.py) reports not-ready
        #: while draining / probing / mid-weight-swap so rollouts can
        #: gate traffic WITHOUT touching /healthz's liveness meaning.
        #: ``None`` → readiness mirrors liveness.
        self._readiness = readiness
        #: member-side half of zombie-router fencing: ``() ->
        #: Optional[int]`` reading the router election lease's CURRENT
        #: epoch (serve/router_ha.py's ``router_epoch_from``). When set,
        #: a ``POST /generate`` whose ``x-router-epoch`` header is below
        #: it came from a router that already lost the lease — answered
        #: ``409 Conflict`` (kind ``StaleRouterEpochError``) instead of
        #: decoding tokens the new active is re-generating. ``None`` (or
        #: no header) → no fencing.
        self._router_epoch_fn = router_epoch_fn
        #: lifecycle actuator for ``POST /admin/lifecycle``:
        #: ``(action, spec) -> payload dict`` (drain / admit / restart /
        #: swap / rollback — serve/membership.py wires the member's
        #: state machine in). ``None`` → the endpoint answers 501.
        self._lifecycle = lifecycle
        self._host = host
        self._requested_port = port  # 0 = ephemeral, fresh per start()
        self._port = port
        self._limit = threading.Semaphore(max_connections)
        self._sock: Optional[socket.socket] = None
        self._accept_thread: Optional[threading.Thread] = None
        self._stopping = threading.Event()
        self._sampler_held = False

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> Tuple[str, int]:
        """Bind and serve in a daemon thread; returns ``(host, port)``
        (port resolved when 0 was requested). A stopped server may be
        started again."""
        if self._sock is not None:
            raise RuntimeError("server already started")
        self._stopping.clear()  # restart after stop()
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        # bind the REQUESTED port: an ephemeral (0) server picks a fresh
        # port each start (re-binding the previous resolved port races
        # lingering connections; callers re-read start()'s return)
        s.bind((self._host, self._requested_port))
        s.listen()
        self._sock = s
        if self._engine is not None and self._engine._thread is None:
            # the generate endpoint needs the stepping loop; start it for
            # the server's lifetime (an engine the caller already started
            # is left under the caller's control)
            self._engine.start()
            self._engine_started_here = True
        # a live server holds the time-series sampler, so /varz and the
        # SLO monitors have history for exactly as long as traffic can
        # reach them (refcounted; released in stop())
        from ..obs import timeseries as _ts

        _ts.acquire_sampler()
        self._sampler_held = True
        try:
            # fleet telemetry identity: a server wrapping an engine is a
            # serve replica; a score-only server is just a driver process
            from ..obs import export as _obs_export

            _obs_export.set_identity(
                "serve-replica" if self._engine is not None else "driver"
            )
        except Exception:
            from ..utils import get_logger

            get_logger("interop.serving").warning(
                "telemetry identity failed", exc_info=True
            )
        self._port = s.getsockname()[1]
        self._accept_thread = threading.Thread(
            target=self._accept_loop, daemon=True
        )
        self._accept_thread.start()
        return self._host, self._port

    @property
    def address(self) -> str:
        if self._sock is None:
            raise RuntimeError("server not started")
        return f"{self._host}:{self._port}"

    def stop(self) -> None:
        self._stopping.set()
        if getattr(self, "_sampler_held", False):
            from ..obs import timeseries as _ts

            self._sampler_held = False
            _ts.release_sampler()
        if self._engine_started_here:
            self._engine.stop()
            self._engine_started_here = False
        if self._sock is not None:
            try:
                # close() alone leaves a thread blocked in accept()
                # asleep on Linux; shutdown() wakes it with an OSError
                try:
                    self._sock.shutdown(socket.SHUT_RDWR)
                except OSError:  # never connected / already down
                    pass
                self._sock.close()
            finally:
                self._sock = None
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=5)
            self._accept_thread = None

    def __enter__(self) -> str:
        self.start()
        return self.address

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- serving -----------------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._stopping.is_set():
            sock = self._sock  # stop() may null the attribute mid-loop
            if sock is None:
                return
            try:
                conn, _ = sock.accept()
            except OSError:  # socket closed by stop()
                return
            # bound concurrency without parking stop(): wake periodically
            # so a full pool cannot leave this thread (and a pending
            # connection) stranded across shutdown
            while not self._limit.acquire(timeout=0.5):
                if self._stopping.is_set():
                    conn.close()
                    return
            threading.Thread(
                target=self._serve_one, args=(conn,), daemon=True
            ).start()

    #: HTTP verbs the Arrow port answers as plain HTTP (an Arrow IPC
    #: stream can never start with these bytes)
    _HTTP_PREFIXES = (b"GET ", b"POST")

    #: the HTTP routing table: path -> verbs it answers. Anything else is
    #: a crisp 404 (unknown path) or 405 + ``Allow`` (wrong verb) — note
    #: only GET/POST-prefixed requests reach HTTP handling at all (the
    #: peek above routes everything else to the Arrow parser)
    _ROUTES: Dict[str, Tuple[str, ...]] = {
        "/metrics": ("GET",),
        "/healthz": ("GET",),
        "/readyz": ("GET",),
        "/statusz": ("GET",),
        "/varz": ("GET",),
        "/generate": ("POST",),
        "/admin/tenants": ("GET", "POST"),
        "/admin/lifecycle": ("POST",),
    }

    @classmethod
    def _peek(cls, conn: socket.socket) -> bytes:
        """The request's first bytes without consuming them (so the Arrow
        reader still sees a whole stream). Blocks for the FIRST byte just
        like the pre-scrape server blocked in the Arrow parser — a slow
        client must not be dropped. Waits for more bytes ONLY while the
        prefix is still ambiguous with an HTTP verb (an Arrow stream's
        first byte is never ``G`` or ``P``, so Arrow clients route
        immediately); that disambiguation wait is bounded so a client
        wedged exactly at ``b"GE"`` falls through to the Arrow path — the
        same failure surface it would have hit before the scrape
        existed."""
        buf = conn.recv(4, socket.MSG_PEEK)  # blocking first-byte wait
        if not buf or not any(
            v.startswith(buf[:4]) for v in cls._HTTP_PREFIXES
        ):
            return buf
        deadline = time.monotonic() + 10.0
        while len(buf) < 4 and any(
            v.startswith(buf) for v in cls._HTTP_PREFIXES
        ):
            if time.monotonic() > deadline:
                break
            time.sleep(0.005)
            buf = conn.recv(4, socket.MSG_PEEK)
            if not buf:
                break
        return buf

    def _serve_http(self, conn: socket.socket) -> str:
        """Answer a plain-HTTP request on the Arrow port. Routes:

        - ``GET /metrics`` — the default registry in Prometheus
          exposition format, so ``curl http://host:port/metrics`` (or an
          actual scrape job) works against a live server with no sidecar;
        - ``GET /healthz`` — liveness JSON (engine watchdog age, queue
          depth, pages in use, SLO state); 200 while healthy (the
          ``status`` field says ``"degraded"`` under an SLO breach),
          503 once the serving supervisor marked the engine unhealthy
          or a stop wedged;
        - ``GET /readyz`` — readiness JSON (``{"ready", "state"}``):
          503 while a fleet member is draining / probing /
          mid-weight-swap even though it is perfectly alive — the
          traffic gate rollouts and balancers act on (liveness and
          readiness are deliberately separate probes);
        - ``GET /varz`` — the time-series store as JSON (sampled
          gauges, counter rates, histogram quantiles; ``prefix=`` /
          ``window=`` query params);
        - ``POST /generate`` (``engine=`` configured) — JSON
          ``{"prompt": [ids], "max_new_tokens": n, "temperature"?,
          "top_p"?, "seed"?, "deadline_s"?, "session"?}`` submitted to
          the continuous-batching engine (or placed by the fleet
          router); responds ``{"request_id", "tokens"}`` when the
          stream completes. 503 + adaptive ``Retry-After`` on a full
          admission queue or an unhealthy engine / all-fenced fleet
          (shed, don't block), 504 on a missed deadline, 400 on an
          infeasible request, 429 + ``Retry-After`` when the tenant's
          QoS policy refuses it (quota / rate / SLO shed);
        - ``GET|POST /admin/tenants`` — the QoS policy registry
          (``serve/tenancy.py``): read or update per-tenant quotas,
          rate limits, and priority classes at runtime;
        - ``POST /admin/lifecycle`` — the fleet-member lifecycle
          actuator (drain / admit / restart / swap / rollback /
          commit; ``serve/membership.py``).

        ``POST /generate`` with ``"stream": true`` answers NDJSON: one
        ``{"t": token}`` line per emission and a terminal ``{"done":
        ...}`` / ``{"error": ..., "kind": ...}`` line — the wire the
        fleet router's remote replicas relay token-by-token.

        Unknown paths answer 404; known paths with the wrong verb 405
        with an ``Allow`` header. Returns the request kind for the
        metrics label."""
        import json

        conn.settimeout(10)
        buf = b""
        while b"\r\n\r\n" not in buf and len(buf) < 65536:
            chunk = conn.recv(4096)
            if not chunk:
                break
            buf += chunk
        head, _, body = buf.partition(b"\r\n\r\n")
        line = head.split(b"\r\n", 1)[0].decode("latin-1", "replace")
        parts = line.split()
        verb = parts[0].upper() if parts else ""
        path, _, query = (parts[1] if len(parts) > 1 else "/").partition("?")
        headers: Dict[str, str] = {}
        for hline in head.split(b"\r\n")[1:]:
            name, _, val = hline.partition(b":")
            headers[name.strip().lower().decode("latin-1", "replace")] = (
                val.strip().decode("latin-1", "replace")
            )
        clen = 0
        try:
            clen = int(headers.get("content-length", "0"))
        except ValueError:
            pass
        while len(body) < clen:
            chunk = conn.recv(4096)
            if not chunk:
                break
            body += chunk

        kind = "http"
        ctype = "text/plain; charset=utf-8"
        extra_headers: Dict[str, str] = {}
        norm = path.rstrip("/") or "/"
        allowed = self._ROUTES.get(norm)
        if allowed is None:
            # an unknown path is the CLIENT's mistake: say so crisply
            # instead of falling through to an ambiguous catch-all
            out = (
                b"endpoints: GET /metrics, GET /healthz, GET /readyz, "
                b"GET /statusz, GET /varz, POST /generate, "
                b"GET|POST /admin/tenants, POST /admin/lifecycle\n"
            )
            status = "404 Not Found"
        elif verb not in allowed:
            # right path, wrong verb: 405 with the verbs that would work
            out = f"method {verb or '?'} not allowed on {norm}\n".encode(
                "utf-8"
            )
            status = "405 Method Not Allowed"
            extra_headers["Allow"] = ", ".join(allowed)
        elif norm == "/metrics":
            kind = "metrics"
            out = _render_prometheus().encode("utf-8")
            status = "200 OK"
            ctype = "text/plain; version=0.0.4; charset=utf-8"
        elif norm == "/healthz":
            kind = "healthz"
            status, out, extra_headers = self._handle_healthz()
            ctype = "application/json; charset=utf-8"
        elif norm == "/readyz":
            kind = "readyz"
            status, out, extra_headers = self._handle_readyz()
            ctype = "application/json; charset=utf-8"
        elif norm == "/statusz":
            kind = "statusz"
            status, out, extra_headers = self._handle_statusz()
            ctype = "application/json; charset=utf-8"
        elif norm == "/varz":
            kind = "varz"
            status, out, extra_headers = self._handle_varz(query)
            ctype = "application/json; charset=utf-8"
        elif norm == "/admin/tenants":
            kind = "admin"
            status, out, extra_headers = self._handle_admin_tenants(
                verb, body
            )
            ctype = "application/json; charset=utf-8"
        elif norm == "/admin/lifecycle":
            kind = "lifecycle"
            status, out, extra_headers = self._handle_lifecycle(body)
            ctype = "application/json; charset=utf-8"
        else:  # /generate, POST
            kind = "generate"
            res = self._handle_generate(body, headers, conn=conn)
            if res is None:
                return kind  # streamed: the response is already on the wire
            status, out, extra_headers = res
            ctype = "application/json; charset=utf-8"
        header_lines = "".join(
            f"{k}: {v}\r\n" for k, v in extra_headers.items()
        )
        conn.sendall(
            (
                f"HTTP/1.1 {status}\r\n"
                f"Content-Type: {ctype}\r\n"
                f"Content-Length: {len(out)}\r\n"
                f"{header_lines}"
                "Connection: close\r\n\r\n"
            ).encode("latin-1")
            + out
        )
        return kind

    def _handle_healthz(self) -> Tuple[str, bytes, Dict[str, str]]:
        """Liveness for load balancers and the chaos soak: the engine's
        :meth:`~tensorframes_tpu.serve.GenerationEngine.health` snapshot
        (last-step watchdog age, queue depth, pages in use, unhealthy
        flags) — for a :class:`~tensorframes_tpu.serve.Fleet`, the
        AGGREGATE with per-replica detail, 200 while any replica serves
        — plus this process's batch-job summary (``engine/jobs.py``:
        active/completed/failed runs, the last job's block counts and
        quarantine tally; for a journaled job, the ``"journal"`` view
        read from the journal directory itself — block progress and the
        distributed worker/lease table of ``engine/dist_jobs.py``, so
        ANY process's probe shows the whole fleet draining the
        manifest) so operators see batch health next to serving
        health. A server with no engine is just an Arrow scorer —
        always healthy as long as it accepts connections. A 503 carries
        the adaptive ``Retry-After`` so probes and balancers know when
        to look again."""
        import json

        if self._engine is None:
            report: Dict[str, Any] = {"healthy": True, "engine": None}
        else:
            report = self._engine.health()
        try:
            from ..engine.jobs import jobs_status

            report["jobs"] = jobs_status()
        except Exception:  # health must never 500 over a status probe
            report["jobs"] = None
        try:
            # the flight recorder's recent debug bundles: the probe that
            # notices a failure points straight at its black box
            report["debug_bundles"] = _flight.recent_bundles()
        except Exception:
            report["debug_bundles"] = []
        # SLO state rides the health probe: "degraded" is a state
        # DISTINCT from unhealthy — the engine still serves (stay 200,
        # the balancer must not drain a whole fleet over a latency SLO)
        # but it is violating its declared objectives, and the "status"
        # field says so to anything that looks
        degraded = False
        try:
            from ..obs import slo as _slo

            mon = _slo.monitor()
            report["slo"] = mon.status()
            degraded = mon.degraded()
        except Exception:
            report["slo"] = []
        # fleet telemetry summary: when a telemetry dir is configured,
        # the probe shows every federated process's identity and
        # staleness (a kill -9'd worker shows up HERE as stale, not by
        # silently vanishing from the page)
        try:
            from ..obs import aggregate as _obs_agg
            from ..obs import export as _obs_export

            tdir = _obs_export.telemetry_dir()
            if tdir:
                fs = _obs_agg.fleet_status(tdir)
                report["fleet"] = {
                    "dir": tdir,
                    "procs": fs.get("procs", []),
                    "stale": sum(
                        1 for p in fs.get("procs", []) if p.get("stale")
                    ),
                }
            else:
                report["fleet"] = None
        except Exception:
            report["fleet"] = None
        report["status"] = (
            "unhealthy"
            if not report["healthy"]
            else ("degraded" if degraded else "ok")
        )
        body = json.dumps(report).encode("utf-8")
        if report["healthy"]:
            return "200 OK", body, {}
        return "503 Service Unavailable", body, {
            "Retry-After": _adaptive_retry_after(self._engine)
        }

    def _handle_readyz(self) -> Tuple[str, bytes, Dict[str, str]]:
        """``GET /readyz`` — readiness, as distinct from ``/healthz``'s
        liveness: "should a balancer SEND this member traffic right
        now", not "is the process worth keeping alive". A serving
        member answers 503 while **draining** (rolling restart /
        SIGTERM), **probing** (restarted, not yet re-validated), or
        **mid-weight-swap** — states where the process is perfectly
        healthy (``/healthz`` stays 200/ok, a balancer must NOT recycle
        it) but must not take new streams. Without a readiness hook
        (plain scorer / standalone engine server) readiness mirrors
        liveness, so probing either endpoint is always safe."""
        import json

        state = "ready"
        if self._readiness is not None:
            try:
                ready, state = self._readiness()
            except Exception as e:  # a probe must never 500
                ready, state = False, f"error: {type(e).__name__}"
        elif self._engine is not None:
            ready = bool(self._engine.health().get("healthy"))
            state = "ready" if ready else "unhealthy"
        else:
            ready = True
        body = json.dumps({"ready": bool(ready), "state": state}).encode(
            "utf-8"
        )
        if ready:
            return "200 OK", body, {}
        return "503 Service Unavailable", body, {"Retry-After": "1"}

    def _handle_lifecycle(
        self, body: bytes
    ) -> Tuple[str, bytes, Dict[str, str]]:
        """``POST /admin/lifecycle`` — the member's lifecycle actuator
        (``serve/membership.py`` wires it): ``{"action": "drain" |
        "admit" | "restart" | "swap" | "rollback", ...}``. The rollout
        orchestrator drives members through drain → restart/swap →
        probe → admit over this endpoint; ``/readyz`` reflects each
        transition. 501 when no lifecycle hook is configured, 400 for
        an unknown action or bad spec, 500 when the action itself
        failed (e.g. a checkpoint that does not load)."""
        import json

        if self._lifecycle is None:
            return (
                "501 Not Implemented",
                json.dumps(
                    {"error": "server has no lifecycle hook (not a "
                              "fleet member)"}
                ).encode("utf-8"),
                {},
            )
        try:
            spec = json.loads(body.decode("utf-8") or "{}")
            action = str(spec.get("action", ""))
        except ValueError as e:
            return (
                "400 Bad Request",
                json.dumps({"error": f"bad JSON: {e}"}).encode("utf-8"),
                {},
            )
        try:
            payload = self._lifecycle(action, spec)
        except ValueError as e:
            return (
                "400 Bad Request",
                json.dumps({"error": str(e)}).encode("utf-8"),
                {},
            )
        except Exception as e:
            return (
                "500 Internal Server Error",
                json.dumps(
                    {"error": f"{type(e).__name__}: {e}",
                     "kind": type(e).__name__}
                ).encode("utf-8"),
                {},
            )
        return "200 OK", json.dumps(dict(payload or {})).encode("utf-8"), {}

    def _handle_statusz(self) -> Tuple[str, bytes, Dict[str, str]]:
        """``GET /statusz`` — the operator's at-a-glance page, JSON:

        - ``requests``: the flight recorder's recent generate/score
          records, newest last (kind, HTTP status, wall seconds,
          trace_id — paste the trace_id into a grep over the JSONL sink
          to pull the whole span tree);
        - ``slowest_requests``: the same records, slowest first (top
          10) — where to start when p99 moved;
        - ``debug_bundles``: recent flight-recorder bundles (path,
          reason, timestamp), newest first;
        - ``flight``: events currently held per ring;
        - ``programs``: the per-program cost registry
          (``obs/programs.py``) — every compiled program with compile
          wall-time, FLOP/byte estimates, invocations, cumulative
          dispatch time, and roofline utilization, heaviest first;
        - ``slo``: every declared objective with its burn rates and
          breach state (``obs/slo.py``);
        - ``timeseries``: sampler state (running, interval, series
          tracked — the full points are on ``GET /varz``);
        - ``chaos``: the active chaos spec ("" when clean — anything
          else taints every number on the page);
        - ``tune``: the self-tuning layer's view
          (``tensorframes_tpu.tune``: active mode, store path, and
          every installed/stored tuned winner with its source);
        - ``identity``: this process's fleet identity (proc id, pid,
          role, package version, device kind — ``obs/export.py``);
        - ``request_costs``: the top requests by estimated FLOPs from
          the per-request cost ledger (``obs/requests.py``), tenant
          label included;
        - ``fleet``: when a telemetry dir is configured, the federated
          process table (``obs/aggregate.py`` — merged numbers are on
          ``GET /varz?scope=fleet``);
        - ``serving``: the engine/fleet health snapshot — per replica:
          ``tp_degree`` and (under tensor parallelism) the ``tp`` block
          with sharded-pool capacity, per-shard pages in use, and
          per-shard KV bytes, so operators see capacity scaling with
          the mesh at a glance (ISSUE 14);
        - ``router``: router-HA election + WAL state when a
          ``RouterHA`` is attached (``serve/router_ha.py``);
        - ``tiers``: replica tier roles and live KV-migration totals
          when the fleet is disaggregated (``serve/tiers.py``; None
          for an untiered topology);
        - ``trace_sink``: whether a JSONL span sink is attached.

        Always 200; rendering reads only lock-light engine counters
        (the same ``health()`` snapshot ``/healthz`` serves — safe even
        against a wedged stepping thread, which holds the step lock,
        not the bookkeeping locks) and never dispatches device work."""
        import json

        from ..obs import programs as _programs
        from ..obs import slo as _slo
        from ..obs import timeseries as _ts
        from ..obs import trace_sink as _trace_sink
        from ..utils.config import get_config
        from ..utils import chaos as _chaos_mod

        from .. import tune as _tune

        rings = _flight.rings()
        requests = rings.get("serving", [])
        slowest = sorted(
            requests, key=lambda e: e.get("dur_s") or 0.0, reverse=True
        )[:10]
        try:
            tune_view = {
                "mode": _tune.mode(),
                "store": _tune.store_path(),
                "winners": _tune.snapshot(),
            }
        except Exception:
            tune_view = None
        try:
            from ..obs import export as _obs_export
            from ..obs import requests as _obs_requests

            identity_view = _obs_export.identity()
            costs_view = _obs_requests.top_by_cost(10)
        except Exception:
            identity_view = None
            costs_view = []
        fleet_view = None
        try:
            from ..obs import aggregate as _obs_agg
            from ..obs import export as _obs_export

            tdir = _obs_export.telemetry_dir()
            if tdir:
                fs = _obs_agg.fleet_status(tdir)
                fleet_view = {"dir": tdir, "procs": fs.get("procs", [])}
        except Exception:
            fleet_view = None
        payload = {
            "requests": requests[-50:],
            "slowest_requests": slowest,
            "debug_bundles": _flight.recent_bundles(),
            "flight": {name: len(evts) for name, evts in rings.items()},
            "programs": _programs.table(),
            "slo": _slo.monitor().status(),
            "timeseries": {
                "sampler_running": _ts.sampler_running(),
                "interval_s": get_config().obs_sample_interval_s,
                "series": len(_ts.store().names()),
            },
            "chaos": _chaos_mod.active_spec(),
            "trace_sink": _trace_sink() is not None,
            # the serving topology: engine (or per-replica fleet)
            # health incl. tensor-parallel degree and sharded-pool
            # capacity — never 500s the status page over a sick engine
            "serving": self._serving_view(),
            # the self-tuning layer's installed/stored winners
            # (tensorframes_tpu.tune): which tuned configs this process
            # is actually running with, and where they came from
            "tune": tune_view,
            # fleet telemetry: who this process is, what its requests
            # cost, and (telemetry dir configured) who else is exporting
            "identity": identity_view,
            "request_costs": costs_view,
            "fleet": fleet_view,
            # the QoS plane's per-tenant view (None with no policies
            # configured): policies, live slots/queue share, recent
            # tokens/s + est FLOPs from the cost ledger, throttles —
            # read-side aggregation only (serve/tenancy.py)
            "tenants": self._tenants_view(),
            # router HA (serve/router_ha.py; None without an attached
            # RouterHA): election state (active/fenced, epoch, TTL) and
            # the WAL tracker's depth — the first place to look after a
            # takeover drill
            "router": self._router_view(),
            # disaggregated tiers (serve/tiers.py; None on an untiered
            # engine/fleet): replica roles plus live KV-migration
            # totals by reason — the first place to look when TTFT or
            # inter-token latency moves after a re-tiering
            "tiers": self._tiers_view(),
        }
        return "200 OK", json.dumps(payload, default=str).encode(
            "utf-8"
        ), {}

    def _tenants_view(self):
        """The QoS plane's ``/statusz`` block (None when off);
        exceptions degrade to an ``"error"`` stub — the status page
        always renders."""
        try:
            from ..serve import tenancy as _tenancy

            return _tenancy.statusz_view(self._engine)
        except Exception as e:  # pragma: no cover - defensive
            return {"error": f"{type(e).__name__}: {e}"}

    def _router_view(self):
        """The router-HA ``/statusz`` block (None when this server's
        engine has no :class:`~tensorframes_tpu.serve.router_ha.RouterHA`
        attached); exceptions degrade to an ``"error"`` stub — the
        status page always renders."""
        ha = getattr(self._engine, "router_ha", None)
        if ha is None:
            return None
        try:
            return ha.statusz_view()
        except Exception as e:  # pragma: no cover - defensive
            return {"error": f"{type(e).__name__}: {e}"}

    def _tiers_view(self):
        """The disaggregated-tier ``/statusz`` block (None when the
        engine is not a fleet, or when every replica is ``mixed`` —
        the monolithic topology has nothing tier-shaped to report);
        exceptions degrade to an ``"error"`` stub — the status page
        always renders."""
        reps = getattr(self._engine, "_replicas", None)
        if reps is None:
            return None
        try:
            roles = {
                rep.name: getattr(rep, "tier", "mixed") for rep in reps
            }
            if all(t == "mixed" for t in roles.values()):
                return None
            from ..obs import metrics as _metrics

            snap = _metrics.snapshot().get("serve.kv_migrations_total", {})
            return {
                "replicas": roles,
                "migrations": dict(snap.get("values", {})),
            }
        except Exception as e:  # pragma: no cover - defensive
            return {"error": f"{type(e).__name__}: {e}"}

    def _serving_view(self):
        """The engine's (or fleet's) ``health()`` snapshot for
        ``/statusz``, None when this server is a pure Arrow scorer;
        exceptions degrade to an ``"error"`` stub — the status page
        always renders."""
        if self._engine is None:
            return None
        try:
            return self._engine.health()
        except Exception as e:  # pragma: no cover - defensive
            return {"error": f"{type(e).__name__}: {e}"}

    @staticmethod
    def _handle_admin_tenants(
        verb: str, body: bytes
    ) -> Tuple[str, bytes, Dict[str, str]]:
        """``/admin/tenants`` — the QoS policy registry
        (``serve/tenancy.py``). GET returns the live policies plus the
        plane/shedding state; POST applies one of three shapes (a
        single policy object → upsert, ``{"tenant": x, "delete":
        true}`` → remove, ``{"tenants": [...]}`` → replace all — ``[]``
        turns the plane off) through ``set_config``, so every consumer
        (scheduler order, admission buckets, placement) flips
        atomically. Validation errors are 400s; nothing changes on a
        rejected body."""
        import json

        from ..serve import tenancy as _tenancy

        if verb == "GET":
            payload = {
                "enabled": _tenancy.enabled(),
                "shedding": _tenancy.shedding(),
                "tenants": _tenancy.policies_view(),
            }
            return (
                "200 OK",
                json.dumps(payload).encode("utf-8"),
                {},
            )
        try:
            spec = json.loads(body.decode("utf-8") or "{}")
            tenants = _tenancy.apply_admin(spec)
        except (ValueError, TypeError, KeyError) as e:
            return (
                "400 Bad Request",
                json.dumps(
                    {"error": f"{type(e).__name__}: {e}"}
                ).encode("utf-8"),
                {},
            )
        return (
            "200 OK",
            json.dumps(
                {"enabled": _tenancy.enabled(), "tenants": tenants}
            ).encode("utf-8"),
            {},
        )

    @staticmethod
    def _handle_varz(query: str = "") -> Tuple[str, bytes, Dict[str, str]]:
        """``GET /varz`` — the time-series store as JSON: every sampled
        series (gauges, counter ``.rate``\\ s, histogram ``.p50``/
        ``.p99``/``.rate``) with its raw recent points and per-tier
        depths, plus the sampler state. Query params: ``prefix=`` keeps
        only series whose name starts with it; ``window=SECONDS``
        returns the tier-merged trailing window instead of the raw
        tier; ``scope=fleet`` answers with the FEDERATED view instead —
        every process's exported snapshot under the telemetry dir,
        merged read-side (``obs/aggregate.py``: counters summed,
        gauges per-proc + sum/max, histogram quantiles recomputed from
        merged bucket counts, stale exporters flagged). Always 200 (an
        empty store renders as ``{}``: the sampler simply has not
        run)."""
        import json
        from urllib.parse import parse_qs

        from ..obs import timeseries as _ts
        from ..utils.config import get_config

        prefix: Optional[str] = None
        window_s: Optional[float] = None
        scope: Optional[str] = None
        try:
            q = parse_qs(query or "")
            if q.get("prefix"):
                prefix = q["prefix"][0]
            if q.get("window"):
                window_s = float(q["window"][0])
            if q.get("scope"):
                scope = q["scope"][0]
        except (ValueError, TypeError):
            return (
                "400 Bad Request",
                b'{"error": "bad query: expected prefix=NAME and/or '
                b'window=SECONDS and/or scope=fleet"}',
                {},
            )
        if scope == "fleet":
            from ..obs import aggregate as _obs_agg
            from ..obs import export as _obs_export

            tdir = _obs_export.telemetry_dir()
            if not tdir:
                payload = {
                    "scope": "fleet",
                    "enabled": False,
                    "error": "no telemetry dir configured (set "
                             "Config.telemetry_dir or TFT_TELEMETRY_DIR)",
                }
            else:
                payload = {"scope": "fleet", "enabled": True}
                payload.update(_obs_agg.fleet_status(tdir))
            return (
                "200 OK",
                json.dumps(payload, default=str).encode("utf-8"),
                {},
            )
        last_tick = _ts.last_tick_ts()
        payload = {
            "sampler_running": _ts.sampler_running(),
            "interval_s": get_config().obs_sample_interval_s,
            "last_tick_ts": last_tick,
            "sampler_lag_s": (
                None if last_tick is None
                else max(0.0, time.time() - last_tick)
            ),
            "series": _ts.store().to_dict(
                prefix=prefix, window_s=window_s
            ),
        }
        return "200 OK", json.dumps(payload).encode("utf-8"), {}

    @staticmethod
    def _timing_payload(handle, total_s: float) -> Dict[str, Any]:
        """The per-request timing breakdown echoed in the generate
        response: endpoint wall clock plus whatever stages the engine
        recorded on the handle (queue wait and what it waited on,
        prefill, chunked-prefill dispatches, summed decode gaps, what a
        preemption recomputed, fleet replays)."""
        t = dict(handle.timings) if handle is not None else {}
        out: Dict[str, Any] = {"total_s": round(total_s, 6)}
        # the speculative keys (draft/verify/rollback walls + the
        # proposed/accepted/rolled-back counts) appear only when the
        # engine actually speculated — a plain decode response carries
        # the same payload it always did
        for k in (
            "queue_wait_s", "prefill_s", "decode_s",
            "draft_s", "verify_s", "rollback_s",
            "wait_slots_s", "wait_pages_s", "requeue_wait_s",
        ):
            if k in t:
                out[k] = round(float(t[k]), 6)
        out["prefill_chunks"] = int(t.get("prefill_chunks", 0))
        out["replays"] = int(t.get("replays", 0))
        # why it waited and what preemption cost it (the scheduler's
        # stamps): a request that never waited or was never preempted
        # carries no such key
        for k in (
            "spec_proposed", "spec_accepted", "spec_rolled_back",
            "preemptions", "prefill_tokens", "recomputed_tokens",
        ):
            if k in t:
                out[k] = int(t[k])
        # per-request cost attribution (obs/requests.py): what this
        # request consumed, echoed so the caller can bill without
        # scraping the server-side ledger
        for k in ("tokens", "kv_pages", "prefix_cached_tokens"):
            if k in t:
                out[k] = int(t[k])
        if "est_flops" in t:
            out["est_flops"] = float(t["est_flops"])
        if t.get("tenant"):
            out["tenant"] = str(t["tenant"])
        return out

    def _handle_generate(
        self,
        body: bytes,
        headers: Optional[Dict[str, str]] = None,
        conn: Optional[socket.socket] = None,
    ) -> Optional[Tuple[str, bytes, Dict[str, str]]]:
        """One generate request against the engine; returns (status,
        JSON body, extra headers). Failure modes map to HTTP semantics
        instead of crashing the connection thread: bad JSON / infeasible
        request → 400, no engine → 501, full admission queue or
        unhealthy engine → fast 503 with ``Retry-After`` (shedding, not
        blocking), missed deadline (``"deadline_s"`` in the request, or
        the ``serve_result_timeout_s`` backstop) → 504.

        **Tracing**: a W3C ``traceparent`` request header is adopted
        (same trace_id, this server as a child position) — absent or
        malformed, a fresh trace starts. Every response carries a
        ``traceparent`` header and a ``"trace_id"`` JSON field, and
        completed generations add a ``"timing"`` breakdown (queue wait,
        prefill, chunked-prefill count, decode, replay count), so a
        caller can join its own telemetry to the engine's spans in the
        JSONL sink (docs/observability.md).

        **Streaming**: ``"stream": true`` switches the success path to
        NDJSON over the same connection — one ``{"t": token}`` line per
        emission, then a terminal ``{"done": ...}`` or ``{"error": ...,
        "kind": ExceptionName}`` line (returns ``None``: the response
        is already on the wire). Pre-submit failures still answer their
        plain-JSON status codes, each now carrying a ``"kind"`` field
        so remote callers (the fleet router's
        :class:`~tensorframes_tpu.serve.membership.RemoteEngine`) can
        re-raise the exact exception class.

        **Admission gate**: while the member's lifecycle state is
        ``"draining"`` (rolling restart / SIGTERM) or ``"fenced"``
        (lease lost — a zombie must not take traffic), new requests
        answer 503 immediately — in-flight streams keep decoding;
        probes during ``"probing"``/``"swapping"`` deliberately pass
        (the rollout's validation traffic must reach the engine).

        **Durable requests** (``Config.router_wal`` +
        ``serve/router_ha.py``): a client-supplied ``"request_id"`` is
        echoed on every response and, with the WAL attached, makes the
        request idempotent — a duplicate id serves the journaled entry
        instead of generating again, and a reconnect with
        ``"request_id"`` + ``"from": <tokens already received>``
        replays the missed prefix then follows the live tail. A
        placement whose ``x-router-epoch`` header is below the router
        election lease's epoch answers ``409 Conflict``
        (``StaleRouterEpochError``) — zombie-router fencing; a standby
        router answers 503 (kind ``RouterStandby``)."""
        import json

        t0 = time.perf_counter()
        root = _TraceContext.from_traceparent(
            (headers or {}).get("traceparent")
        )
        ctx = root.child() if root is not None else _new_trace()
        # the client-supplied idempotent request id (filled in during
        # spec parse); when present, EVERY response echoes it verbatim
        # — it names the request across retries/reconnects, so the
        # engine's internal handle id stays internal
        rid_box: Dict[str, Optional[str]] = {"rid": None}

        def reply(
            status: str,
            payload: Dict[str, Any],
            extra: Optional[Dict[str, str]] = None,
            handle=None,
        ) -> Tuple[str, bytes, Dict[str, str]]:
            total = time.perf_counter() - t0
            if rid_box["rid"] is not None:
                payload["request_id"] = rid_box["rid"]
            payload["trace_id"] = ctx.trace_id
            if handle is not None or status.startswith("200"):
                payload["timing"] = self._timing_payload(handle, total)
            _flight.record(
                "serving", "generate",
                status=status.split(" ", 1)[0],
                trace_id=ctx.trace_id,
                dur_s=round(total, 6),
                request_id=payload.get("request_id"),
            )
            hdrs = dict(extra or {})
            hdrs["traceparent"] = ctx.traceparent()
            return status, json.dumps(payload).encode("utf-8"), hdrs

        if self._engine is None:
            return reply(
                "501 Not Implemented",
                {"error": "server has no generation engine"},
            )
        def echo_rid() -> None:
            # refusals answered BEFORE the spec parse still echo a
            # client-supplied request_id (the retry loop keys on it);
            # best-effort only — a malformed body stays a refusal
            if rid_box["rid"] is None:
                try:
                    _rid = json.loads(
                        body.decode("utf-8") or "{}"
                    ).get("request_id")
                except Exception:
                    _rid = None
                if _rid is not None:
                    rid_box["rid"] = str(_rid)

        # zombie-router fencing (member side): a placement stamped with
        # an election epoch BELOW the lease's current one came from a
        # router that already lost the lease — its requests are being
        # re-generated by the new active, so decoding them here would
        # double-spend the chip and race the resumed stream
        stale = self._stale_router_epoch((headers or {}).get(
            "x-router-epoch"
        ))
        if stale is not None:
            placed, cur = stale
            echo_rid()
            return reply(
                "409 Conflict",
                {"error": f"placement carries router epoch {placed} but "
                          f"the election lease is at epoch {cur}: the "
                          "placing router was superseded (fenced "
                          "zombie)",
                 "kind": "StaleRouterEpochError"},
            )
        # router standby gate (router side): only the ACTIVE router may
        # admit — a standby (or a fenced ex-active) answers 503 so
        # clients re-resolve to the current active instead of parking
        # work on a router that cannot place it
        ha = getattr(self._engine, "router_ha", None)
        if ha is not None and not ha.active:
            echo_rid()
            return reply(
                "503 Service Unavailable",
                {"error": "this router is standby/fenced (not the "
                          "active router); retry — takeover completes "
                          "within the election TTL",
                 "kind": "RouterStandby"},
                {"Retry-After": "1"},
            )
        if self._readiness is not None:
            try:
                _, _member_state = self._readiness()
            except Exception:
                _member_state = ""
            if _member_state in ("draining", "fenced"):
                return reply(
                    "503 Service Unavailable",
                    {"error": "member is draining (admission stopped; "
                              "in-flight streams are finishing)"
                     if _member_state == "draining"
                     else "member was fenced (lease lost; re-register "
                          "before admitting traffic)",
                     "kind": "Draining"},
                    {"Retry-After": "2"},
                )
        from ..serve.engine import EngineUnhealthyError
        from ..serve.scheduler import QueueFullError
        from ..utils.config import get_config
        from ..utils.failures import TenantThrottledError

        try:
            spec = json.loads(body.decode("utf-8") or "{}")
            prompt = spec["prompt"]
            max_new = int(spec["max_new_tokens"])
            deadline = spec.get("deadline_s")
            stream = bool(spec.get("stream", False)) and conn is not None
            kwargs: Dict[str, Any] = dict(
                temperature=float(spec.get("temperature", 0.0)),
                top_p=float(spec.get("top_p", 1.0)),
                seed=int(spec.get("seed", 0)),
                deadline=None if deadline is None else float(deadline),
                block=False,
            )
            if spec.get("eos_id") is not None:
                kwargs["eos_id"] = int(spec["eos_id"])
            if spec.get("session") is not None:
                # replica affinity — only the fleet router understands it
                # (duck-typed on its replica surface; catching TypeError
                # from submit instead would blame the client for any
                # internal TypeError bug)
                if not hasattr(self._engine, "replica_names"):
                    return reply(
                        "400 Bad Request",
                        {"error": "session affinity requires a fleet "
                                  "engine (serve.Fleet)"},
                    )
                kwargs["session"] = str(spec["session"])
            tenant = spec.get("tenant")
            if tenant is None:
                tenant = spec.get("session")
            if tenant is not None:
                # cost-attribution label; only passed when the client
                # supplied one so duck-typed engines without the kwarg
                # keep working
                kwargs["tenant"] = str(tenant)
            if spec.get("request_id") is not None:
                rid_box["rid"] = str(spec["request_id"])
            # stream-resume cursor: how many tokens the client already
            # has (only meaningful on a reconnect with a request_id the
            # WAL tracker knows)
            resume_from = int(spec.get("from", 0) or 0)
            if resume_from < 0:
                raise ValueError(f"negative resume offset {resume_from}")
        except (ValueError, KeyError, TypeError) as e:
            return reply(
                "400 Bad Request",
                {"error": f"bad request: {type(e).__name__}: {e}"},
            )
        # durable-request plane (serve/router_ha.py, Config.router_wal):
        # with a client request_id and an attached WAL, a duplicate id
        # serves the EXISTING entry (dedupe / reconnect-resume) and a
        # fresh one is journaled before placement. Gated zero-cost-off:
        # no request_id, no WAL, or router_wal=False → this whole block
        # is a couple of attribute reads and the path below is
        # byte-identical to the pre-HA stack.
        wal = None
        wal_entry = None
        if rid_box["rid"] is not None:
            wal = getattr(self._engine, "wal", None)
            if wal is not None:
                from ..serve.router_ha import enabled as _wal_enabled

                if not _wal_enabled():
                    wal = None
        if wal is not None:
            rid = rid_box["rid"]
            record = {
                "prompt": [int(t) for t in prompt],
                "max_new": max_new,
                "temperature": kwargs["temperature"],
                "top_p": kwargs["top_p"],
                "seed": kwargs["seed"],
                "eos_id": kwargs.get("eos_id"),
                "session": kwargs.get("session"),
                "tenant": kwargs.get("tenant"),
                "deadline_s": deadline,
                "trace": ctx.traceparent(),
            }
            wal_entry, created = wal.admit(rid, record)
            if not created:
                # duplicate submit or reconnect: serve what the tracker
                # already holds — never generate the same id twice
                _m_stream_resumes.inc()
                if stream:
                    self._stream_entry(
                        conn, ctx, wal_entry, t0, resume_from
                    )
                    return None
                return self._reply_entry(reply, wal_entry)
        try:
            # the ambient trace around submit is how the trace_id
            # reaches the engine/fleet: the request record and every
            # engine-side span (prefill, chunks, failover replays) join
            # this request's trace
            with _use_trace(ctx), _span(
                "serving.generate", prompt_len=len(prompt),
                max_new=max_new,
            ):
                handle = self._engine.submit(prompt, max_new, **kwargs)
        except TimeoutError as e:
            # the fleet router can notice a deadline expiring DURING
            # placement (DeadlineExceededError) — same 504 as a stream
            # that expired mid-generation
            if wal_entry is not None:
                wal.forget(rid_box["rid"], e)
            return reply(
                "504 Gateway Timeout",
                {"error": str(e), "kind": type(e).__name__},
            )
        except TenantThrottledError as e:
            # per-TENANT refusal (quota / rate bucket / SLO shed,
            # serve/tenancy.py) — the server has capacity, this tenant
            # may not use it: 429, not the all-full 503. Retry-After is
            # the refusing token bucket's refill time, clamped to the
            # same [1, 30] window the adaptive 503 hint uses — UNLESS
            # the refusal was passed on from a member, in which case the
            # member's own Retry-After header rides the exception
            # (retry_after_hint) and is echoed verbatim: the member
            # knows its bucket, the router's would be a guess.
            import math

            if wal_entry is not None:
                wal.forget(rid_box["rid"], e)
            hint = getattr(e, "retry_after_hint", None)
            retry = str(hint) if hint else str(
                int(min(30, max(1, math.ceil(e.retry_after))))
            )
            return reply(
                "429 Too Many Requests",
                {"error": str(e), "tenant": e.tenant, "reason": e.reason,
                 "retry_after": e.retry_after,
                 "kind": "TenantThrottledError"},
                {"Retry-After": retry},
            )
        except (QueueFullError, EngineUnhealthyError) as e:
            # overload shedding: the caller can retry, THIS server can't
            # help right now — answer fast instead of parking the
            # connection against a full queue or a dead engine. The
            # Retry-After adapts to the backlog (depth x p50 ITL), or is
            # the member's verbatim hint when the refusal came from one.
            if wal_entry is not None:
                wal.forget(rid_box["rid"], e)
            hint = getattr(e, "retry_after_hint", None)
            return reply(
                "503 Service Unavailable",
                {"error": str(e), "kind": type(e).__name__},
                {"Retry-After": str(hint) if hint
                 else _adaptive_retry_after(self._engine)},
            )
        except ValueError as e:
            if wal_entry is not None:
                wal.forget(rid_box["rid"], e)
            return reply(
                "400 Bad Request",
                {"error": str(e), "kind": "ValueError"},
            )
        if wal_entry is not None:
            # from here the tracker entry is the request's source of
            # truth: the pump (owning the handle's queue) feeds it and
            # the journal; this and any future connection stream FROM it
            wal.bind(wal_entry, handle)
            if stream:
                self._stream_entry(conn, ctx, wal_entry, t0, resume_from)
                return None
            return self._reply_entry(reply, wal_entry)
        if stream:
            self._stream_generate(conn, ctx, handle, t0, rid=rid_box["rid"])
            return None
        try:
            toks = handle.result(
                timeout=get_config().serve_result_timeout_s
            )
        except TimeoutError as e:
            # DeadlineExceededError (the scheduler evicted it) and the
            # result-timeout backstop both mean the same thing upstream
            return reply(
                "504 Gateway Timeout",
                {"request_id": handle.request_id, "error": str(e),
                 "kind": type(e).__name__},
                handle=handle,
            )
        except Exception as e:  # engine-side failure closed the handle
            return reply(
                "500 Internal Server Error",
                {
                    "request_id": handle.request_id,
                    "error": f"{type(e).__name__}: {e}",
                    "kind": type(e).__name__,
                },
                handle=handle,
            )
        return reply(
            "200 OK",
            {
                "request_id": handle.request_id,
                "tokens": [int(t) for t in toks],
            },
            handle=handle,
        )

    def _stale_router_epoch(self, hdr) -> Optional[Tuple[int, int]]:
        """``(placed, current)`` when a placement's ``x-router-epoch``
        header is BELOW the election lease's current epoch — the
        zombie-router case — else ``None`` (no fencing configured, no
        header, or the lease is unreadable: a broken shared filesystem
        must not reject live traffic)."""
        if self._router_epoch_fn is None or hdr is None:
            return None
        try:
            placed = int(hdr)
        except (TypeError, ValueError):
            return None
        try:
            cur = self._router_epoch_fn()
        except Exception:
            return None
        if cur is None or placed >= int(cur):
            return None
        return placed, int(cur)

    def _reply_entry(self, reply, entry):
        """Answer a NON-streaming generate from a WAL tracker entry
        (fresh admissions and duplicate-id dedupes both land here when
        the durable plane is on): wait for the entry to settle — the
        pump thread feeds it from the engine handle — then map its
        outcome through the same status ladder the handle path uses."""
        from ..utils.config import get_config

        timeout_s = get_config().serve_result_timeout_s
        deadline = time.monotonic() + timeout_s
        with entry.cond:
            while not entry.done:
                rem = deadline - time.monotonic()
                if rem <= 0:
                    return reply(
                        "504 Gateway Timeout",
                        {"error": f"no result within {timeout_s}s",
                         "kind": "TimeoutError"},
                        handle=entry.handle,
                    )
                entry.cond.wait(rem)
            err = entry.error
            toks = list(entry.tokens)
        if err is not None:
            kind, msg = err
            status = (
                "504 Gateway Timeout"
                if kind in ("TimeoutError", "DeadlineExceededError")
                else "500 Internal Server Error"
            )
            return reply(
                status, {"error": msg, "kind": kind}, handle=entry.handle
            )
        return reply(
            "200 OK",
            {"tokens": [int(t) for t in toks]},
            handle=entry.handle,
        )

    def _stream_entry(
        self, conn, ctx, entry, t0: float, from_off: int = 0
    ) -> None:
        """NDJSON streaming from a WAL tracker entry — the durable
        twin of :meth:`_stream_generate`. The already-delivered prefix
        past ``from_off`` replays immediately (a reconnecting client
        sends ``from=<count of tokens it already has>``), then the live
        tail follows as the pump lands tokens, then exactly one
        terminal line. Byte-identity of the replayed prefix with what
        the torn connection delivered is inherited from the fleet's
        deterministic replay — the tracker holds THE token sequence,
        every connection is a view of it."""
        import json

        from ..utils.config import get_config

        conn.sendall(
            (
                "HTTP/1.1 200 OK\r\n"
                "Content-Type: application/x-ndjson; charset=utf-8\r\n"
                f"traceparent: {ctx.traceparent()}\r\n"
                "Connection: close\r\n\r\n"
            ).encode("latin-1")
        )
        cursor = max(0, int(from_off))
        timeout_s = get_config().serve_result_timeout_s
        sent = 0
        terminal: Dict[str, Any]
        try:
            while True:
                got = entry.wait(cursor, timeout_s)
                if got is None:  # the no-emission backstop fired
                    terminal = {
                        "error": f"no emission within {timeout_s}s",
                        "kind": "TimeoutError",
                        "request_id": entry.rid,
                    }
                    break
                new, done, err = got
                for t in new:
                    conn.sendall(
                        (json.dumps({"t": int(t)}) + "\n").encode("utf-8")
                    )
                cursor += len(new)
                sent += len(new)
                if done:
                    if err is None:
                        total = time.perf_counter() - t0
                        terminal = {
                            "done": True,
                            "request_id": entry.rid,
                            "tokens_total": cursor,
                            "trace_id": ctx.trace_id,
                            "timing": self._timing_payload(
                                entry.handle, total
                            ),
                        }
                    else:
                        terminal = {
                            "error": err[1],
                            "kind": err[0],
                            "request_id": entry.rid,
                        }
                    break
            conn.sendall((json.dumps(terminal) + "\n").encode("utf-8"))
            status = "200" if terminal.get("done") else "error"
        except OSError:
            # the client went away (again): the pump keeps feeding the
            # tracker and the journal, so the NEXT reconnect resumes
            # from wherever the stream is by then
            status = "client-gone"
        _flight.record(
            "serving", "generate_stream",
            status=status,
            trace_id=ctx.trace_id,
            tokens=sent,
            request_id=entry.rid,
            resumed_from=int(from_off),
            dur_s=round(time.perf_counter() - t0, 6),
        )

    def _stream_generate(self, conn, ctx, handle, t0: float,
                         rid: Optional[str] = None) -> None:
        """The NDJSON success path of ``POST /generate`` with
        ``"stream": true``: headers first (no Content-Length — the
        stream's end is the connection's), then one ``{"t": token}``
        line per emission as the engine emits it, then exactly one
        terminal line — ``{"done": true, request_id, tokens_total,
        trace_id, timing}`` or ``{"error", "kind", request_id}``. The
        per-line flush is the point: a remote router relays each token
        to its caller the moment it lands, and a member killed
        mid-stream tears the connection, which the router treats as a
        replayable replica fault (the emitted prefix folds into the
        replay prompt — byte-identity preserved)."""
        import json

        from ..utils.config import get_config

        conn.sendall(
            (
                "HTTP/1.1 200 OK\r\n"
                "Content-Type: application/x-ndjson; charset=utf-8\r\n"
                f"traceparent: {ctx.traceparent()}\r\n"
                "Connection: close\r\n\r\n"
            ).encode("latin-1")
        )
        # a client-supplied request_id is the stream's identity even
        # without the durable plane: echo it, not the engine handle's
        rid = rid if rid is not None else handle.request_id
        sent = 0
        timeout_s = get_config().serve_result_timeout_s
        terminal: Dict[str, Any]
        try:
            while True:
                try:
                    item = handle._q.get(timeout=timeout_s)
                except Exception:  # queue.Empty: the backstop fired
                    terminal = {
                        "error": f"no emission within {timeout_s}s",
                        "kind": "TimeoutError",
                        "request_id": rid,
                    }
                    break
                if item is handle._DONE:
                    err = handle.error
                    if err is None:
                        total = time.perf_counter() - t0
                        terminal = {
                            "done": True,
                            "request_id": rid,
                            "tokens_total": sent,
                            "trace_id": ctx.trace_id,
                            "timing": self._timing_payload(handle, total),
                        }
                    else:
                        terminal = {
                            "error": str(err),
                            "kind": type(err).__name__,
                            "request_id": rid,
                        }
                    break
                conn.sendall(
                    (json.dumps({"t": int(item)}) + "\n").encode("utf-8")
                )
                sent += 1
            conn.sendall((json.dumps(terminal) + "\n").encode("utf-8"))
            status = "200" if terminal.get("done") else "error"
        except OSError:
            # the client went away mid-stream (a fenced router, a killed
            # process): nothing to answer — the engine-side stream keeps
            # its own lifecycle and the relay identity gate upstream
            # drops whatever else this request emits
            status = "client-gone"
        _flight.record(
            "serving", "generate_stream",
            status=status,
            trace_id=ctx.trace_id,
            tokens=sent,
            request_id=rid,
            dur_s=round(time.perf_counter() - t0, 6),
        )

    def _serve_one(self, conn: socket.socket) -> None:
        import pyarrow as pa

        from ..utils import get_logger

        t0 = time.perf_counter()
        kind, status = "score", "ok"
        # one gate snapshot for the inc/dec PAIR: a kill-switch flip while
        # this request is in flight must not strand the gauge
        tracked = _obs_enabled()
        if tracked:
            _m_active.adjust(1.0)
        try:
            with conn:
                # chaos: a dropped/slow connection at the door — the
                # teardown path below must absorb it like a real one
                _chaos.site("serving.conn")
                first = self._peek(conn)
                if not first:
                    # client connected and went away without a request
                    status = "empty"
                    return
                if first in self._HTTP_PREFIXES:
                    kind = "http"
                    try:
                        kind = self._serve_http(conn)
                    except OSError:
                        status = "error"
                    return
                wf = None
                try:
                    if self._mapper is None:
                        raise RuntimeError(
                            "server has no scoring program (generate-only "
                            "server; use POST /generate)"
                        )
                    rf = _CountingFile(conn.makefile("rb"), _m_bytes_in)
                    reader = pa.ipc.open_stream(rf)
                    # results buffer until the request stream ends: a
                    # client that writes its whole partition before
                    # reading (Spark's mapInArrow generator does) must
                    # never deadlock against our send buffer
                    with _span("serving.request", peer=conn.getpeername()[0]):
                        out_batches = list(self._mapper(reader))
                    conn.shutdown(socket.SHUT_RD)
                    wf = _CountingFile(conn.makefile("wb"), _m_bytes_out)
                    # response = 1 status byte, then the payload: \x00 +
                    # Arrow stream, or \x01 + utf-8 error text (the
                    # executor re-raises it as its task failure — engine
                    # errors must not look like wire corruption)
                    wf.write(b"\x00")
                    if out_batches:
                        with pa.ipc.new_stream(
                            wf, out_batches[0].schema
                        ) as w:
                            for b in out_batches:
                                w.write_batch(b)
                    else:
                        with pa.ipc.new_stream(wf, pa.schema([])):
                            pass
                    wf.flush()
                except Exception as e:
                    status = "error"
                    get_logger("interop.serving").warning(
                        "scoring connection failed", exc_info=True
                    )
                    try:
                        if wf is None:
                            wf = conn.makefile("wb")
                        wf.write(
                            b"\x01"
                            + f"{type(e).__name__}: {e}".encode(
                                "utf-8", "replace"
                            )
                        )
                        wf.flush()
                    except OSError:
                        pass  # client already gone
                finally:
                    # drain any unread request bytes BEFORE closing: a
                    # failure mid-stream leaves data in the receive
                    # buffer, and closing over it makes the kernel send
                    # RST — destroying the in-flight \x01 error reply
                    # (the client would see ConnectionReset instead of
                    # the engine error). Bounded by a timeout so a
                    # wedged client cannot pin the worker.
                    try:
                        conn.settimeout(10)
                        while conn.recv(1 << 16):
                            pass
                    except OSError:
                        pass
                    # then force the FIN at the TCP level: socket.close()
                    # defers while makefile handles are alive, and a
                    # captured log record (exc_info traceback frames —
                    # e.g. pytest's logging plugin) can pin them long
                    # after this thread exits, leaving the client
                    # blocked on read
                    try:
                        conn.shutdown(socket.SHUT_RDWR)
                    except OSError:
                        pass
        except Exception:
            status = "error"
            get_logger("interop.serving").warning(
                "scoring connection teardown failed", exc_info=True
            )
        finally:
            if tracked:
                _m_active.adjust(-1.0)
            _m_requests.inc(kind=kind, status=status)
            if kind == "score" and status != "empty":
                _m_latency.observe(time.perf_counter() - t0)
            if kind not in ("generate", "empty") and status != "empty":
                # generate requests record themselves (with trace ids)
                # inside the handler; real work (score/http) lands in
                # the same `serving` ring, while metrics/health/statusz
                # PROBES get their own — a 15s scrape + health check
                # would otherwise evict the entire trace-id request
                # history from the 512-slot ring within the hour
                ring = (
                    "probes"
                    if kind in ("metrics", "healthz", "statusz", "varz")
                    else "serving"
                )
                _flight.record(
                    ring, kind, status=status,
                    dur_s=round(time.perf_counter() - t0, 6),
                )
            self._limit.release()


def remote_arrow_mapper(address: str):
    """The executor-side function for ``DataFrame.mapInArrow`` against a
    :class:`ScoringServer` at ``"host:port"``.

    The returned closure captures only the address string and imports
    only ``socket``/``pyarrow`` inside — it pickles to Spark workers
    that have NO jax and NO tensorframes_tpu installed (the whole point:
    the engine lives on the TPU host, executors just move Arrow)."""
    host, port_s = address.rsplit(":", 1)
    port = int(port_s)

    def fn(batches):
        import socket as _socket

        import pyarrow as _pa

        it = iter(batches)
        first = next(it, None)
        if first is None:
            return
        conn = _socket.create_connection((host, port))
        try:
            wf = conn.makefile("wb")
            with _pa.ipc.new_stream(wf, first.schema) as w:
                w.write_batch(first)
                for b in it:
                    w.write_batch(b)
            wf.flush()
            conn.shutdown(_socket.SHUT_WR)  # end of request stream
            rf = conn.makefile("rb")
            status = rf.read(1)
            if status == b"\x01":  # server-side failure, text follows
                raise RuntimeError(
                    "remote scoring failed: "
                    + rf.read().decode("utf-8", "replace")
                )
            if status != b"\x00":
                raise RuntimeError(
                    "remote scoring connection closed without a response"
                )
            reader = _pa.ipc.open_stream(rf)
            for b in reader:
                yield b
        finally:
            conn.close()

    return fn


def remote_map_in_arrow(spark_df, address: str, output_schema):
    """``mapInArrow`` against a remote :class:`ScoringServer`: each Spark
    partition streams to the TPU host and back, no driver collect. Pair
    with repartitioning so partitions match the block sizes the scoring
    program wants (one connection = one partition = one logical block
    span)."""
    return spark_df.mapInArrow(remote_arrow_mapper(address), output_schema)
