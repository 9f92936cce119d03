"""Elastic multi-host serving fleet: lease-based membership, host-death
failover, and zero-downtime rolling weight swaps.

PR 17's fleet (``serve/fleet.py``) replicates engines INSIDE one
process: the router holds every
:class:`~tensorframes_tpu.serve.GenerationEngine` object, so a "replica
death" is an exception, never a dead host. This module is the
multi-host tier — the deployment shape where each replica is its own
OS process (its own chip, its own ``ScoringServer`` ingress) and the
router reaches it over HTTP:

- :class:`MemberRegistry` — membership as **epoch-stamped lease files**
  (:class:`~tensorframes_tpu.utils.leases.LeaseStore`, the primitive
  generalized out of ``engine/dist_jobs.py``) in a shared filesystem
  directory. A serving process registers itself with its URL and model
  shape, a background heartbeat keeps the lease fresh, and the epoch in
  the filename is the **fencing token**: a member whose heartbeat
  lapses past the TTL is presumed dead and fenced by a tombstone at
  ``epoch + 1``; if the "dead" process was merely wedged and wakes up,
  its next registry write raises
  :class:`~tensorframes_tpu.utils.failures.StaleLeaseError` — the
  zombie cannot re-assert itself (exactly the dist-jobs write fence).
- :class:`RemoteEngine` — the router-side adapter that makes a remote
  member look like a local engine to the PR-17 router: ``submit()``
  opens a streaming ``POST /generate`` (NDJSON) against the member's
  ingress and relays each token as it lands; ``health()`` forwards
  ``GET /healthz``. A connection torn mid-stream (kill -9, host gone)
  closes the relay with a REPLAYABLE error, so the router resubmits the
  stream's remainder to a survivor recompute-style — byte-identical for
  greedy and seeded sampling, exactly like in-process failover.
- :class:`MemberAgent` — the member-side state machine
  (``ready | draining | probing | swapping | fenced``) wired into the
  server's ``/readyz`` and ``POST /admin/lifecycle``: drains stop
  admission at the ingress while in-flight streams finish, SIGTERM
  triggers drain → final telemetry export → lease release, and a
  lease lost underneath us (we were presumed dead) stops admission
  immediately.
- :func:`connect_fleet` — builds a :class:`~.fleet.Fleet` in
  remote-replica mode (pre-built ``engines=``) plus a registry-sync
  hook on the router tick: new registrations join the roster, expired
  heartbeats fence the member like in-process fencing (streams replay
  to survivors), tombstones and resignations leave.
- :func:`rolling_restart` / :func:`rolling_weight_swap` — one member
  at a time: drain (admission stops, in-flight finishes or migrates),
  restart or hot-swap weights (``engine.swap_weights`` — a device_put
  + pointer flip, zero recompiles), then a **probe generation must
  pass before re-admission**; a failed probe rolls the weights back
  (fleet-wide, so replicas never serve mixed weights) and halts the
  rollout.
- :class:`Autoscaler` — watches the PR-12 time-series (queue depth,
  pages in use, inter-token p99) and calls injectable spawn/drain
  callbacks with cooldown and min/max bounds.

Liveness vs safety, stated once: the lease TTL
(``member_lease_ttl_s``) only affects how FAST a dead member is
noticed; correctness never depends on it. A premature fence of a live
member costs a replay (byte-identical) and the fenced member learns
via ``on_lost``/``StaleLeaseError`` — it can re-register under a new
epoch whenever it is actually healthy.

Chaos sites: ``fleet.member_heartbeat`` fires in the member's
heartbeat sweep (``latency`` past the TTL is the presumed-dead drill);
``fleet.registry`` fires in registry reads/writes (``transient`` there
retries invisibly). Metrics: ``fleet.members``,
``fleet.member_fences_total``, ``fleet.rollouts_total{outcome}``,
``fleet.scale_decisions_total{direction}`` (docs/observability.md).
Cookbook: docs/fault_tolerance.md "Elastic fleet";
deployment shapes: docs/serving_llm.md.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import uuid
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..obs import flight as _flight
from ..obs.metrics import counter as _counter, gauge as _gauge
from ..utils import chaos as _chaos
from ..utils.config import get_config
from ..utils.failures import (
    DeadlineExceededError,
    StaleLeaseError,
    StaleRouterEpochError,
    TenantThrottledError,
    run_with_retries,
)
from ..utils.leases import LeaseStore, LeaseView
from ..utils.logging import get_logger
from .engine import EngineUnhealthyError
from .fleet import Fleet
from .router_ha import ROUTER_LEASE_KEY, router_epoch_from
from .scheduler import GenerationHandle, QueueFullError

__all__ = [
    "Autoscaler",
    "LocalProcessProvisioner",
    "MemberAgent",
    "MemberRegistry",
    "RemoteEngine",
    "connect_fleet",
    "load_params",
    "rolling_restart",
    "rolling_weight_swap",
    "save_params",
]

logger = get_logger("serve.membership")

_m_members = _gauge(
    "fleet.members",
    "Live members in the shared registry (fresh heartbeat, not "
    "tombstoned)",
)
_m_member_fences = _counter(
    "fleet.member_fences_total",
    "Members fenced via lease tombstone after an expired heartbeat "
    "(presumed dead; their streams replayed to survivors)",
)
_m_rollouts = _counter(
    "fleet.rollouts_total",
    "Rolling restarts / weight swaps, by terminal outcome "
    "(ok | rolled_back | halted)",
    labels=("outcome",),
)
_m_scale_decisions = _counter(
    "fleet.scale_decisions_total",
    "Autoscaler actions taken, by direction (up | down)",
    labels=("direction",),
)


# -- checkpoint helpers ----------------------------------------------------
#
# A deliberately tiny format for the SERVING plane's hot swaps: flatten
# the params pytree (nested dicts + per-block lists) to dotted keys in
# one ``np.savez``. Training-state checkpointing keeps its Orbax path
# (utils/checkpoint.py); serving processes swapping weights need no
# checkpointing dependency at all, just numpy.

def _flatten_params(tree: Any, prefix: str, out: Dict[str, np.ndarray]):
    if isinstance(tree, dict):
        for k in tree:
            if "." in str(k):
                raise ValueError(f"param key {k!r} contains '.'")
            _flatten_params(tree[k], f"{prefix}{k}.", out)
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            _flatten_params(v, f"{prefix}[{i}].", out)
    else:
        out[prefix[:-1]] = np.asarray(tree)


def save_params(path: str, model_or_params: Any) -> str:
    """Save a model's params (or a bare params dict) as one ``.npz``
    the rolling weight swap can ship to members. Returns ``path``."""
    params = getattr(model_or_params, "params", model_or_params)
    flat: Dict[str, np.ndarray] = {}
    _flatten_params(params, "", flat)
    with open(path, "wb") as f:
        np.savez(f, **flat)
    return path


def load_params(path: str) -> Dict[str, Any]:
    """Load a :func:`save_params` checkpoint back into the nested
    params structure (dicts, per-block lists, static ints restored as
    Python scalars) that :meth:`GenerationEngine.swap_weights`
    validates against the live model."""
    with np.load(path) as z:
        flat = {k: z[k] for k in z.files}
    root: Dict[str, Any] = {}
    for key in sorted(flat):
        parts = key.split(".")
        node: Any = root
        for i, part in enumerate(parts):
            last = i == len(parts) - 1
            if part.startswith("[") and part.endswith("]"):
                idx = int(part[1:-1])
                while len(node) <= idx:
                    node.append(None)
                if last:
                    node[idx] = _unflatten_leaf(flat[key])
                else:
                    if node[idx] is None:
                        node[idx] = (
                            [] if parts[i + 1].startswith("[") else {}
                        )
                    node = node[idx]
            else:
                if last:
                    node[part] = _unflatten_leaf(flat[key])
                else:
                    if part not in node:
                        node[part] = (
                            [] if parts[i + 1].startswith("[") else {}
                        )
                    node = node[part]
    return root


def _unflatten_leaf(arr: np.ndarray) -> Any:
    # static scalars (``n_heads``) round-trip as 0-d arrays; the model
    # treats them as Python ints, so restore them that way
    return arr.item() if arr.ndim == 0 else arr


# -- the shared registry ---------------------------------------------------


class MemberRegistry(LeaseStore):
    """The fleet's membership table: one lease per member under
    ``<path>/leases/``, metadata (URL, pid, model shape, lifecycle
    state) in the lease payload.

    Members call :meth:`register` once and :meth:`publish_state` on
    lifecycle transitions; the inherited heartbeat thread renews the
    lease every ``heartbeat_s``. Routers call :meth:`members` to scan
    and :meth:`fence` to tombstone a member whose heartbeat lapsed —
    the steal races at ``epoch + 1``, so concurrent routers fence a
    victim exactly once, and the victim's own next write raises
    :class:`StaleLeaseError` (the zombie rejection)."""

    def __init__(
        self,
        path: str,
        worker_id: Optional[str] = None,
        ttl_s: Optional[float] = None,
        heartbeat_s: Optional[float] = None,
    ):
        cfg = get_config()
        if worker_id is None:
            worker_id = (
                f"{socket.gethostname()}-{os.getpid()}-"
                f"{uuid.uuid4().hex[:6]}"
            )
        super().__init__(
            path,
            worker_id,
            ttl_s=float(
                cfg.member_lease_ttl_s if ttl_s is None else ttl_s
            ),
            heartbeat_s=float(
                cfg.member_heartbeat_s
                if heartbeat_s is None
                else heartbeat_s
            ),
        )

    # every registry mutation/scan passes the chaos site inside a retry
    # loop: a ``transient`` there (flaky shared filesystem) is invisible

    def register(self, name: str, meta: Dict[str, Any]) -> int:
        """Claim the member's lease and publish its registration
        metadata (``url``, ``pid``, ``state``, model shape). Raises
        ``RuntimeError`` when the name is live-leased by another
        process (two members may not share a name)."""

        def attempt() -> int:
            _chaos.site("fleet.registry")
            epoch = self.acquire(name, meta=meta)
            if epoch is None:
                cur = self._scan(name)
                if cur is not None and cur.terminal:
                    epoch = self._reincarnate(name, cur, meta)
            if epoch is None:
                raise RuntimeError(
                    f"member name {name!r} is live-leased by another "
                    f"process"
                )
            return epoch

        epoch = run_with_retries(attempt, what="fleet.registry")
        _flight.record(
            "membership", "register",
            member=name, epoch=epoch, url=meta.get("url"),
        )
        logger.warning(
            "membership: %s registered as %r epoch %d (%s)",
            self.worker_id, name, epoch, meta.get("url"),
        )
        return epoch

    def _reincarnate(self, name, cur: LeaseView, meta) -> Optional[int]:
        """Claim a live lease PAST a tombstone: a fresh process reusing
        a fenced/resigned member's name is a new incarnation and races
        for ``tombstone_epoch + 1`` — epochs stay monotonic, so the old
        incarnation's zombie writes stay epoch-rejected forever. (Job
        leases deliberately lack this: a terminal block must never
        re-run; a terminal MEMBER NAME may serve again.)"""
        epoch = cur.epoch + 1
        fname = f"{name}.e{epoch:06d}.lease"
        if not self._create_excl(fname, self._payload(epoch, meta=meta)):
            return None  # lost the race to another new incarnation
        with self._lock:
            self._held[name] = (epoch, fname)
        self._ensure_heartbeat()
        self._unlink_superseded(name, epoch)
        return epoch

    def publish_state(self, name: str, **meta_updates: Any) -> int:
        """Fenced metadata write: merge ``meta_updates`` over the
        member's current metadata. Raises :class:`StaleLeaseError`
        when this process no longer owns the lease — a fenced zombie's
        late write lands HERE and is rejected."""

        def attempt() -> int:
            _chaos.site("fleet.registry")
            cur = self._scan(name)
            meta = dict(cur.meta) if cur is not None else {}
            meta.update(meta_updates)
            return self.publish(name, meta)

        return run_with_retries(attempt, what="fleet.registry")

    def members(self) -> List[LeaseView]:
        """Every member's current lease view (live, expired, and
        tombstoned alike — the router-side sync decides what each
        means)."""

        def attempt() -> List[LeaseView]:
            _chaos.site("fleet.registry")
            return self.scan_all()

        return run_with_retries(attempt, what="fleet.registry")

    def fence(self, name: str) -> Optional[int]:
        """Tombstone a presumed-dead member at ``epoch + 1``. Returns
        the tombstone epoch, or ``None`` when another router already
        fenced it (or it resigned) — the exactly-once guarantee rides
        the exclusive epoch-file create."""

        def attempt() -> Optional[int]:
            _chaos.site("fleet.registry")
            return self.steal(name, state="fenced")

        epoch = run_with_retries(attempt, what="fleet.registry")
        if epoch is not None:
            _m_member_fences.inc()
            _flight.record(
                "membership", "fence", member=name, epoch=epoch,
            )
            logger.warning(
                "membership: member %r fenced at epoch %d (heartbeat "
                "expired — presumed dead)", name, epoch,
            )
        return epoch

    def resign(self, name: str) -> None:
        """Clean departure: tombstone our own lease as ``resigned`` so
        routers drop the member without fencing theatrics."""
        self.mark_state(name, "resigned")
        _flight.record("membership", "resign", member=name)

    def _heartbeat_sweep(self) -> None:
        # the presumed-death drill: ``latency`` injected here past the
        # TTL delays renewal until the lease has expired and a router
        # fences us; ``transient`` skips one sweep (survivable)
        _chaos.site("fleet.member_heartbeat")
        super()._heartbeat_sweep()


# -- the router-side remote engine adapter ---------------------------------


class _RemotePool:
    """Placement-key shim: the router sorts candidates by
    ``pool.pages_free``; for a remote member that is the last health
    poll's view (the watchdog refreshes it every tick)."""

    def __init__(self, engine: "RemoteEngine"):
        self._engine = engine

    @property
    def pages_free(self) -> int:
        h = self._engine._last_health
        return max(
            0,
            int(h.get("pages_capacity", 0)) - int(h.get("pages_in_use", 0)),
        )


class _RemoteSlot:
    __slots__ = ("req",)

    def __init__(self, tenant: str):
        self.req = _RemoteSlotReq(tenant)


class _RemoteSlotReq:
    __slots__ = ("tenant",)

    def __init__(self, tenant: str):
        self.tenant = tenant


class _RemoteScheduler:
    """Scheduler-shaped view of a remote member, backed by the relays
    this ROUTER has open against it (per-tenant accounting must count
    this router's own in-flight placements synchronously — the remote
    health poll lags a tick) plus the health poll's queue depth."""

    def __init__(self, engine: "RemoteEngine"):
        self._engine = engine

    @property
    def queue_depth(self) -> int:
        return int(self._engine._last_health.get("queue_depth", 0))

    @property
    def slots(self) -> List[Optional[_RemoteSlot]]:
        with self._engine._lock:
            tenants = [
                t for _, t in self._engine._inflight.values()
            ]
        return [_RemoteSlot(t) for t in tenants]

    def tenant_counts(self) -> Tuple[dict, dict]:
        active: Dict[str, int] = {}
        with self._engine._lock:
            for _, tenant in self._engine._inflight.values():
                active[tenant] = active.get(tenant, 0) + 1
        return active, {}

    def has_work(self) -> bool:
        with self._engine._lock:
            return bool(self._engine._inflight)

    def fail_all(self, error: BaseException) -> int:
        return self._engine._fail_inflight(error)


class RemoteEngine:
    """A remote serving member, duck-typed as a local engine for the
    PR-17 router: ``submit()`` opens a streaming ``POST /generate``
    against the member's ingress and relays NDJSON tokens into the
    router's handle the moment they land; ``health()`` forwards ``GET
    /healthz``. A torn connection mid-stream (the member was killed, or
    the host vanished) finishes the relay with a replayable
    ``RuntimeError`` — the router folds the emitted prefix into the
    prompt and resubmits to a survivor, byte-identical.

    The ``_thread is None`` shape is deliberate: the router's fence
    path then drains via :meth:`_fail_inflight` (this router's relays)
    instead of trying to reach into a remote process, and the probe
    path's ``run_until_idle()`` is a no-op (the member steps itself).
    """

    #: pre-submit error kinds from the member's JSON replies, re-raised
    #: as the exception class the router's placement loop expects; a
    #: member answering "Draining" raced an administrative drain — the
    #: router treats it like unhealthy and tries the next candidate
    _KIND_MAP: Dict[str, Callable[[str], BaseException]] = {
        "QueueFullError": QueueFullError,
        "EngineUnhealthyError": EngineUnhealthyError,
        "Draining": EngineUnhealthyError,
        "ValueError": ValueError,
        "DeadlineExceededError": DeadlineExceededError,
        "TimeoutError": TimeoutError,
        # the member refused a ZOMBIE router's placement (its
        # x-router-epoch is below the election lease's current epoch,
        # serve/router_ha.py) — non-replayable: the new active router
        # already owns this request
        "StaleRouterEpochError": StaleRouterEpochError,
    }

    def __init__(
        self,
        name: str,
        url: str,
        *,
        eos_id: Optional[int] = None,
        max_seq_len: int = 2048,
        connect_timeout_s: float = 5.0,
    ):
        self.name = name
        self.url = url  # "host:port"
        self.eos_id = eos_id
        self.max_seq_len = int(max_seq_len)
        self.connect_timeout_s = float(connect_timeout_s)
        self.healthy = True
        #: ``() -> Optional[int]``: the placing fleet's router-election
        #: epoch (set by the membership sync when router HA is attached;
        #: ``serve/router_ha.py``). None / returning None → no fencing
        #: header on the wire, the pre-HA format.
        self.router_epoch_fn: Optional[Callable[[], Optional[int]]] = None
        self._stop_wedged = False
        self._thread = None
        self._poison = None
        self._lock = threading.Lock()
        self._id_lock = threading.Lock()
        self._req_counter = 0
        #: rid -> (handle, tenant) for relays this router holds open
        self._inflight: Dict[int, Tuple[GenerationHandle, str]] = {}
        self._last_health: Dict[str, Any] = {}
        self.scheduler = _RemoteScheduler(self)
        self.pool = _RemotePool(self)

    # -- HTTP plumbing -----------------------------------------------------

    def _connect(self) -> socket.socket:
        host, _, port = self.url.rpartition(":")
        return socket.create_connection(
            (host, int(port)), timeout=self.connect_timeout_s
        )

    def _request(
        self,
        method: str,
        path: str,
        body: Optional[dict] = None,
        timeout_s: Optional[float] = None,
    ) -> Tuple[int, dict]:
        """One plain (non-streaming) HTTP exchange with the member;
        returns ``(status_code, parsed_json_body)``."""
        payload = b"" if body is None else json.dumps(body).encode("utf-8")
        conn = self._connect()
        try:
            if timeout_s is not None:
                conn.settimeout(timeout_s)
            conn.sendall(
                (
                    f"{method} {path} HTTP/1.1\r\n"
                    f"Host: {self.url}\r\n"
                    f"Content-Length: {len(payload)}\r\n"
                    f"Connection: close\r\n\r\n"
                ).encode("latin-1")
                + payload
            )
            f = conn.makefile("rb")
            status_line = f.readline().decode("latin-1", "replace")
            status = int(status_line.split(" ", 2)[1])
            while f.readline() not in (b"\r\n", b"\n", b""):
                pass  # headers; Connection: close → body runs to EOF
            raw = f.read()
        finally:
            conn.close()
        try:
            parsed = json.loads(raw.decode("utf-8")) if raw else {}
        except ValueError:
            parsed = {}
        return status, parsed if isinstance(parsed, dict) else {}

    # -- the engine surface the router drives ------------------------------

    def health(self) -> Dict[str, Any]:
        """The member's ``GET /healthz`` snapshot, shaped for the
        router's watchdog. A connection failure reads as unhealthy —
        the watchdog fences on it, and the registry sweep (lease
        expiry) independently confirms an actual death."""
        try:
            status, body = self._request(
                "GET", "/healthz", timeout_s=self.connect_timeout_s
            )
        except OSError as e:
            self.healthy = False
            return {
                "healthy": False,
                "reachable": False,
                "error": f"{type(e).__name__}: {e}",
                "last_step_age_s": 0.0,
                "queue_depth": 0,
                "active_slots": 0,
                "pages_in_use": 0,
                "pages_capacity": 0,
                "stepping_thread_alive": False,
            }
        body.setdefault("last_step_age_s", 0.0)
        body.setdefault("queue_depth", 0)
        body.setdefault("active_slots", 0)
        body.setdefault("pages_in_use", 0)
        body.setdefault("pages_capacity", 0)
        body.setdefault("stepping_thread_alive", True)
        body["healthy"] = bool(body.get("healthy")) and status == 200
        body["reachable"] = True
        self._last_health = body
        self.healthy = body["healthy"]
        return body

    @property
    def num_step_programs(self) -> int:
        return int(self._last_health.get("num_step_programs", 0))

    def submit(
        self,
        prompt,
        max_new_tokens: int,
        temperature: float = 0.0,
        top_p: float = 1.0,
        seed: int = 0,
        eos_id: Optional[int] = None,
        block: bool = True,
        timeout: Optional[float] = None,
        deadline: Optional[float] = None,
        trace=None,
        tenant: Optional[str] = None,
        _handle_factory=None,
    ) -> GenerationHandle:
        """Open a streaming generation against the member. Pre-submit
        refusals re-raise as the exception class the member named in
        its JSON ``kind`` (queue full, unhealthy, throttled, 400);
        after the 200 status line a daemon reader relays each NDJSON
        token into the handle, and a torn connection finishes the
        handle with a replayable error."""
        if not self.healthy:
            raise EngineUnhealthyError(
                f"remote member {self.name} is unhealthy"
            )
        spec: Dict[str, Any] = {
            "prompt": [int(t) for t in np.asarray(prompt).ravel()],
            "max_new_tokens": int(max_new_tokens),
            "temperature": float(temperature),
            "top_p": float(top_p),
            "seed": int(seed),
            "stream": True,
        }
        if eos_id is not None:
            spec["eos_id"] = int(eos_id)
        if deadline is not None:
            spec["deadline_s"] = float(deadline)
        if tenant:
            spec["tenant"] = str(tenant)
        payload = json.dumps(spec).encode("utf-8")
        traceparent = None
        if trace is not None:
            try:
                traceparent = trace.traceparent()
            except Exception:
                traceparent = None
        with self._id_lock:
            self._req_counter += 1
            rid = self._req_counter
        router_epoch = None
        if self.router_epoch_fn is not None:
            try:
                router_epoch = self.router_epoch_fn()
            except Exception:
                router_epoch = None
        conn = None
        try:
            conn = self._connect()
            extra = (
                f"traceparent: {traceparent}\r\n" if traceparent else ""
            )
            if router_epoch is not None:
                # the fencing token: a member whose election-lease view
                # is AHEAD of this epoch rejects the placement (zombie
                # router; serve/router_ha.py)
                extra += f"x-router-epoch: {int(router_epoch)}\r\n"
            conn.sendall(
                (
                    f"POST /generate HTTP/1.1\r\n"
                    f"Host: {self.url}\r\n"
                    f"Content-Length: {len(payload)}\r\n"
                    f"{extra}"
                    f"Connection: close\r\n\r\n"
                ).encode("latin-1")
                + payload
            )
            f = conn.makefile("rb")
            status_line = f.readline().decode("latin-1", "replace")
            status = int(status_line.split(" ", 2)[1])
            # keep the refusal headers: the member's own Retry-After
            # must reach the ultimate client verbatim, not be
            # recomputed from this router's (different) backlog
            resp_headers: Dict[str, str] = {}
            while True:
                line = f.readline()
                if line in (b"\r\n", b"\n", b""):
                    break
                k, _, v = line.partition(b":")
                resp_headers[
                    k.strip().lower().decode("latin-1", "replace")
                ] = v.strip().decode("latin-1", "replace")
            if status != 200:
                raw = f.read()
                conn.close()
                self._raise_refusal(
                    status, raw,
                    retry_after=resp_headers.get("retry-after"),
                )
        except (OSError, IndexError, ValueError) as e:
            # the member went away between the health poll and this
            # placement (or refused the connection outright): shaped as
            # unhealthy so the router's placement loop moves to the
            # next candidate this tick
            if conn is not None:
                try:
                    conn.close()
                except OSError:
                    pass
            if isinstance(e, (IndexError, ValueError)):
                raise EngineUnhealthyError(
                    f"remote member {self.name} sent a malformed "
                    f"response: {e}"
                ) from e
            raise EngineUnhealthyError(
                f"remote member {self.name} unreachable: "
                f"{type(e).__name__}: {e}"
            ) from e
        handle = (
            _handle_factory(rid)
            if _handle_factory is not None
            else GenerationHandle(rid)
        )
        with self._lock:
            self._inflight[rid] = (handle, str(tenant or ""))
        reader = threading.Thread(
            target=self._relay,
            args=(conn, f, rid, handle),
            name=f"tft-remote-relay-{self.name}-{rid}",
            daemon=True,
        )
        reader.start()
        return handle

    def _raise_refusal(
        self,
        status: int,
        raw: bytes,
        retry_after: Optional[str] = None,
    ) -> None:
        """Re-raise a member's pre-submit refusal as the exception
        class it named. ``retry_after`` (the member's literal
        ``Retry-After`` header) rides the exception as
        ``retry_after_hint`` so the serving layer fronting this router
        can echo the MEMBER's verbatim hint to the client instead of
        recomputing one from the router's own (empty) backlog."""
        try:
            body = json.loads(raw.decode("utf-8")) if raw else {}
        except ValueError:
            body = {}
        kind = str(body.get("kind", ""))
        msg = str(
            body.get("error", f"member {self.name} answered {status}")
        )

        def _hinted(exc: BaseException) -> BaseException:
            exc.retry_after_hint = retry_after
            return exc

        if kind == "TenantThrottledError":
            raise _hinted(
                TenantThrottledError(
                    msg,
                    retry_after=float(body.get("retry_after", 1.0)),
                    reason=str(body.get("reason", "quota")),
                    tenant=str(body.get("tenant", "")),
                )
            )
        exc_cls = self._KIND_MAP.get(kind)
        if exc_cls is not None:
            raise _hinted(exc_cls(msg))
        if status in (503, 501):
            raise _hinted(EngineUnhealthyError(msg))
        if status == 400:
            raise ValueError(msg)
        raise RuntimeError(f"member {self.name}: HTTP {status}: {msg}")

    def _relay(self, conn, f, rid: int, handle: GenerationHandle) -> None:
        """Reader thread for one streaming generation: NDJSON lines →
        handle emissions; the terminal line (or a torn connection)
        closes the handle. The handle is a router relay, so its close
        reports to the fleet's failover machinery."""
        err: Optional[BaseException] = None
        terminal = False
        try:
            conn.settimeout(get_config().serve_result_timeout_s)
            for line in f:
                line = line.strip()
                if not line:
                    continue
                d = json.loads(line.decode("utf-8"))
                if "t" in d:
                    handle._emit(int(d["t"]))
                    continue
                terminal = True
                if not d.get("done"):
                    kind = str(d.get("kind", "RuntimeError"))
                    exc_cls = self._KIND_MAP.get(kind, RuntimeError)
                    err = exc_cls(str(d.get("error", "remote error")))
                break
            if not terminal:
                # EOF before the terminal line: the member died
                # mid-stream (kill -9, host gone) — a REPLAYABLE fault;
                # the router folds the emitted prefix into the replay
                err = RuntimeError(
                    f"member {self.name} connection lost mid-stream "
                    f"(request {rid})"
                )
        except (OSError, ValueError) as e:
            err = RuntimeError(
                f"member {self.name} stream failed mid-flight: "
                f"{type(e).__name__}: {e}"
            )
        finally:
            try:
                conn.close()
            except OSError:
                pass
            with self._lock:
                self._inflight.pop(rid, None)
            handle._finish(err)

    def _fail_inflight(self, error: BaseException) -> int:
        """Fail every relay this router holds open against the member
        (the router's fence-drain path for a ``_thread is None``
        engine). The remote process — if it still exists — keeps
        decoding into closed sockets; its late bytes go nowhere."""
        with self._lock:
            victims = list(self._inflight.values())
            self._inflight.clear()
        for handle, _ in victims:
            handle._finish(error)
        return len(victims)

    def inject_fault(self, error: BaseException) -> None:
        self._fail_inflight(error)

    def restart(self) -> "RemoteEngine":
        """Ask the member to restart its engine (``POST
        /admin/lifecycle``) — the auto-restart path after a fence.
        Raises when the member is unreachable or refuses (it stays
        fenced for the next attempt)."""
        status, body = self.lifecycle("restart")
        if status != 200:
            raise RuntimeError(
                f"member {self.name} restart failed: HTTP {status}: "
                f"{body.get('error')}"
            )
        self.healthy = True
        return self

    def lifecycle(self, action: str, **spec: Any) -> Tuple[int, dict]:
        """Drive the member's lifecycle actuator. Returns
        ``(status, body)`` — rollout orchestration checks the status
        rather than interpreting exceptions."""
        return self._request(
            "POST",
            "/admin/lifecycle",
            body={"action": action, **spec},
            timeout_s=max(self.connect_timeout_s, 30.0),
        )

    def start(self) -> "RemoteEngine":
        return self  # the member steps itself

    def run_until_idle(self) -> None:
        pass  # probe results arrive over the stream; nothing to drive

    def stop(self) -> None:
        # the router stopping must NOT stop the remote member (other
        # routers may be serving through it); open relays are failed by
        # Fleet.stop()'s sweep
        pass


# -- the member-side agent -------------------------------------------------


class MemberAgent:
    """One serving process's membership state machine, wired into its
    :class:`~tensorframes_tpu.interop.serving.ScoringServer`:

    - ``/readyz`` answers from :meth:`_readiness` — 503 unless the
      state is ``ready`` (draining / probing / swapping / fenced are
      healthy-but-not-admitting states; ``/healthz`` stays 200);
    - ``POST /admin/lifecycle`` drives :meth:`_lifecycle` (drain /
      admit / restart / swap / rollback / status / resign);
    - the registry lease carries ``state`` in its metadata, so routers
      see transitions without polling every member's HTTP endpoint;
    - SIGTERM (:meth:`install_sigterm`) triggers the graceful drain:
      stop admission, wait for in-flight streams to finish, export a
      final telemetry snapshot, resign the lease, stop the server.

    ``swap`` loads a :func:`save_params` checkpoint and hot-swaps it
    into the live engine (``swap_weights`` — a device_put + pointer
    flip under the step lock, zero recompiles), stashing the old params
    so ``rollback`` can restore them when the orchestrator's probe
    fails."""

    def __init__(
        self,
        engine,
        registry: MemberRegistry,
        name: str,
        *,
        host: str = "127.0.0.1",
        port: int = 0,
        drain_timeout_s: float = 30.0,
        tier: str = "mixed",
        server_kwargs: Optional[Dict[str, Any]] = None,
    ):
        from ..interop.serving import ScoringServer
        from .tiers import TIERS

        if tier not in TIERS:
            raise ValueError(f"unknown tier {tier!r}; expected one of {TIERS}")
        self.engine = engine
        self.registry = registry
        self.name = name
        #: advertised placement role (serve/tiers.py), carried in the
        #: lease metadata so routers apply it on join without polling
        self.tier = str(tier)
        self.drain_timeout_s = float(drain_timeout_s)
        self._state = "ready"
        self._state_lock = threading.Lock()
        self._old_params: Optional[Dict[str, Any]] = None
        self._shutdown_done = threading.Event()
        kw = dict(server_kwargs or {})
        # the member-side half of zombie-router fencing: /generate
        # compares a placement's x-router-epoch header against the
        # election lease's current epoch in the shared registry dir and
        # answers 409 StaleRouterEpochError when it is superseded
        # (serve/router_ha.py; cached scan, ~one clock read/request)
        kw.setdefault("router_epoch_fn", router_epoch_from(registry))
        self.server = ScoringServer(
            engine=engine,
            host=host,
            port=port,
            readiness=self._readiness,
            lifecycle=self._lifecycle,
            **kw,
        )

    # -- state -------------------------------------------------------------

    @property
    def state(self) -> str:
        with self._state_lock:
            return self._state

    def _set_state(self, state: str, publish: bool = True) -> None:
        with self._state_lock:
            self._state = state
        if publish:
            try:
                self.registry.publish_state(self.name, state=state)
            except StaleLeaseError:
                # fenced underneath us: the registry write is refused
                # (the zombie rejection) — stop admitting; the router
                # already replayed our streams elsewhere
                with self._state_lock:
                    self._state = "fenced"
                logger.warning(
                    "membership: %s state publish fenced (presumed "
                    "dead); admission stopped", self.name,
                )

    def _readiness(self) -> Tuple[bool, str]:
        state = self.state
        if state != "ready":
            return False, state
        try:
            healthy = bool(self.engine.health().get("healthy"))
        except Exception:
            healthy = False
        return healthy, "ready" if healthy else "unhealthy"

    # -- lifecycle actuator ------------------------------------------------

    def _lifecycle(self, action: str, spec: Dict[str, Any]) -> Dict[str, Any]:
        if action == "drain":
            self._set_state("draining")
            return {"state": self.state}
        if action == "admit":
            # NOTE: the rollback stash survives re-admission — during a
            # rolling swap every member is re-admitted as soon as ITS
            # probe passes, and a LATER member's failure must still be
            # able to roll this one back; only an explicit "commit" (the
            # whole rollout succeeded) drops the stash
            self._set_state("ready")
            return {"state": self.state}
        if action == "commit":
            self._old_params = None  # the rollout committed fleet-wide
            return {"state": self.state, "committed": True}
        if action == "restart":
            self._set_state("probing")
            try:
                self.engine.restart()
            except Exception:
                self._set_state("draining")
                raise
            return {"state": self.state, "restarted": True}
        if action == "swap":
            path = spec.get("checkpoint")
            if not path:
                raise ValueError("swap needs a 'checkpoint' path")
            self._set_state("swapping")
            try:
                params = load_params(str(path))
                old = self.engine.swap_weights(params)
            except Exception:
                self._set_state("draining")
                raise
            if self._old_params is None:
                # first swap of this rollout: stash for rollback (a
                # re-delivered swap keeps the ORIGINAL stash — rolling
                # back twice must not "restore" the bad weights)
                self._old_params = old
            self._set_state("probing")
            return {"state": self.state, "swapped": True}
        if action == "rollback":
            if self._old_params is None:
                raise ValueError("nothing to roll back")
            self.engine.swap_weights(self._old_params)
            self._old_params = None
            self._set_state("probing")
            return {"state": self.state, "rolled_back": True}
        if action == "status":
            ready, state = self._readiness()
            return {
                "state": state, "ready": ready,
                "held_epoch": self.registry.held_epoch(self.name),
            }
        if action == "resign":
            threading.Thread(
                target=self.shutdown, daemon=True,
                name=f"tft-member-shutdown-{self.name}",
            ).start()
            return {"state": "draining", "resigning": True}
        raise ValueError(f"unknown lifecycle action {action!r}")

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> Tuple[str, int]:
        """Start the ingress, register the membership lease, and begin
        heartbeating. Returns the bound ``(host, port)``."""
        host, port = self.server.start()
        meta = {
            "url": f"{host}:{port}",
            "pid": os.getpid(),
            "state": "ready",
            "tier": self.tier,
            "eos_id": getattr(self.engine, "eos_id", None),
            "max_seq_len": getattr(self.engine, "max_seq_len", 2048),
        }
        self.registry.on_lost = self._on_lease_lost
        self.registry.register(self.name, meta)
        return host, port

    def _on_lease_lost(self, key, epoch, cur) -> None:
        """The heartbeat sweep found our lease stolen: we were presumed
        dead and fenced. Stop admitting immediately — the router has
        already replayed our streams; anything we emit now lands in
        closed sockets."""
        if key != self.name:
            return
        with self._state_lock:
            self._state = "fenced"
        _flight.record(
            "membership", "lease_lost", member=self.name, epoch=epoch,
            holder=None if cur is None else cur.worker,
        )
        logger.warning(
            "membership: %s lost its lease at epoch %d (fenced by a "
            "router); admission stopped", self.name, epoch,
        )

    def wait_idle(self, timeout_s: Optional[float] = None) -> bool:
        """Block until the engine has no queued or active work (True),
        or the timeout passes (False)."""
        deadline = time.monotonic() + (
            self.drain_timeout_s if timeout_s is None else timeout_s
        )
        while time.monotonic() < deadline:
            h = self.engine.health()
            if not h["queue_depth"] and not h["active_slots"]:
                return True
            time.sleep(0.02)
        return False

    def shutdown(self, timeout_s: Optional[float] = None) -> bool:
        """The graceful exit (SIGTERM / resign): stop admission, let
        in-flight streams finish (up to the drain timeout — leftovers
        fail on engine stop and the router replays them to survivors),
        export a final telemetry snapshot, release the membership and
        any job leases, stop the ingress. Idempotent. Returns whether
        the drain finished cleanly (no streams abandoned)."""
        if self._shutdown_done.is_set():
            return True
        self._set_state("draining")
        clean = self.wait_idle(timeout_s)
        try:
            from ..obs import export as _obs_export

            _obs_export.export_snapshot()
        except Exception:
            logger.warning(
                "membership: %s final telemetry export failed",
                self.name, exc_info=True,
            )
        try:
            self.registry.resign(self.name)
        except Exception:
            logger.warning(
                "membership: %s resign failed", self.name, exc_info=True
            )
        self.registry.stop()
        self._shutdown_done.set()
        try:
            self.server.stop()
        except Exception:
            logger.warning(
                "membership: %s server stop failed", self.name,
                exc_info=True,
            )
        try:
            if self.engine._thread is not None:
                self.engine.stop()
        except Exception:
            pass
        _flight.record(
            "membership", "shutdown", member=self.name, clean=clean,
        )
        return clean

    def install_sigterm(self) -> None:
        """Route SIGTERM to :meth:`shutdown` — the platform's
        drain-before-kill contract. Call from the main thread."""
        import signal as _signal

        def _handler(signum, frame):
            logger.warning(
                "membership: %s received SIGTERM; draining", self.name
            )
            self.shutdown()
            raise SystemExit(0)

        _signal.signal(_signal.SIGTERM, _handler)

    def __enter__(self) -> "MemberAgent":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()


# -- router-side membership sync -------------------------------------------


class _MemberSync:
    """The fleet's registry-sync tick hook: reconcile the router's
    replica roster against the shared registry.

    - a fresh lease unknown to the roster joins as a
      :class:`RemoteEngine` replica;
    - an EXPIRED lease is fenced — tombstone in the registry (exactly
      once across routers, via the epoch race) AND
      :meth:`Fleet._fence` locally, so the member's streams replay to
      survivors exactly like an in-process replica death;
    - a tombstone (``fenced``/``resigned``) leaves the roster (fencing
      locally first unless it resigned after a clean drain);
    - metadata ``state`` transitions map to the router's administrative
      gates: ``draining`` → :meth:`Fleet.drain_replica`, back to
      ``ready`` → :meth:`Fleet.admit_replica` (probe-gated)."""

    def __init__(
        self,
        fleet: Fleet,
        registry: MemberRegistry,
        interval_s: float = 0.5,
        engine_factory: Optional[Callable[[str, dict], Any]] = None,
    ):
        self.fleet = fleet
        self.registry = registry
        self.interval_s = float(interval_s)
        self._engine_factory = engine_factory or self._default_engine
        self._last_sync = 0.0
        self._admitting: set = set()

    @staticmethod
    def _default_engine(name: str, meta: dict) -> RemoteEngine:
        eos = meta.get("eos_id")
        return RemoteEngine(
            name,
            str(meta.get("url", "")),
            eos_id=None if eos is None else int(eos),
            max_seq_len=int(meta.get("max_seq_len", 2048) or 2048),
        )

    def __call__(self) -> None:
        now = time.monotonic()
        if now - self._last_sync < self.interval_s:
            return
        self._last_sync = now
        try:
            views = self.registry.members()
        except Exception:
            logger.warning(
                "membership: registry scan failed; roster unchanged",
                exc_info=True,
            )
            return
        fleet = self.fleet
        roster = set(fleet.replica_names)
        seen: set = set()
        live = 0
        for view in views:
            name = view.key
            if name == ROUTER_LEASE_KEY:
                # the router-ELECTION lease (serve/router_ha.py) shares
                # the directory; it is not a member and must never be
                # fenced/joined as one
                continue
            seen.add(name)
            if view.terminal:
                if name in roster:
                    self._leave(name, resigned=view.state == "resigned")
                continue
            if view.expired:
                # presumed dead: fence FIRST in the registry (the
                # epoch race makes this exactly-once across routers),
                # then locally so its streams replay now
                self.registry.fence(name)
                if name in roster:
                    self._leave(name, resigned=False)
                continue
            live += 1
            state = str(view.meta.get("state", "ready"))
            if name not in roster:
                eng = self._engine_factory(name, view.meta)
                try:
                    # placements carry the fleet's election epoch as a
                    # fencing header once router HA activates; reading
                    # it live (not captured) tracks takeover/demotion
                    eng.router_epoch_fn = (
                        lambda: getattr(self.fleet, "router_epoch", None)
                    )
                except Exception:
                    pass  # duck-typed factory engine without the attr
                tier = str(view.meta.get("tier", "mixed") or "mixed")
                try:
                    fleet._add_replica(name, eng, tier=tier)
                except ValueError:
                    # raced another sync pass, or the member advertises
                    # a tier label this router does not know — join it
                    # untiered rather than strand its capacity
                    if name not in fleet.replica_names:
                        try:
                            fleet._add_replica(name, eng)
                        except ValueError:
                            continue
                if state != "ready":
                    fleet.drain_replica(name)
                continue
            try:
                # a member may re-role between heartbeats (operator
                # re-shaping the tiers); apply it like any other
                # metadata transition
                fleet.set_replica_tier(
                    name, str(view.meta.get("tier", "mixed") or "mixed")
                )
            except (KeyError, ValueError):
                pass
            rep_state = fleet.replica_state(name)
            if state == "draining" and rep_state == "active":
                fleet.drain_replica(name)
            elif state == "ready" and rep_state == "draining":
                # the member finished its drain cycle (e.g. SIGTERM
                # canceled, or an external orchestrator re-admitted
                # it): re-admit probe-gated, off the router tick — a
                # probe generation must not stall the failover drain
                if name not in self._admitting:
                    self._admitting.add(name)
                    threading.Thread(
                        target=self._admit_worker, args=(name,),
                        daemon=True,
                    ).start()
        # members the registry no longer lists at all (lease files
        # unlinked by a clean release) leave the roster too
        for name in roster - seen:
            self._leave(name, resigned=True)
        _m_members.set(float(live))

    def _admit_worker(self, name: str) -> None:
        try:
            self.fleet.admit_replica(name, probe=True)
        except Exception:
            logger.warning(
                "membership: re-admission of %s failed", name,
                exc_info=True,
            )
        finally:
            self._admitting.discard(name)

    def _leave(self, name: str, resigned: bool) -> None:
        try:
            rep = self.fleet._replica(name)
        except KeyError:
            return
        if not resigned:
            # death: drain the local relays so their streams hit the
            # failover queue before the replica object disappears
            self.fleet._fence(
                rep,
                EngineUnhealthyError(
                    f"member {name} fenced (lease expired or tombstoned)"
                ),
            )
        self.fleet._remove_replica(name)
        _flight.record(
            "membership", "leave", member=name, resigned=resigned,
        )


def connect_fleet(
    path: str,
    *,
    worker_id: Optional[str] = None,
    ttl_s: Optional[float] = None,
    sync_interval_s: float = 0.5,
    engine_factory: Optional[Callable[[str, dict], Any]] = None,
    **fleet_kwargs,
) -> Fleet:
    """Build a router over the member registry at ``path``: a
    :class:`~.fleet.Fleet` in remote-replica mode whose roster tracks
    the registry — members join as they register, expired heartbeats
    fence them (streams replay to survivors), tombstones leave.

    The returned fleet starts empty (members appear on the first
    watchdog tick after :meth:`~.fleet.Fleet.start`) and carries two
    extra attributes: ``registry`` (the router's
    :class:`MemberRegistry` view) and ``membership_sync`` (the tick
    hook, for tests to drive synchronously). ``auto_restart`` defaults
    OFF in this mode: a dead PROCESS cannot be restarted from here —
    member supervision belongs to the platform; a member that comes
    back re-registers and re-joins."""
    registry = MemberRegistry(
        path, worker_id=worker_id, ttl_s=ttl_s
    )
    fleet_kwargs.setdefault("auto_restart", False)
    fleet = Fleet(engines=[], **fleet_kwargs)
    sync = _MemberSync(
        fleet, registry,
        interval_s=sync_interval_s,
        engine_factory=engine_factory,
    )
    fleet._tick_hooks.append(sync)
    fleet.registry = registry
    fleet.membership_sync = sync
    return fleet


# -- rolling restart / weight swap -----------------------------------------


def _is_remote(engine) -> bool:
    return isinstance(engine, RemoteEngine)


def _drain_member(fleet: Fleet, name: str, drain_timeout_s: float) -> None:
    """Drain one member end to end: admission stops at the member's
    ingress (remote) and at the router, then in-flight streams get
    ``drain_timeout_s`` to finish; leftovers MIGRATE — the replica is
    fenced so its streams replay to survivors recompute-style."""
    rep = fleet._replica(name)
    if _is_remote(rep.engine):
        status, body = rep.engine.lifecycle("drain")
        if status != 200:
            raise RuntimeError(
                f"member {name} refused drain: HTTP {status}: "
                f"{body.get('error')}"
            )
    fleet.drain_replica(name)
    deadline = time.monotonic() + drain_timeout_s
    while time.monotonic() < deadline:
        h = rep.engine.health()
        if not h["queue_depth"] and not h["active_slots"]:
            return
        time.sleep(0.02)
    logger.warning(
        "membership: member %s drain timed out after %.1fs; migrating "
        "its in-flight streams to survivors", name, drain_timeout_s,
    )
    fleet._fence(
        rep,
        EngineUnhealthyError(
            f"member {name} drained past its timeout; streams migrate"
        ),
    )


def _admit_member(fleet: Fleet, name: str, probe: bool) -> bool:
    rep = fleet._replica(name)
    if _is_remote(rep.engine):
        status, body = rep.engine.lifecycle("admit")
        if status != 200:
            logger.warning(
                "membership: member %s refused admit: HTTP %s: %s",
                name, status, body.get("error"),
            )
            return False
        rep.engine.healthy = True
    return fleet.admit_replica(name, probe=probe)


def rolling_restart(
    fleet: Fleet,
    members: Optional[List[str]] = None,
    *,
    drain_timeout_s: float = 30.0,
) -> Dict[str, Any]:
    """Restart the fleet one member at a time with zero downtime: for
    each member, drain (admission stops; in-flight streams finish, or
    migrate to survivors past the timeout), restart the engine, then a
    **probe generation must pass** before re-admission. A member whose
    restart or probe fails halts the rollout (it stays out of
    placement; the rest of the fleet keeps serving) — re-run after
    fixing it. Returns ``{"outcome", "restarted", "failed"}``."""
    names = list(members if members is not None else fleet.replica_names)
    restarted: List[str] = []
    for name in names:
        rep = fleet._replica(name)
        try:
            _drain_member(fleet, name, drain_timeout_s)
            if _is_remote(rep.engine):
                rep.engine.restart()
            else:
                rep.engine.restart()
            ok = _admit_member(fleet, name, probe=True)
        except Exception as e:
            logger.warning(
                "membership: rolling restart halted at %s: %s",
                name, e, exc_info=True,
            )
            ok = False
        if not ok:
            _m_rollouts.inc(outcome="halted")
            _flight.record(
                "membership", "rollout",
                op="restart", outcome="halted", member=name,
            )
            return {
                "outcome": "halted",
                "restarted": restarted,
                "failed": name,
            }
        restarted.append(name)
    _m_rollouts.inc(outcome="ok")
    _flight.record(
        "membership", "rollout", op="restart", outcome="ok",
        members=len(restarted),
    )
    return {"outcome": "ok", "restarted": restarted, "failed": None}


def rolling_weight_swap(
    fleet: Fleet,
    checkpoint: str,
    *,
    drain_timeout_s: float = 30.0,
) -> Dict[str, Any]:
    """Hot-swap a new checkpoint across the fleet with zero downtime,
    one member at a time: drain → ``swap_weights`` (device_put +
    pointer flip; zero recompiles) → **probe generation** → re-admit.
    A probe failure on any member ROLLS BACK — that member and every
    member already swapped return to the old weights (mixed weights
    across replicas would break failover byte-identity) — and the
    rollout halts. Returns ``{"outcome", "swapped", "failed"}``;
    ``fleet.rollouts_total{outcome}`` counts it."""
    names = list(fleet.replica_names)
    swapped: List[str] = []
    stash: Dict[str, Any] = {}

    def swap_one(name: str) -> None:
        rep = fleet._replica(name)
        if _is_remote(rep.engine):
            status, body = rep.engine.lifecycle(
                "swap", checkpoint=str(checkpoint)
            )
            if status != 200:
                raise RuntimeError(
                    f"member {name} refused swap: HTTP {status}: "
                    f"{body.get('error')}"
                )
        else:
            stash[name] = rep.engine.swap_weights(load_params(checkpoint))

    def rollback_one(name: str) -> None:
        rep = fleet._replica(name)
        if _is_remote(rep.engine):
            status, body = rep.engine.lifecycle("rollback")
            if status != 200:
                # a member that cannot PROVE it restored the old weights
                # must stay out of placement — re-admitting it could mix
                # weights across replicas and break failover identity
                raise RuntimeError(
                    f"member {name} rollback failed: HTTP {status}: "
                    f"{body.get('error')}"
                )
        elif name in stash:
            rep.engine.swap_weights(stash.pop(name))

    for name in names:
        try:
            _drain_member(fleet, name, drain_timeout_s)
            swap_one(name)
            ok = fleet.probe_replica(name)
        except Exception as e:
            logger.warning(
                "membership: weight swap failed on %s: %s", name, e,
                exc_info=True,
            )
            ok = False
        if ok:
            ok = _admit_member(fleet, name, probe=False)
        if not ok:
            # roll the WHOLE rollout back: this member first, then
            # every member already carrying the new weights
            logger.warning(
                "membership: weight swap probe failed on %s; rolling "
                "back %d member(s) and halting the rollout",
                name, len(swapped) + 1,
            )
            for victim in [name] + list(reversed(swapped)):
                try:
                    if victim != name:
                        _drain_member(fleet, victim, drain_timeout_s)
                    rollback_one(victim)
                    _admit_member(fleet, victim, probe=True)
                except Exception:
                    logger.warning(
                        "membership: rollback of %s failed; it stays "
                        "out of placement", victim, exc_info=True,
                    )
            _m_rollouts.inc(outcome="rolled_back")
            _flight.record(
                "membership", "rollout",
                op="swap", outcome="rolled_back", member=name,
            )
            return {
                "outcome": "rolled_back",
                "swapped": [],
                "failed": name,
            }
        swapped.append(name)
    # the WHOLE rollout succeeded: tell every member to drop its
    # rollback stash (best-effort — an unreachable member just keeps a
    # harmless pre-rollout stash until its next rollout)
    for name in swapped:
        try:
            rep = fleet._replica(name)
            if _is_remote(rep.engine):
                rep.engine.lifecycle("commit")
            else:
                stash.pop(name, None)
        except Exception:
            logger.warning(
                "membership: commit of %s failed (stash lingers)",
                name, exc_info=True,
            )
    _m_rollouts.inc(outcome="ok")
    _flight.record(
        "membership", "rollout", op="swap", outcome="ok",
        members=len(swapped),
    )
    return {"outcome": "ok", "swapped": swapped, "failed": None}


# -- autoscaling -----------------------------------------------------------


class Autoscaler:
    """Scale decisions from the PR-12 signals, actuation injected.

    Watches three pressure signals — aggregate queue depth, KV pages in
    use (as a fraction of capacity), and the inter-token p99 from the
    time-series store (``serve.inter_token_seconds.p99``) — and calls
    the injected ``scale_up()`` / ``scale_down()`` callbacks (spawn a
    member process / drain one; the platform owns HOW). Guard rails:
    ``min_members``/``max_members`` bounds on the current roster size
    and a ``cooldown_s`` between actions so one burst cannot flap the
    fleet. ``signals_fn`` overrides the signal read for tests.

    Attach to a router with :meth:`attach` (it evaluates on the fleet's
    watchdog tick) or call :meth:`evaluate` from your own loop."""

    def __init__(
        self,
        fleet: Fleet,
        *,
        scale_up: Callable[[], Any],
        scale_down: Callable[[], Any],
        min_members: int = 1,
        max_members: int = 8,
        queue_high: int = 8,
        pages_frac_high: float = 0.85,
        itl_p99_high_s: float = 1.0,
        queue_low: int = 0,
        pages_frac_low: float = 0.25,
        cooldown_s: float = 30.0,
        signals_fn: Optional[Callable[[], Dict[str, float]]] = None,
    ):
        self.fleet = fleet
        self.scale_up = scale_up
        self.scale_down = scale_down
        self.min_members = int(min_members)
        self.max_members = int(max_members)
        self.queue_high = int(queue_high)
        self.pages_frac_high = float(pages_frac_high)
        self.itl_p99_high_s = float(itl_p99_high_s)
        self.queue_low = int(queue_low)
        self.pages_frac_low = float(pages_frac_low)
        self.cooldown_s = float(cooldown_s)
        self._signals_fn = signals_fn
        self._last_action_t: float = -float("inf")
        self.decisions: List[Tuple[float, str, Dict[str, float]]] = []

    def signals(self) -> Dict[str, float]:
        """The current pressure read: fleet aggregates for queue/pages
        (synchronous truth) + the time-series store's inter-token p99
        (windowed; ``0.0`` while no samples exist)."""
        if self._signals_fn is not None:
            return dict(self._signals_fn())
        h = self.fleet.health()
        cap = float(h.get("pages_capacity") or 0)
        p99 = 0.0
        try:
            from ..obs import timeseries as _ts

            pt = _ts.store().latest("serve.inter_token_seconds.p99")
            if pt is not None:
                p99 = float(pt[1])
        except Exception:
            p99 = 0.0
        return {
            "queue_depth": float(h.get("queue_depth") or 0),
            "pages_frac": (
                float(h.get("pages_in_use") or 0) / cap if cap else 0.0
            ),
            "itl_p99_s": p99,
            "members": float(len(self.fleet.replica_names)),
        }

    def evaluate(self, now: Optional[float] = None) -> Optional[str]:
        """One scaling decision: ``"up"``, ``"down"``, or ``None``.
        Scale-up wins ties (pressure beats thrift); both respect the
        member bounds and the cooldown."""
        now = time.monotonic() if now is None else now
        if now - self._last_action_t < self.cooldown_s:
            return None
        s = self.signals()
        n = int(s.get("members", len(self.fleet.replica_names)))
        decision: Optional[str] = None
        if n < self.max_members and (
            s["queue_depth"] > self.queue_high
            or s["pages_frac"] > self.pages_frac_high
            or s["itl_p99_s"] > self.itl_p99_high_s
        ):
            decision = "up"
        elif n > self.min_members and (
            s["queue_depth"] <= self.queue_low
            and s["pages_frac"] < self.pages_frac_low
            and s["itl_p99_s"] < self.itl_p99_high_s / 2.0
        ):
            decision = "down"
        if decision is None:
            return None
        self._last_action_t = now
        self.decisions.append((now, decision, s))
        _m_scale_decisions.inc(direction=decision)
        _flight.record(
            "membership", "scale", direction=decision, **{
                k: round(v, 4) for k, v in s.items()
            },
        )
        logger.warning(
            "membership: autoscaler decided %s (queue=%.0f "
            "pages_frac=%.2f itl_p99=%.3fs members=%d)",
            decision, s["queue_depth"], s["pages_frac"],
            s["itl_p99_s"], n,
        )
        try:
            (self.scale_up if decision == "up" else self.scale_down)()
        except Exception:
            logger.warning(
                "membership: scale_%s callback failed", decision,
                exc_info=True,
            )
        return decision

    def attach(self, interval_s: float = 1.0) -> "Autoscaler":
        """Evaluate on the fleet's watchdog tick, rate-limited to
        ``interval_s``."""
        state = {"t": 0.0}

        def tick() -> None:
            now = time.monotonic()
            if now - state["t"] < interval_s:
                return
            state["t"] = now
            self.evaluate(now)

        self.fleet._tick_hooks.append(tick)
        return self


class LocalProcessProvisioner:
    """A REAL actuator behind :class:`Autoscaler`'s ``scale_up`` /
    ``scale_down`` callbacks: spawn and retire :class:`MemberAgent`
    subprocesses on this host (the single-host closing of ROADMAP item
    3's "real provisioner" remainder; a cloud provisioner swaps in the
    same two callbacks).

    ``script`` is the member's ``python -c`` source; it is launched as
    ``python -c <script> <registry_path> <member_name> [*extra_args]``
    and is expected to build an engine, construct a
    :class:`MemberAgent` on the shared ``path``, call
    :meth:`MemberAgent.install_sigterm`, start, and serve until
    signaled — retirement is a SIGTERM, so the member drains
    gracefully (stop admission, finish in-flight streams, resign the
    lease) rather than being fenced as a death.

    Bounded by ``max_procs`` (scale-up past it is a logged no-op —
    the autoscaler's own ``max_members``/``cooldown_s`` guard rails
    stay in charge of WHEN); scale-down only ever retires processes
    THIS provisioner spawned, newest first, so externally-managed
    members are untouchable from here.

    The rule on accelerators is ONE MEMBER PROCESS PER CHIP: a process
    that initializes jax takes every chip it can see, and a second
    process that needs one then fails or hangs at start-up. So the
    process that owns this provisioner (the router) must hold no engine
    and initialize no backend — scale-up is refused with a logged
    reason if it has — each member's ``env`` must narrow it to its own
    chip, and ``max_procs`` must not exceed the chips of the host. On a
    one-chip host there is nothing to provision: run in-process
    :class:`~tensorframes_tpu.serve.Fleet` replicas instead. Members
    inherit this process's stdout/stderr, so a member that dies at
    start-up says why."""

    def __init__(
        self,
        path: str,
        script: str,
        *,
        python: Optional[str] = None,
        base_name: str = "auto",
        max_procs: int = 8,
        extra_args: Tuple[str, ...] = (),
        env: Optional[Dict[str, str]] = None,
        term_grace_s: float = 10.0,
    ):
        self.path = str(path)
        self.script = script
        self.python = python or sys.executable
        self.base_name = str(base_name)
        self.max_procs = int(max_procs)
        self.extra_args = tuple(str(a) for a in extra_args)
        self.env = dict(env) if env is not None else None
        self.term_grace_s = float(term_grace_s)
        self._procs: "Dict[str, subprocess.Popen]" = {}
        self._order: List[str] = []  # spawn order; retire newest first
        self._seq = 0
        self._lock = threading.Lock()

    def reap(self) -> List[str]:
        """Forget exited processes; returns the names reaped."""
        gone = []
        with self._lock:
            for name, proc in list(self._procs.items()):
                if proc.poll() is not None:
                    gone.append(name)
                    del self._procs[name]
                    self._order.remove(name)
        return gone

    @property
    def alive(self) -> int:
        self.reap()
        with self._lock:
            return len(self._procs)

    def names(self) -> List[str]:
        self.reap()
        with self._lock:
            return list(self._order)

    def scale_up(self) -> Optional[str]:
        """Spawn one member subprocess; returns its name, or ``None``
        at the ``max_procs`` bound."""
        self.reap()
        from ..obs.programs import initialized_device

        dev = initialized_device()
        if dev is not None and dev.platform != "cpu":
            logger.warning(
                "provisioner: scale_up refused — this process has "
                "initialized the %s backend and holds its chips, so a "
                "member process could not start on one", dev.platform,
            )
            return None
        with self._lock:
            if len(self._procs) >= self.max_procs:
                logger.warning(
                    "provisioner: scale_up refused at the max_procs "
                    "bound (%d)", self.max_procs,
                )
                return None
            self._seq += 1
            name = f"{self.base_name}-{self._seq}"
        env = None
        if self.env is not None:
            env = dict(os.environ)
            env.update(self.env)
        proc = subprocess.Popen(
            [self.python, "-c", self.script, self.path, name,
             *self.extra_args],
            env=env,
        )
        with self._lock:
            self._procs[name] = proc
            self._order.append(name)
        _flight.record(
            "membership", "provision", member=name, pid=proc.pid,
        )
        logger.warning(
            "provisioner: spawned member %s (pid %d)", name, proc.pid,
        )
        return name

    def scale_down(self) -> Optional[str]:
        """SIGTERM the newest member this provisioner owns (graceful
        drain + resign via :meth:`MemberAgent.install_sigterm`);
        returns its name, or ``None`` with nothing to retire."""
        self.reap()
        with self._lock:
            if not self._order:
                return None
            name = self._order[-1]
            proc = self._procs[name]
        try:
            proc.send_signal(signal.SIGTERM)
        except OSError:
            pass  # exited under us; the next reap forgets it
        _flight.record("membership", "retire", member=name, pid=proc.pid)
        logger.warning(
            "provisioner: retiring member %s (pid %d, SIGTERM)",
            name, proc.pid,
        )
        return name

    def autoscaler(self, fleet: Fleet, **kw: Any) -> Autoscaler:
        """Convenience: an :class:`Autoscaler` with this provisioner's
        callbacks bound (``max_members`` defaults to ``max_procs``)."""
        kw.setdefault("max_members", self.max_procs)
        return Autoscaler(
            fleet, scale_up=self.scale_up, scale_down=self.scale_down,
            **kw,
        )

    def stop(self) -> None:
        """Retire everything: SIGTERM all, wait out the grace period,
        SIGKILL leftovers."""
        with self._lock:
            procs = list(self._procs.values())
        for proc in procs:
            if proc.poll() is None:
                try:
                    proc.send_signal(signal.SIGTERM)
                except OSError:
                    pass
        deadline = time.monotonic() + self.term_grace_s
        for proc in procs:
            rem = deadline - time.monotonic()
            try:
                proc.wait(timeout=max(0.0, rem))
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=5.0)
        self.reap()
