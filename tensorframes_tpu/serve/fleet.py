"""Serving fleet: replicated engines behind a health-gated router.

Everything below ``serve/`` so far protects exactly ONE
:class:`~.engine.GenerationEngine`: the supervisor retries/degrades/
restarts it, but a terminal engine failure still fails every in-flight
request, and max throughput is one chip. The fleet is the next tier —
the deployment shape TPU serving work assumes (Ragged Paged Attention,
PAPERS.md): **N identical paged-KV engines behind one placement layer**,
where a replica death becomes a retried request, not an outage.

- :class:`Fleet` owns N replicas (same model/config, independent
  :class:`~.kv_pages.PagePool`\\ s) plus the router. :meth:`Fleet.submit`
  places each request on a healthy replica by **least-loaded** order
  (most free KV pages, then shallowest admission queue) with optional
  **session affinity** (``session=`` pins a chat/tenant to one replica's
  KV locality while it stays healthy).
- **Health gating** reuses the PR-3 supervisor machinery per replica: a
  watchdog thread polls ``engine.health()``; an unhealthy or wedged
  replica is **fenced** (no new placements), drained (every attached
  handle fails now, so its survivors replay immediately), ``restart()``\\ ed
  in the background, and re-admitted only after a **probe generation**
  (one token through prefill AND decode) succeeds.
- **Request replay** is the robustness core: the router records each
  request's prompt/params and forwards tokens through a relay, so when a
  replica dies mid-stream the survivors resubmit to a healthy replica
  *recompute-style* — already-emitted tokens fold into the prompt and
  the budget shrinks, the same trick the scheduler's preemption uses.
  Client streams never replay or lose tokens, and stay **byte-identical**
  to a solo decode for greedy and seeded-sampling requests alike
  (per-step sampling keys fold at absolute positions, so the replayed
  continuation draws the same tokens the dead replica would have).

What does NOT replay: :class:`DeadlineExceededError` (the budget already
passed) and submit-time ``ValueError`` rejections (every replica is
identical, so an infeasible request is infeasible everywhere). Replays
are capped at ``max_replays`` per request so one poison request that
deterministically kills its replica cannot churn the whole fleet
forever. Static shapes mean failover adds **zero compiled programs**:
every replica keeps its own ≤ 3 step programs for the fleet's lifetime
(≤ 5 with speculative decoding's draft + verify). Speculation composes
with replay unchanged: the relay only ever carries ACCEPTED target
tokens, so a failover folds them into the prompt exactly as today —
and replicas of DIFFERENT draft length k (or none at all) stay
byte-identical, since every k emits the target's own sampled tokens.

Chaos sites (``utils/chaos.py``): ``fleet.place`` sits in the placement
path (a ``transient`` there retries invisibly); ``fleet.replica_fault``
is polled once per replica per watchdog tick and **kills the replica
whose poll fired** — append the replica name to target one
(``fleet.replica_fault.r1=fatal:every=8`` kills ``r1`` on the 8th tick).

``interop/serving.py`` accepts ``engine=Fleet`` unchanged: ``POST
/generate`` places through the router, ``GET /healthz`` aggregates
(200 while ANY replica serves; per-replica detail in the body), and
503-shedding starts only when ALL replicas are fenced. Metrics:
``fleet.replicas_healthy``, ``fleet.failovers_total``,
``fleet.replays_total``, and per-replica pages/queue gauges with a
``replica`` label (``docs/observability.md``). Sizing guidance and the
failover cookbook: ``docs/serving_llm.md`` + ``docs/fault_tolerance.md``.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import (
    current_trace as _current_trace,
    event as _trace_event,
    flight as _flight,
    use_trace as _use_trace,
)
from ..obs.metrics import counter as _counter, gauge as _gauge
from ..utils import chaos as _chaos
from ..utils.config import get_config
from ..utils.failures import (
    DeadlineExceededError,
    StaleLeaseError,
    TenantThrottledError,
    first_line as _first_line,
    run_with_retries,
)
from ..utils.logging import get_logger
from . import tenancy as _tenancy
from . import tiers as _tiers
from .engine import EngineUnhealthyError, GenerationEngine
from .scheduler import GenerationHandle, QueueFullError
from .tiers import TIERS, TierMigrationError

__all__ = ["Fleet", "FleetHandle"]

logger = get_logger("serve.fleet")

_m_replicas_healthy = _gauge(
    "fleet.replicas_healthy",
    "Replicas currently accepting placements (active and healthy)",
)
_m_failovers = _counter(
    "fleet.failovers_total",
    "Replicas fenced by the router (death, failed health, or wedge): "
    "the replica was gated out and drained; any survivors it carried "
    "replay elsewhere (fleet.replays_total counts those)",
)
_m_replays = _counter(
    "fleet.replays_total",
    "Requests resubmitted to another replica after a replica death "
    "(recompute-style: emitted tokens folded into the prompt)",
)
_m_rep_pages = _gauge(
    "fleet.replica_pages_in_use",
    "KV pages owned by live sequences, per replica",
    labels=("replica",),
)
_m_rep_queue = _gauge(
    "fleet.replica_queue_depth",
    "Admission-queue depth, per replica",
    labels=("replica",),
)
_m_placements = _counter(
    "fleet.placements_total",
    "Requests placed by the router, by chosen replica",
    labels=("replica",),
)
_m_tier_replicas = _gauge(
    "fleet.tier_replicas_active",
    "Replicas currently accepting placements, by tier role "
    "(prefill / decode / mixed — see serve/tiers.py)",
    labels=("tier",),
)

#: session-affinity map bound: beyond this many distinct sessions the
#: oldest mapping is forgotten (affinity is an optimization, not a
#: correctness property — a forgotten session just re-places least-loaded)
_MAX_SESSIONS = 4096


class FleetHandle(GenerationHandle):
    """The caller's end of one FLEET request: the same streaming surface
    as :class:`~.scheduler.GenerationHandle` (iterate for tokens,
    :meth:`result` for the full generation), fed by the router's relay —
    tokens keep flowing across replica failovers, and the stream is
    byte-identical to a solo decode whether zero or several replicas
    died underneath it."""

    def _finish(self, error: Optional[BaseException] = None) -> None:
        # idempotent: a late engine-side close (e.g. fleet stop racing a
        # replica's own shutdown sweep) must not clobber the first result
        if self._done.is_set():
            return
        super()._finish(error)


class _FleetRequest:
    """The router's replay record for one request: everything needed to
    resubmit it recompute-style, plus the live relay identity."""

    __slots__ = (
        "request_id", "prompt", "max_new_tokens", "temperature", "top_p",
        "seed", "eos_id", "deadline_t", "session", "handle", "replica",
        "inner", "replays", "last_error", "lock", "parked_t", "trace",
        "tenant",
    )

    def __init__(
        self,
        request_id: int,
        prompt: np.ndarray,
        max_new_tokens: int,
        temperature: float,
        top_p: float,
        seed: int,
        eos_id: Optional[int],
        deadline_t: Optional[float],
        session: Optional[str],
        handle: FleetHandle,
        tenant: str = "",
    ):
        self.request_id = request_id
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.top_p = top_p
        self.seed = seed
        self.eos_id = eos_id
        self.deadline_t = deadline_t
        self.session = session
        self.handle = handle
        self.tenant = tenant
        self.replica: Optional["_Replica"] = None
        self.inner: Optional["_RelayHandle"] = None
        self.replays = 0
        self.last_error: Optional[BaseException] = None
        #: serializes the relay identity gate against detach+snapshot in
        #: ``_submit_to`` — without it, a wedged replica's thread could
        #: pass the gate, stall, and forward its token AFTER the replay
        #: snapshot (a duplicated position on the client stream)
        self.lock = threading.Lock()
        #: monotonic time this record entered the failover queue (reset
        #: each death); bounds how long a survivor may wait for a
        #: healthy replica before failing fail-fast-style
        self.parked_t: Optional[float] = None
        #: the request's TraceContext: one trace_id across EVERY replica
        #: that serves it — each replay adds a ``fleet.replay`` event
        #: with a ``replay=N`` attribute to the same trace
        self.trace = None


class _RelayHandle(GenerationHandle):
    """The engine-side handle the router submits on a request's behalf:
    emissions forward to the caller's :class:`FleetHandle`, and the
    terminal close reports back to the fleet so a replica death turns
    into a replay instead of a failed stream. Forwarding is gated on
    relay IDENTITY (``rec.inner is self``) so a stale relay — a wedged
    replica waking up after its request was already replayed — cannot
    corrupt the stream with duplicate tokens or a stale close."""

    def __init__(self, request_id: int, fleet: "Fleet", rec: _FleetRequest):
        super().__init__(request_id)
        self._fleet = fleet
        self._rec = rec
        # the engine writes its timing breakdown to the handle IT holds
        # (this relay); sharing the dict object makes those writes land
        # on the caller's FleetHandle — and accumulate across replays
        self.timings = rec.handle.timings
        with rec.lock:
            rec.inner = self

    def _emit(self, token: int) -> None:
        super()._emit(token)
        # gate check and forward under the record lock: a bare
        # check-then-forward could pass the gate, stall, and deliver
        # after a replay snapshot — the duplicated-position corruption
        # the gate exists to prevent
        first = len(self._tokens) == 1
        with self._rec.lock:
            if self._rec.inner is self:
                self._rec.handle._emit(token)
            else:
                first = False
        if first and not self._done.is_set():
            # first live token from THIS relay: on a prefill-tier
            # replica that is the handoff point — prefill work is done,
            # every decode step from here on belongs on the decode
            # tier. Enqueue only; the router tick does the migration
            # (this runs on the engine's stepping thread, step lock
            # held — it must stay O(1)).
            self._fleet._maybe_handoff(self._rec)

    def _finish(self, error: Optional[BaseException] = None) -> None:
        super()._finish(error)
        self._fleet._on_inner_finish(self._rec, self, error)


class _StreamComplete(Exception):
    """Raised by ``_submit_to`` when the locked snapshot shows the
    stream already delivered its whole budget (or its EOS): there is
    nothing left to resubmit — the caller settles the handle as
    SUCCESS. Internal control flow, never caller-visible."""


class _Replica:
    """One engine plus its gate state. ``active`` replicas accept
    placements; ``fenced`` ones are draining/restarting; ``draining``
    ones finish their in-flight streams but take no NEW placements (the
    rolling-restart / graceful-shutdown gate — an administrative state,
    not a failure). ``wedged`` marks a fence whose stepping thread never
    exited (a stuck device call) — auto-restart skips those, since
    ``restart()`` would block on the lock the wedged step still holds;
    recycle the process."""

    __slots__ = (
        "name", "engine", "state", "wedged", "restarting", "lock", "tier",
    )

    def __init__(self, name: str, engine: GenerationEngine, tier: str = "mixed"):
        self.name = name
        self.engine = engine
        self.state = "active"
        self.wedged = False
        self.restarting = False
        self.lock = threading.Lock()
        #: placement role (``serve/tiers.py``): ``prefill`` replicas take
        #: new requests and hand streams off at first token; ``decode``
        #: replicas receive migrated streams; ``mixed`` (the default) does
        #: both — a fleet of all-mixed replicas behaves exactly as before
        #: tiering existed.
        self.tier = tier


class Fleet:
    """N :class:`GenerationEngine` replicas behind one admission router.

    >>> fleet = Fleet(lm, replicas=3, max_slots=8, page_size=16)
    >>> with fleet:                      # engines + watchdog threads
    ...     h = fleet.submit(prompt_ids, max_new_tokens=64, session="u1")
    ...     for tok in h: ...            # survives replica deaths
    >>> fleet.generate([p1, p2], 32)     # convenience, like the engine's

    Engine-construction keywords (``max_slots``, ``page_size``,
    ``num_pages``, ``max_seq_len``, ``queue_capacity``, ``top_k``,
    ``eos_id``, ``moe_top_k``) apply to every replica — identical
    replicas are what make replay byte-identical. Replica *i* lives on
    local chip *i mod n* (weights, KV pool, programs), so a four-chip
    host runs four replicas on four chips. Fleet knobs:

    - ``watchdog_interval_s`` — health-poll + failover-drain cadence;
    - ``wedge_timeout_s`` — last-step watchdog age (with work pending)
      past which a live-but-stuck replica is fenced (a step that
      compiles its program is exempt: ``health()["compiling"]``);
    - ``probe_timeout_s`` — how long a restarted replica's probe
      generation may take before re-admission is abandoned (retried on
      a later poll);
    - ``max_replays`` — per-request failover budget (a poison request
      that deterministically kills replicas is failed, not bounced
      forever);
    - ``failover_timeout_s`` — how long a survivor of a replica death
      may wait parked for a healthy replica (every replica fenced,
      restarts failing) before its handle fails with the replica's
      error — the fleet's version of the fail-fast rule that a doomed
      stream's consumer must never hang to its own timeout;
    - ``auto_restart`` — False leaves fenced replicas down until a
      caller restarts + probes them (``restart_replica()``);
    - ``replica_kwargs`` — per-replica engine-kwarg overrides (one dict
      per replica, merged over the shared kwargs). The tensor-parallel
      door: replicas of different TP degree (``mesh=...``) coexist
      behind one router, and failover replay ACROSS degrees stays
      byte-identical because every degree emits the same bytes
      (``serve/tp.py``);
    - ``engines`` — pre-built ``(name, engine)`` pairs instead of a
      model + construction kwargs: the elastic-membership door
      (``serve/membership.py``) where the router fronts remote-replica
      adapters it did not construct and the roster grows/shrinks at
      runtime as members register and resign;
    - ``tiers`` — one role label per replica (``prefill`` / ``decode``
      / ``mixed``): the disaggregated-serving door (``serve/tiers.py``).
      New requests place on prefill-capable replicas and migrate to the
      decode tier at first token via live KV-page handoff; all-``mixed``
      (the default) is the monolithic fleet, byte-for-byte.
    """

    def __init__(
        self,
        model=None,
        *,
        replicas: int = 2,
        watchdog_interval_s: float = 0.05,
        wedge_timeout_s: float = 30.0,
        probe_timeout_s: float = 30.0,
        max_replays: int = 8,
        failover_timeout_s: float = 60.0,
        auto_restart: bool = True,
        replica_kwargs: Optional[Sequence[Dict]] = None,
        engines: Optional[Sequence[Tuple[str, object]]] = None,
        tiers: Optional[Sequence[str]] = None,
        **engine_kwargs,
    ):
        if engines is not None:
            # pre-built engine injection — the elastic-membership door
            # (serve/membership.py): the router fronts engines it did
            # NOT construct (remote-replica adapters, an empty roster
            # that fills as members register). Construction kwargs are
            # meaningless here, so mixing the modes is a caller bug.
            if model is not None or replica_kwargs is not None or engine_kwargs:
                raise ValueError(
                    "engines= is mutually exclusive with model/"
                    "replica_kwargs/engine construction kwargs — the "
                    "injected engines are already built"
                )
            names = [str(n) for n, _ in engines]
            if len(set(names)) != len(names):
                raise ValueError(f"duplicate replica names in engines=: {names}")
        elif model is None:
            raise ValueError("need a model (or pre-built engines=)")
        elif replicas < 1:
            raise ValueError(f"need replicas >= 1; got {replicas}")
        if replica_kwargs is not None:
            if len(replica_kwargs) != replicas:
                raise ValueError(
                    f"replica_kwargs has {len(replica_kwargs)} entries "
                    f"for {replicas} replicas — one override dict per "
                    f"replica"
                )
            for i, kw in enumerate(replica_kwargs):
                reserved = {"name", "model"} & set(kw)
                if reserved:
                    # replica names are fleet-owned (the cost registry
                    # and /statusz key on them) and the model is the
                    # positional argument — a collision would surface
                    # as an opaque TypeError from engine construction
                    raise ValueError(
                        f"replica_kwargs[{i}] overrides fleet-owned "
                        f"key(s) {sorted(reserved)}; replica names are "
                        f"assigned by the fleet and the model is shared"
                    )
        # replica names flow into each engine so the per-program cost
        # registry (obs/programs.py) and /statusz attribute every step
        # program to its replica (serve.decode[r1], ...).
        #
        # ``replica_kwargs`` overlays PER-REPLICA engine kwargs on the
        # shared ones — the heterogeneous-fleet door: replicas of
        # DIFFERENT tensor-parallel degree (``mesh=...``) behind one
        # router. Byte-identity makes that safe: every TP degree emits
        # the same bytes for the same request (serve/tp.py), so failover
        # replay across degrees stays invisible to the stream exactly
        # like same-shape failover. Overrides that change emitted
        # streams (the model, top_k, eos_id) are the caller's contract
        # to keep identical, as ever.
        #
        # ``self._replicas`` is rebound copy-on-write (never mutated in
        # place) so the router's lock-free sweeps iterate a consistent
        # snapshot while members join and leave (:meth:`_add_replica` /
        # :meth:`_remove_replica`).
        if engines is not None:
            self._replicas: List[_Replica] = [
                _Replica(str(name), eng) for name, eng in engines
            ]
        else:
            import jax
            from jax.sharding import Mesh

            # replica i lives on local chip i mod n — weights, KV pool
            # and step programs — so N replicas use N chips instead of
            # stacking on chip 0. A replica given its own ``mesh``
            # (tensor parallelism) already says where it lives.
            chips = jax.local_devices()
            self._replicas = []
            for i in range(int(replicas)):
                kw = {
                    "mesh": Mesh(
                        np.array([chips[i % len(chips)]]), ("tp",)
                    ),
                    **engine_kwargs,
                    **(replica_kwargs[i] if replica_kwargs else {}),
                }
                self._replicas.append(
                    _Replica(
                        f"r{i}",
                        GenerationEngine(model, name=f"r{i}", **kw),
                    )
                )
        if tiers is not None:
            # one tier label per replica, roster order — the
            # disaggregated-serving door (serve/tiers.py): ``prefill``
            # replicas take new requests and hand each stream off at
            # first token; ``decode`` replicas receive the migrated
            # streams. All-``mixed`` (the default) is the monolithic
            # fleet, byte-for-byte.
            if len(tiers) != len(self._replicas):
                raise ValueError(
                    f"tiers= has {len(tiers)} labels for "
                    f"{len(self._replicas)} replicas — one per replica"
                )
            for t in tiers:
                if t not in TIERS:
                    raise ValueError(
                        f"unknown tier {t!r}; expected one of {TIERS}"
                    )
            for rep, t in zip(self._replicas, tiers):
                rep.tier = str(t)
        self.watchdog_interval_s = float(watchdog_interval_s)
        self.wedge_timeout_s = float(wedge_timeout_s)
        self.probe_timeout_s = float(probe_timeout_s)
        self.max_replays = int(max_replays)
        self.failover_timeout_s = float(failover_timeout_s)
        self.auto_restart = bool(auto_restart)
        self._lock = threading.Lock()
        self._id_lock = threading.Lock()
        self._req_counter = 0
        self._inflight: Dict[int, _FleetRequest] = {}
        self._pending: Deque[_FleetRequest] = deque()
        #: first-token handoff queue (serve/tiers.py): records whose
        #: stream just produced its first token on a ``prefill``-tier
        #: replica, awaiting migration to a decode-capable replica on
        #: the next router tick. Drained by :meth:`_drain_migrations`.
        self._handoff: Deque[_FleetRequest] = deque()
        #: pool-pressure rebalance queue: ``(snapshot, rec, dst_name)``
        #: triples detached synchronously by the on_pressure hook (on
        #: the source engine's stepping thread) and imported
        #: asynchronously here — the split keeps the source step lock
        #: and the destination step lock from ever nesting.
        self._imports: Deque[Tuple[object, _FleetRequest, str]] = deque()
        #: session key -> (pinned replica, tenant) — the tenant rides
        #: along so the SLO actuator can drop one tenant's pins
        #: (:meth:`replace_tenant_sessions`) without scanning requests
        self._sessions: "OrderedDict[str, Tuple[_Replica, str]]" = (
            OrderedDict()
        )
        self._thread: Optional[threading.Thread] = None
        self._stop_evt = threading.Event()
        self._wake = threading.Event()
        self._closed = False
        #: callables run once per router tick (after health polling,
        #: before the failover drain) — the membership layer's sync
        #: point: registry scans, autoscaler evaluation. A hook that
        #: raises is logged and kept; it must not kill the watchdog.
        self._tick_hooks: List = []
        #: the router-election epoch this fleet places under (None =
        #: router HA not attached → no fencing header on remote
        #: placements, the pre-HA wire format). Set by
        #: ``serve/router_ha.py`` when this process wins the router
        #: lease; deliberately LEFT at the stale value after a lease
        #: loss so a zombie router's placements carry the superseded
        #: epoch and members reject them (StaleRouterEpochError).
        self.router_epoch: Optional[int] = None
        _m_replicas_healthy.set(float(len(self._replicas)))

    # -- introspection -----------------------------------------------------

    @property
    def engines(self) -> List[GenerationEngine]:
        """The replica engines, placement order (benches warm each one)."""
        return [rep.engine for rep in self._replicas]

    @property
    def replica_names(self) -> List[str]:
        return [rep.name for rep in self._replicas]

    def replica_state(self, name: str) -> str:
        return self._replica(name).state

    def _replica(self, name: str) -> _Replica:
        for rep in self._replicas:
            if rep.name == name:
                return rep
        raise KeyError(f"no replica named {name!r}")

    def program_counts(self) -> Dict[str, int]:
        """Compiled step programs per replica — the soak pins every value
        at <= 3 (<= 5 for speculative replicas); failover, fencing,
        restart, and probe are all shape-static."""
        return {
            rep.name: rep.engine.num_step_programs for rep in self._replicas
        }

    def health(self) -> Dict[str, object]:
        """Aggregate liveness for ``GET /healthz``: 200-worthy while ANY
        replica serves, with per-replica detail (each replica's engine
        snapshot plus its gate state) for operators and the soak."""
        reps: Dict[str, object] = {}
        healthy = 0
        queue_depth = active = pages = cap = 0
        for rep in self._replicas:
            h = rep.engine.health()
            h["state"] = rep.state
            h["wedged"] = rep.wedged
            h["tier"] = rep.tier
            reps[rep.name] = h
            if rep.state == "active" and h["healthy"]:
                healthy += 1
            queue_depth += h["queue_depth"]
            active += h["active_slots"]
            pages += h["pages_in_use"]
            cap += h["pages_capacity"]
        return {
            "healthy": healthy > 0,
            "replicas_total": len(self._replicas),
            "replicas_healthy": healthy,
            "queue_depth": queue_depth,
            "active_slots": active,
            "pages_in_use": pages,
            "pages_capacity": cap,
            "inflight_requests": len(self._inflight),
            "replicas": reps,
        }

    # -- placement ---------------------------------------------------------

    @staticmethod
    def _tenant_slots(rep: _Replica, tenant: str) -> int:
        """This tenant's live decode slots on one replica (lock-free
        sweep of the slot list — the same stale-tolerant read the
        pages_free/queue_depth placement keys already are)."""
        return sum(
            1
            for a in rep.engine.scheduler.slots
            if a is not None and a.req.tenant == tenant
        )

    def _candidates(
        self,
        session: Optional[str] = None,
        tenant: Optional[str] = None,
        role: str = "new",
    ) -> List[_Replica]:
        """Active, healthy replicas in placement-preference order:
        session-affine replica first (when mapped and still eligible),
        then least-loaded — most free KV pages, then shallowest queue,
        then name (a deterministic tiebreak). With the QoS plane on and
        a tenant named, replicas holding FEWER of that tenant's active
        slots come first (ahead of raw load): one tenant's flood piles
        onto the replicas it already occupies instead of spreading to
        monopolize every pool.

        ``role`` applies the tier preference (serve/tiers.py) as the
        LEADING sort key — a soft preference, never a filter, so a
        fleet whose preferred tier is entirely fenced degrades to
        placing on whatever is healthy rather than shedding:

        - ``"new"`` — fresh placements prefer ``prefill`` + ``mixed``
          replicas (prefill capacity is what new requests consume);
        - ``"decode"`` — migration targets prefer ``decode`` +
          ``mixed`` replicas.

        Raises :class:`EngineUnhealthyError` when every replica is
        fenced — the ALL-replicas-down shed the endpoint maps to 503."""
        _chaos.site("fleet.place")
        cands = [
            rep
            for rep in self._replicas
            if rep.state == "active"
            and rep.engine.healthy
            and not rep.engine._stop_wedged
        ]
        if not cands:
            raise EngineUnhealthyError(
                "all fleet replicas are fenced or unhealthy; the watchdog "
                "is restarting them — retry shortly"
            )
        preferred = (
            ("prefill", "mixed") if role == "new" else ("decode", "mixed")
        )

        def _tier_rank(rep: _Replica) -> int:
            return 0 if rep.tier in preferred else 1

        if tenant and _tenancy.enabled():
            cands.sort(
                key=lambda rep: (
                    _tier_rank(rep),
                    self._tenant_slots(rep, tenant),
                    -rep.engine.pool.pages_free,
                    rep.engine.scheduler.queue_depth,
                    rep.name,
                )
            )
        else:
            cands.sort(
                key=lambda rep: (
                    _tier_rank(rep),
                    -rep.engine.pool.pages_free,
                    rep.engine.scheduler.queue_depth,
                    rep.name,
                )
            )
        if session is not None:
            with self._lock:
                entry = self._sessions.get(session)
                if entry is not None:
                    self._sessions.move_to_end(session)
            sticky = entry[0] if entry is not None else None
            if sticky is not None and sticky in cands:
                cands.remove(sticky)
                cands.insert(0, sticky)
        return cands

    def _remember_session(
        self, session: str, rep: _Replica, tenant: str = ""
    ) -> None:
        with self._lock:
            self._sessions[session] = (rep, tenant)
            self._sessions.move_to_end(session)
            while len(self._sessions) > _MAX_SESSIONS:
                self._sessions.popitem(last=False)

    def replace_tenant_sessions(self, tenant: str) -> int:
        """Drop every session→replica pin whose traffic bills to
        ``tenant`` (the SLO actuator's sustained-burn re-placement):
        the tenant's NEXT requests place least-loaded instead of
        sticking to the replicas they saturated. In-flight streams are
        untouched — placement moves, bytes don't. Returns the number
        of pins dropped."""
        with self._lock:
            victims = [
                s for s, (_, t) in self._sessions.items() if t == tenant
            ]
            for s in victims:
                del self._sessions[s]
        if victims:
            _flight.record(
                "fleet", "replace_sessions", tenant=tenant,
                sessions=len(victims),
            )
        return len(victims)

    def tenant_counts(self) -> Tuple[dict, dict]:
        """Fleet-wide per-tenant footprint: active slots and queued
        requests summed across replicas (the QoS quota input and the
        ``/statusz`` per-tenant view)."""
        active: dict = {}
        queued: dict = {}
        for rep in self._replicas:
            a, q = rep.engine.scheduler.tenant_counts()
            for t, n in a.items():
                active[t] = active.get(t, 0) + n
            for t, n in q.items():
                queued[t] = queued.get(t, 0) + n
        return active, queued

    def _submit_to(self, rep: _Replica, rec: _FleetRequest) -> None:
        """One engine submission for ``rec`` on ``rep``, recompute-style:
        whatever the stream already delivered folds into the prompt and
        shrinks the budget, so the replica prefills ``prompt + emitted``
        and the relay emits only NEW tokens."""
        deadline = None
        if rec.deadline_t is not None:
            deadline = rec.deadline_t - time.monotonic()
            if deadline <= 0:
                raise DeadlineExceededError(
                    f"request {rec.request_id} exceeded its deadline "
                    f"before placement"
                )
        # detach any previous relay and snapshot progress ATOMICALLY
        # (rec.lock pairs with the gate in _RelayHandle._emit): a wedged
        # replica waking up after the snapshot must find the gate
        # closed, or its late emission would both reach the client and
        # be regenerated by the replay (a duplicated position)
        with rec.lock:
            rec.inner = None
            emitted = list(rec.handle._tokens)
        # the AUTHORITATIVE completeness check, on the locked snapshot: a
        # wedged replica's final emission can land after any earlier
        # lock-free check, leaving nothing to resubmit (max_new would be
        # 0) — or an EOS the replay must not generate past
        remaining = rec.max_new_tokens - len(emitted)
        eos = rec.eos_id if rec.eos_id is not None else rep.engine.eos_id
        if remaining <= 0 or (
            eos is not None and emitted and emitted[-1] == eos
        ):
            raise _StreamComplete()
        prompt = (
            np.concatenate([rec.prompt, np.asarray(emitted, np.int32)])
            if emitted
            else rec.prompt
        )
        rep.engine.submit(
            prompt,
            remaining,
            temperature=rec.temperature,
            top_p=rec.top_p,
            seed=rec.seed,
            eos_id=rec.eos_id,
            block=False,
            deadline=deadline,
            trace=rec.trace,
            tenant=rec.tenant,
            _handle_factory=lambda rid: _RelayHandle(rid, self, rec),
        )
        rec.replica = rep

    def submit(
        self,
        prompt: Sequence[int],
        max_new_tokens: int,
        temperature: float = 0.0,
        top_p: float = 1.0,
        seed: int = 0,
        eos_id: Optional[int] = None,
        block: bool = True,
        timeout: Optional[float] = None,
        deadline: Optional[float] = None,
        session: Optional[str] = None,
        tenant: Optional[str] = None,
        _resume_tokens: Optional[Sequence[int]] = None,
    ) -> FleetHandle:
        """Place one request on a healthy replica; returns its streaming
        handle. Raises ``ValueError`` for infeasible requests (every
        replica is identical — rejected everywhere),
        :class:`QueueFullError` when every active replica's admission
        queue is full (``block=True`` waits up to ``timeout`` for room),
        and :class:`EngineUnhealthyError` when ALL replicas are fenced
        (the endpoint's 503). ``session`` pins subsequent requests with
        the same key to one replica while it stays healthy. ``tenant``
        labels the request's cost-attribution record
        (``obs/requests.py``); it defaults to the session key so
        session-affine traffic is attributable without extra plumbing.

        ``_resume_tokens`` (router-HA internal, ``serve/router_ha.py``)
        pre-seeds the handle with tokens a PREVIOUS router incarnation
        already delivered, so placement goes through the same
        recompute-style fold as a replica-death replay: the delivered
        prefix folds into the prompt, the budget shrinks, per-step
        sampling keys land at their absolute positions, and the stream
        stays byte-identical across the takeover. Such a resubmission
        skips the QoS admission gate — the request was admitted (and
        billed) by the incarnation that journaled it; a takeover must
        not re-charge or re-refuse it. A resume whose prefix already
        covers the budget (or ended at EOS) settles immediately as
        success."""
        if self._closed and self._thread is None:
            raise EngineUnhealthyError("fleet is stopped")
        if deadline is not None and deadline <= 0:
            # same client-error classification as GenerationEngine.submit
            # (a 400, not a 504-shaped DeadlineExceededError from the
            # placement path)
            raise ValueError(
                f"deadline must be positive seconds from now; got {deadline}"
            )
        if int(max_new_tokens) < 1:
            # validated here too (not just per-engine) so the placement
            # path can rely on a fresh record never being "complete"
            raise ValueError(
                f"max_new_tokens must be >= 1; got {max_new_tokens}"
            )
        prompt = np.asarray(prompt, np.int32).ravel()
        tenant_key = str(tenant if tenant is not None else (session or ""))
        if _tenancy.enabled() and _resume_tokens is None:
            # the fleet-wide QoS gate, charged ONCE here: the replica
            # engines skip their own check on the relay path
            # (_handle_factory set), so a request is never billed
            # twice, and failover replays never re-enter this method
            active, queued = self.tenant_counts()
            _tenancy.admit_request(
                tenant_key, int(max_new_tokens),
                active.get(tenant_key, 0), queued.get(tenant_key, 0),
            )
        with self._id_lock:
            self._req_counter += 1
            rid = self._req_counter
        rec = _FleetRequest(
            rid,
            prompt,
            int(max_new_tokens),
            float(temperature),
            float(top_p),
            int(seed),
            eos_id,
            None if deadline is None else time.monotonic() + float(deadline),
            session,
            FleetHandle(rid),
            tenant=tenant_key,
        )
        # one trace_id for the request's whole life, however many
        # replicas serve it (the HTTP handler installs the traceparent's
        # context around this call; a fresh submit inherits any ambient
        # trace the same way)
        rec.trace = _current_trace()
        if _resume_tokens is not None:
            # a takeover resubmission: the previous incarnation's
            # delivered watermark becomes the handle's emitted prefix,
            # and _submit_to's fold does the rest (prompt + prefix,
            # shrunken budget). Safe to append directly — no relay has
            # been attached yet, so nothing else touches the handle.
            rec.handle._tokens.extend(int(t) for t in _resume_tokens)
        t_end = None if timeout is None else time.monotonic() + timeout
        while True:
            cands = run_with_retries(
                lambda: self._candidates(session, tenant_key),
                what="fleet.place",
            )
            exhausted = None
            for rep in cands:
                try:
                    self._submit_to(rep, rec)
                except _StreamComplete:
                    # only reachable for a _resume_tokens submission
                    # (a fresh record never starts complete): the WAL
                    # prefix already covers the budget or ends at EOS
                    rec.handle._finish(None)
                    return rec.handle
                except QueueFullError as e:
                    exhausted = e
                    continue
                except EngineUnhealthyError:
                    continue  # raced a death this tick; try the next
                with self._lock:
                    # stop() may have closed the fleet between the entry
                    # guard and placement; registering now would hand
                    # back a handle nothing will ever step or fail
                    if self._closed:
                        rec.handle._finish(
                            RuntimeError(
                                "fleet stopped with the request in flight"
                            )
                        )
                        raise EngineUnhealthyError("fleet is stopped")
                    # a request can settle terminally (instant deadline
                    # sweep, replica death) before this registration —
                    # inserting after _terminal's pop would leak the
                    # record forever, so check under the same lock
                    if not rec.handle.done:
                        self._inflight[rid] = rec
                if session is not None:
                    self._remember_session(session, rep, tenant_key)
                _m_placements.inc(replica=rep.name)
                return rec.handle
            if exhausted is None:
                # every candidate raced into a death mid-attempt (no
                # queue was actually full): re-resolve — the next
                # _candidates() sees their unhealthy flags and either
                # finds survivors or sheds EngineUnhealthyError, the
                # honest signal for "fleet down", not QueueFullError
                continue
            if not block:
                raise QueueFullError(
                    f"admission queues of all {len(cands)} active "
                    f"replica(s) are full"
                ) from exhausted
            if t_end is not None and time.monotonic() >= t_end:
                raise QueueFullError(
                    f"admission queues still full after {timeout}s"
                ) from exhausted
            # bounded poll rather than a cross-engine condition: this
            # path only spins while EVERY replica's queue is full (total
            # saturation), and queue drains happen inside N independent
            # engine locks that have no shared signal to wait on
            time.sleep(0.005)

    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        max_new_tokens: int,
        **kw,
    ) -> List[np.ndarray]:
        """Submit every prompt, wait for completion, return each
        request's generated tokens — the fleet twin of
        :meth:`GenerationEngine.generate`. Starts the fleet for the call
        when it is not already running."""
        started_here = self._thread is None
        if started_here:
            self.start()
        try:
            handles = [self.submit(p, max_new_tokens, **kw) for p in prompts]
            timeout = get_config().serve_result_timeout_s
            return [h.result(timeout=timeout) for h in handles]
        finally:
            if started_here:
                self.stop()

    # -- failover ----------------------------------------------------------

    @staticmethod
    def _replayable(error: BaseException) -> bool:
        """Replica deaths replay; the request's own terminal conditions
        do not: a passed deadline is passed everywhere, an infeasible
        request (``ValueError``) is infeasible on every identical
        replica, and a QoS throttle (``TenantThrottledError``) refused
        the TENANT — replaying would re-run work the admission gate
        rejected. A stale-epoch rejection (``StaleLeaseError`` /
        ``StaleRouterEpochError``) means THIS router was fenced — a
        member refusing a zombie's placement refuses it everywhere, so
        replaying would only hammer survivors with writes the fence
        exists to reject."""
        return not isinstance(
            error,
            (
                DeadlineExceededError, ValueError, TenantThrottledError,
                StaleLeaseError,
            ),
        )

    def _on_inner_finish(
        self,
        rec: _FleetRequest,
        inner: "_RelayHandle",
        error: Optional[BaseException],
    ) -> None:
        """A relay closed (engine thread context — keep this cheap and
        lock-light): success and non-replayable errors settle the
        caller's handle; replica deaths park the record for the router
        thread to resubmit."""
        with rec.lock:
            if rec.inner is not inner:
                return  # stale relay from before a replay
        if error is None:
            rec.handle._finish(None)
            with self._lock:
                self._inflight.pop(rec.request_id, None)
            return
        if (
            self._closed
            or not self._replayable(error)
            or rec.replays >= self.max_replays
        ):
            if rec.replays >= self.max_replays and self._replayable(error):
                logger.warning(
                    "fleet: request %d spent its replay budget (%d); "
                    "failing it with the replica's error",
                    rec.request_id,
                    self.max_replays,
                )
            self._terminal(rec, error)
            return
        rec.last_error = error
        rec.parked_t = time.monotonic()
        with self._lock:
            self._pending.append(rec)
        self._wake.set()

    def _terminal(self, rec: _FleetRequest, error: BaseException) -> None:
        rec.handle._finish(error)
        with self._lock:
            self._inflight.pop(rec.request_id, None)

    def _stream_complete(self, rec: _FleetRequest) -> bool:
        """Whether the stream already delivered everything the request
        asked for — the full budget, or its (request- or engine-level)
        EOS token. A replica can die in the window between a relay's
        final emission and its clean close (the wedged drain path);
        resubmitting such a request would either be infeasible
        (``max_new_tokens=0``) or generate PAST the EOS, so the router
        settles it as success instead."""
        emitted = rec.handle._tokens
        if len(emitted) >= rec.max_new_tokens:
            return True
        eos = rec.eos_id
        if eos is None:
            reps = self._replicas  # snapshot: the roster may be elastic
            eos = reps[0].engine.eos_id if reps else None
        return eos is not None and bool(emitted) and emitted[-1] == eos

    def _replay(self, rec: _FleetRequest) -> bool:
        """Resubmit one survivor of a replica death. True when settled
        (placed, terminally failed, or recognized as already complete);
        False parks it for the next tick (no healthy replica with queue
        room right now)."""
        if rec.handle.done:
            with self._lock:
                self._inflight.pop(rec.request_id, None)
            return True
        if self._stream_complete(rec):
            rec.handle._finish(None)
            with self._lock:
                self._inflight.pop(rec.request_id, None)
            return True
        try:
            cands = run_with_retries(
                lambda: self._candidates(rec.session), what="fleet.place"
            )
        except EngineUnhealthyError:
            return False  # everything fenced; restarts are in flight
        except Exception as e:
            self._terminal(rec, e)
            return True
        for rep in cands:
            try:
                self._submit_to(rep, rec)
            except _StreamComplete:
                # a late (gated) final emission landed after the
                # lock-free pre-check: the consumer already has every
                # byte — settle success, nothing to resubmit
                rec.handle._finish(None)
                with self._lock:
                    self._inflight.pop(rec.request_id, None)
                return True
            except (QueueFullError, EngineUnhealthyError):
                continue
            except Exception as e:
                self._terminal(rec, e)
                return True
            rec.replays += 1
            _m_replays.inc()
            rec.handle.timings["replays"] = rec.replays
            # a new span in the SAME trace marks the failover point: the
            # replayed request's prefill/decode spans on the new replica
            # carry the same trace_id, so the whole story is one trace
            with _use_trace(rec.trace):
                _trace_event(
                    "fleet.replay",
                    request=rec.request_id,
                    replica=rep.name,
                    replay=rec.replays,
                    emitted=len(rec.handle._tokens),
                    error=type(rec.last_error).__name__,
                )
            _flight.record(
                "fleet", "replay", request=rec.request_id,
                replica=rep.name, replay=rec.replays,
            )
            logger.warning(
                "fleet: request %d replayed on replica %s after %s "
                "(%d emitted token(s) folded into the prompt)",
                rec.request_id,
                rep.name,
                type(rec.last_error).__name__,
                len(rec.handle._tokens),
            )
            return True
        return False

    def _drain_failovers(self) -> None:
        with self._lock:
            batch = list(self._pending)
            self._pending.clear()
        parked: List[_FleetRequest] = []
        now = time.monotonic()
        # the fail-fast timer below measures time with NO healthy replica
        # — waiting behind FULL queues on a healthy fleet is ordinary
        # backpressure, not doom, so presence of healthy capacity resets
        # the clock instead of failing the survivor with a stale error
        fleet_has_healthy = any(
            rep.state == "active"
            and rep.engine.healthy
            and not rep.engine._stop_wedged
            for rep in self._replicas
        )
        for rec in batch:
            if (
                rec.deadline_t is not None
                and now >= rec.deadline_t
                and not rec.handle.done
            ):
                self._terminal(
                    rec,
                    DeadlineExceededError(
                        f"request {rec.request_id} exceeded its deadline "
                        f"awaiting failover"
                    ),
                )
                continue
            if fleet_has_healthy:
                rec.parked_t = now
            elif (
                rec.parked_t is not None
                and now - rec.parked_t > self.failover_timeout_s
                and not rec.handle.done
            ):
                # the fail-fast rule, fleet edition: with every replica
                # fenced and restarts not landing, a deadline-less
                # consumer must get the replica's real error rather
                # than hang to its own (or no) timeout
                logger.warning(
                    "fleet: request %d waited %.1fs for a healthy "
                    "replica; failing it with the replica's error",
                    rec.request_id,
                    now - rec.parked_t,
                )
                self._terminal(
                    rec,
                    rec.last_error
                    or EngineUnhealthyError(
                        "no healthy replica within the failover timeout"
                    ),
                )
                continue
            if not self._replay(rec):
                parked.append(rec)
        if parked:
            with self._lock:
                self._pending.extendleft(reversed(parked))

    # -- live KV-page migration (serve/tiers.py) ---------------------------

    def _maybe_handoff(self, rec: _FleetRequest) -> None:
        """Queue ``rec`` for tier handoff if its stream just produced
        its first token on a ``prefill``-tier replica. Called from the
        relay's ``_emit`` — the SOURCE engine's stepping thread, step
        lock held — so this only enqueues; the router tick migrates."""
        if self._closed or not get_config().tier_handoff:
            return
        rep = rec.replica
        if rep is None or rep.tier != "prefill":
            return
        with self._lock:
            self._handoff.append(rec)
        self._wake.set()

    def _drain_migrations(self) -> None:
        """One router tick's worth of migrations: first-token handoffs
        off prefill replicas, then pool-pressure rebalance imports
        parked by the on_pressure hook."""
        with self._lock:
            handoffs = list(self._handoff)
            self._handoff.clear()
            imports = list(self._imports)
            self._imports.clear()
        for rec in handoffs:
            try:
                self._migrate_handoff(rec)
            except Exception:
                logger.exception(
                    "fleet: handoff of request %d failed unexpectedly",
                    rec.request_id,
                )
        for snap, rec, dst_name in imports:
            try:
                self._import_slot(snap, rec, dst_name)
            except Exception:
                logger.exception(
                    "fleet: rebalance import of request %d failed "
                    "unexpectedly",
                    rec.request_id,
                )

    def _migrate_handoff(self, rec: _FleetRequest) -> None:
        """Move one just-prefilled stream from its prefill-tier replica
        to a decode-capable one: export the slot's KV pages (host
        bytes), retire the source relay, restore on the destination.
        Failure BEFORE the export is a no-op (the stream keeps decoding
        where it is); failure AFTER falls back to the recompute-style
        replay path — the same ladder a replica death uses — so the
        caller's stream survives either way, byte-identical."""
        src = rec.replica
        inner = rec.inner
        if (
            rec.handle.done
            or src is None
            or src.tier != "prefill"
            or inner is None
            or not hasattr(src.engine, "detach_slot")
        ):
            return
        try:
            # the chaos window for the fleet-level migration decision;
            # transient faults retry invisibly, a fatal one aborts the
            # handoff before any pages moved (stream unaffected)
            run_with_retries(
                lambda: _chaos.site("fleet.migrate"), what="fleet.migrate"
            )
            dsts = [
                rep
                for rep in self._candidates(
                    rec.session, rec.tenant or None, role="decode"
                )
                if rep is not src and hasattr(rep.engine, "attach_slot")
            ]
            if not dsts:
                return  # no decode capacity: keep decoding on prefill
            snap = src.engine.detach_slot(inner.request_id, reason="handoff")
        except Exception as e:
            # nothing was detached — the slot still lives at the source
            # and keeps streaming; log and count the aborted attempt
            _tiers._m_migrations.inc(reason="aborted")
            logger.warning(
                "fleet: handoff of request %d aborted before export "
                "(%s: %s); stream continues on %s",
                rec.request_id, type(e).__name__,
                str(e).split("\n", 1)[0][:120], src.name,
            )
            return
        if snap is None:
            return  # already finished / preempted / not migratable
        self._place_snapshot(snap, rec, inner, dsts, reason="handoff")

    def _import_slot(self, snap, rec: _FleetRequest, dst_name: str) -> None:
        """Land one rebalance snapshot (detached synchronously by the
        on_pressure hook) on its chosen destination, least-loaded
        fallbacks behind it."""
        inner = rec.inner
        try:
            dsts = [
                rep
                for rep in self._candidates(
                    rec.session, rec.tenant or None, role="decode"
                )
                if rep.name != snap.source
                and hasattr(rep.engine, "attach_slot")
            ]
        except EngineUnhealthyError:
            dsts = []
        # the hook's chosen destination goes first if still eligible
        dsts.sort(key=lambda rep: rep.name != dst_name)
        self._place_snapshot(snap, rec, inner, dsts, reason="rebalance")

    def _place_snapshot(
        self,
        snap,
        rec: _FleetRequest,
        inner: Optional[_RelayHandle],
        dsts: List[_Replica],
        reason: str,
    ) -> None:
        """The import half of a migration: the snapshot's pages are OFF
        the source (freed), so the stream MUST land somewhere — try
        each destination, and when none takes it, fall back to the
        replay queue (recompute-style, same as a replica death). The
        source relay is retired first so a stale late emission from the
        source engine cannot race the destination's stream."""
        with rec.lock:
            if rec.inner is inner:
                rec.inner = None
        for dst in dsts:
            try:
                dst.engine.attach_slot(
                    snap,
                    _handle_factory=lambda rid: _RelayHandle(rid, self, rec),
                )
            except Exception as e:
                logger.warning(
                    "fleet: migration of request %d to %s failed (%s: "
                    "%s); trying next destination",
                    rec.request_id, dst.name, type(e).__name__,
                    str(e).split("\n", 1)[0][:120],
                )
                continue
            rec.replica = dst
            if rec.session is not None:
                self._remember_session(rec.session, dst, rec.tenant)
            with _use_trace(rec.trace):
                _trace_event(
                    "fleet.migrate",
                    request=rec.request_id,
                    source=snap.source,
                    replica=dst.name,
                    reason=reason,
                    pages=snap.n_pages,
                    emitted=len(rec.handle._tokens),
                )
            _flight.record(
                "fleet", "migrate", request=rec.request_id,
                source=snap.source, replica=dst.name, reason=reason,
                pages=snap.n_pages,
            )
            logger.info(
                "fleet: request %d migrated %s -> %s (%s, %d page(s), "
                "%d token(s) emitted)",
                rec.request_id, snap.source, dst.name, reason,
                snap.n_pages, len(rec.handle._tokens),
            )
            return
        # no destination took the pages — recompute-style fallback:
        # park the record for the ordinary replay drain (prompt + the
        # tokens already emitted re-prefill elsewhere, byte-identical)
        _tiers._m_migrations.inc(reason="failed")
        rec.last_error = TierMigrationError(
            f"no destination accepted the migrated pages of request "
            f"{rec.request_id}; replaying recompute-style"
        )
        rec.parked_t = time.monotonic()
        logger.warning(
            "fleet: migration of request %d found no destination; "
            "falling back to recompute replay", rec.request_id,
        )
        with self._lock:
            self._pending.append(rec)
        self._wake.set()

    def _on_pool_pressure(self, rep: _Replica, victim_idx: int) -> bool:
        """The scheduler's ``on_pressure`` hook (serve/tiers.py door):
        under KV-pool pressure on ``rep``, try to MIGRATE the chosen
        victim's slot to a less-loaded decode-capable replica instead
        of preempting it. Runs on the source engine's stepping thread
        with the (re-entrant) step lock held: the export is synchronous
        (it frees the victim's pages, which is the whole point — the
        caller retries its reservation on True), but the import is
        parked for the router tick so the two engines' step locks never
        nest. Returns False for ANY reason migration can't proceed —
        the grow ladder falls back to preemption, exactly as before."""
        if (
            self._closed
            or self._thread is None
            or not get_config().tier_rebalance
        ):
            return False
        eng = rep.engine
        act = eng.scheduler.slots[victim_idx]
        if act is None or not act.generated or act.cow_src is not None:
            return False
        rec = getattr(act.req.handle, "_rec", None)
        if rec is None or rec.handle.done or rec.inner is not act.req.handle:
            return False
        need = len(act.seq.pages)
        try:
            cands = [
                r
                for r in self._candidates(None, None, role="decode")
                if r is not rep
                and hasattr(r.engine, "attach_slot")
                and r.engine.page_size == eng.page_size
                and r.engine.pool.pages_free > need
                and any(s is None for s in r.engine.scheduler.slots)
            ]
        except EngineUnhealthyError:
            return False
        if not cands:
            return False
        try:
            run_with_retries(
                lambda: _chaos.site("fleet.migrate"), what="fleet.migrate"
            )
            snap = eng.detach_slot(act.req.request_id, reason="rebalance")
        except Exception as e:
            logger.warning(
                "fleet: rebalance export on %s aborted (%s); preempting "
                "instead", rep.name, type(e).__name__,
            )
            return False
        if snap is None:
            return False
        with self._lock:
            self._imports.append((snap, rec, cands[0].name))
        self._wake.set()
        logger.info(
            "fleet: pool pressure on %s — slot %d (request %d) exported "
            "for rebalance to %s instead of preemption",
            rep.name, victim_idx, rec.request_id, cands[0].name,
        )
        return True

    def _install_pressure_hook(self, rep: _Replica) -> None:
        """Point ``rep``'s scheduler at the fleet's migrate-not-preempt
        ladder rung. Local engines only — a remote-replica adapter has
        no scheduler here (its own process installs its own hook)."""
        sched = getattr(rep.engine, "scheduler", None)
        if sched is None or not hasattr(rep.engine, "detach_slot"):
            return
        sched.on_pressure = (
            lambda victim_idx, _rep=rep: self._on_pool_pressure(
                _rep, victim_idx
            )
        )

    # -- health gating -----------------------------------------------------

    def _fence(
        self, rep: _Replica, error: BaseException, wedged: bool = False
    ) -> None:
        """Gate a replica out: no new placements, and every attached
        handle fails NOW so its survivors hit the failover queue instead
        of hanging against an engine that will never step them. A
        ``draining`` replica fences too — an administrative drain does
        not immunize a replica against dying, and its in-flight streams
        still deserve the replay path."""
        with rep.lock:
            if rep.state not in ("active", "draining"):
                return
            rep.state = "fenced"
            rep.wedged = wedged
        _m_failovers.inc()
        _flight.record(
            "fleet", "fence", replica=rep.name, wedged=wedged,
            error=f"{type(error).__name__}: {_first_line(error)}",
        )
        logger.warning(
            "fleet: replica %s fenced (%s: %s); draining%s",
            rep.name,
            type(error).__name__,
            str(error).split("\n", 1)[0][:120],
            "" if wedged else " and restarting in the background",
        )
        eng = rep.engine
        eng.healthy = False  # submit sheds immediately, before the drain
        try:
            if wedged:
                # the wedged step may hold the step lock forever; fail the
                # handles through the scheduler directly rather than
                # blocking the watchdog behind a stuck device call
                eng.scheduler.fail_all(error)
            elif eng._thread is not None and eng._thread.is_alive():
                # a live stepping loop drains ITSELF at the next step
                # boundary — fighting it for the step lock from here
                # could lose for many steps while the doomed engine
                # keeps emitting
                eng.inject_fault(error)
            else:
                eng._fail_inflight(error)  # nothing stepping: drain inline
        except Exception:
            logger.warning(
                "fleet: drain of replica %s failed", rep.name, exc_info=True
            )
        self._wake.set()

    def _kill_replica(self, rep: _Replica, error: BaseException) -> None:
        """A chaos-scheduled hard replica fault: the replica dies at its
        next step boundary (fence + injected fault), then its device
        state is scrambled outright (like the crash drills in
        tests/test_chaos.py) — the router must carry every stream
        without the dead replica's help, and ``restart()`` must not
        depend on anything the pool still holds."""
        self._fence(rep, error)
        eng = rep.engine
        # scramble only AFTER the injected fault drained at a step
        # boundary: a step already past the poison check may not have
        # read pool.k/v yet, and scrambling under it would emit wrong
        # bytes through the still-open relay before the kill lands
        drained = time.monotonic() + 2.0
        while eng._poison is not None and time.monotonic() < drained:
            time.sleep(0.002)
        if eng._poison is not None:
            # a step is stuck past the poison check: scrambling under it
            # would be the exact corrupt-emission this wait prevents —
            # the fence (and eventual drain) IS the kill; skip the color
            logger.warning(
                "fleet: replica %s kill: injected fault not drained "
                "after 2s (stuck step?); skipping the pool scramble",
                rep.name,
            )
            return
        try:
            # under the step lock and to completion: the restart worker
            # waits for the same drained poison and may already have the
            # probe's prefill queued; a scramble dispatched beside that
            # step (two multi-device programs from two threads) wedged
            # the replica's stepping thread for good under load
            import jax

            with eng._step_lock:
                eng.pool.fill(97.0, -97.0)
                jax.block_until_ready((eng.pool.k, eng.pool.v))
        except Exception:
            pass  # the fence is the fault; corruption is the drill's color

    def _probe_engine(self, eng) -> None:
        """One probe generation — a token through prefill AND decode —
        that must succeed before a replica (re)takes traffic. Raises on
        failure; shared by the restart worker, :meth:`probe_replica`,
        and the membership layer's admission/weight-swap gates."""
        probe_new = max(1, min(2, eng.max_seq_len - 1))
        probe = eng.submit(
            [1], probe_new, block=False, deadline=self.probe_timeout_s
        )
        if eng._thread is None:
            eng.run_until_idle()  # fleet not started: drive it inline
        probe.result(timeout=self.probe_timeout_s)

    def probe_replica(self, name: str) -> bool:
        """Run one probe generation against a replica WITHOUT touching
        its gate state — the health check the rolling weight swap runs
        on a drained member before re-admitting it. Returns whether the
        probe produced a token in time."""
        rep = self._replica(name)
        try:
            self._probe_engine(rep.engine)
            return True
        except Exception:
            logger.warning(
                "fleet: replica %s probe failed", rep.name, exc_info=True
            )
            return False

    def drain_replica(self, name: str) -> bool:
        """Administratively gate a replica out of NEW placements while
        its in-flight streams finish on it (the first step of a rolling
        restart / weight swap — a drain, not a fence: nothing fails).
        Session pins to the replica are dropped so affine traffic
        re-places immediately. Returns False unless the replica was
        active."""
        rep = self._replica(name)
        with rep.lock:
            if rep.state != "active":
                return False
            rep.state = "draining"
        with self._lock:
            victims = [
                s for s, (r, _) in self._sessions.items() if r is rep
            ]
            for s in victims:
                del self._sessions[s]
        _flight.record(
            "fleet", "drain", replica=rep.name, sessions_dropped=len(victims)
        )
        logger.warning(
            "fleet: replica %s draining (no new placements; %d session "
            "pin(s) dropped)",
            rep.name,
            len(victims),
        )
        self._wake.set()
        return True

    def admit_replica(self, name: str, probe: bool = True) -> bool:
        """Re-admit a drained or fenced replica to placement, gated on a
        probe generation by default (re-admitting a replica that cannot
        generate would just bounce traffic). The administrative twin of
        the restart worker's re-admission — it does NOT restart the
        engine first; callers that recycled the process or swapped
        weights already did. Returns whether the replica is active
        afterwards."""
        rep = self._replica(name)
        with rep.lock:
            if rep.state == "active":
                return True
            if rep.wedged or rep.restarting:
                return False
        if probe:
            try:
                self._probe_engine(rep.engine)
            except Exception:
                logger.warning(
                    "fleet: replica %s admission probe failed; it stays "
                    "%s",
                    rep.name,
                    rep.state,
                    exc_info=True,
                )
                return False
        with rep.lock:
            if rep.wedged or rep.restarting:
                return False  # fenced wedged while the probe ran
            rep.state = "active"
        _flight.record("fleet", "admit", replica=rep.name)
        logger.warning("fleet: replica %s re-admitted", rep.name)
        self._wake.set()
        return True

    def set_replica_tier(self, name: str, tier: str) -> None:
        """Re-role one replica at runtime (serve/tiers.py): the
        membership layer applies a joining member's advertised tier
        here, and an operator can re-shape a live fleet (e.g. grow the
        decode tier for a long-output workload) without restarts.
        In-flight streams are untouched — only FUTURE placements and
        handoffs see the new role."""
        if tier not in TIERS:
            raise ValueError(f"unknown tier {tier!r}; expected one of {TIERS}")
        rep = self._replica(name)
        if rep.tier == tier:
            return
        old, rep.tier = rep.tier, tier
        _flight.record(
            "fleet", "retier", replica=rep.name, tier=tier, was=old
        )
        logger.info("fleet: replica %s re-roled %s -> %s", rep.name, old, tier)
        self._wake.set()

    def _add_replica(self, name: str, engine, tier: str = "mixed") -> None:
        """Grow the roster by one pre-built engine (a member joining the
        elastic fleet). Copy-on-write rebind: concurrent placement and
        watchdog sweeps keep iterating their snapshot."""
        if tier not in TIERS:
            raise ValueError(f"unknown tier {tier!r}; expected one of {TIERS}")
        rep = _Replica(str(name), engine, tier=tier)
        self._install_pressure_hook(rep)
        with self._lock:
            if any(r.name == rep.name for r in self._replicas):
                raise ValueError(f"replica {rep.name!r} already exists")
            self._replicas = self._replicas + [rep]
        if self._thread is not None and engine._thread is None:
            try:
                engine.start()
            except Exception:
                logger.warning(
                    "fleet: replica %s failed to start on join",
                    rep.name,
                    exc_info=True,
                )
        _flight.record("fleet", "replica_join", replica=rep.name)
        self._wake.set()

    def _remove_replica(self, name: str) -> Optional[_Replica]:
        """Shrink the roster (a departed/fenced member leaving the
        elastic fleet). Session pins to the removed replica are dropped;
        the replica object is returned so the caller can drain or stop
        its engine. Unknown names return None (removal is idempotent —
        registry sweeps may race)."""
        with self._lock:
            rep = next(
                (r for r in self._replicas if r.name == name), None
            )
            if rep is None:
                return None
            self._replicas = [r for r in self._replicas if r is not rep]
            victims = [
                s for s, (r, _) in self._sessions.items() if r is rep
            ]
            for s in victims:
                del self._sessions[s]
        _flight.record("fleet", "replica_leave", replica=rep.name)
        self._wake.set()
        return rep

    def restart_replica(self, name: str) -> bool:
        """Manually restart + probe + re-admit a fenced replica (the
        ``auto_restart=False`` path). A no-op on an active replica
        (restarting one that is serving would preempt healthy traffic),
        on a wedged one (``restart()`` would block behind the stuck
        step — recycle the process), and while another restart worker
        already owns the replica. Returns whether the replica is active
        afterwards."""
        rep = self._replica(name)
        with rep.lock:
            if rep.state != "fenced" or rep.wedged or rep.restarting:
                return rep.state == "active"
            rep.restarting = True
        self._restart_worker(rep)
        return rep.state == "active"

    def _restart_worker(self, rep: _Replica) -> None:
        """Background recovery for one fenced replica: ``restart()``
        rebuilds device state (zero recompiles), then a probe generation
        must push one token through prefill AND decode before the
        replica takes traffic again — re-admitting a replica that
        cannot actually generate would just bounce the survivors."""
        try:
            eng = rep.engine
            # let the fence's injected fault drain the old traffic first:
            # restarting early would requeue survivors on THIS replica
            # instead of letting the router replay them, and the probe
            # would race the pending kill
            drained = time.monotonic() + 5.0
            while time.monotonic() < drained and (
                eng._poison is not None or eng.scheduler.has_work()
            ):
                if self._stop_evt.is_set():
                    return
                time.sleep(0.005)
            if self._stop_evt.is_set() or self._closed:
                # the fleet stopped while this worker waited: restarting
                # (healthy=True, probe compute) AFTER stop() returned
                # would resurrect a replica the caller believes is down
                return
            try:
                eng.restart()
            except RuntimeError:
                logger.warning(
                    "fleet: replica %s restart refused (wedged stop?); "
                    "leaving it fenced",
                    rep.name,
                )
                return
            self._probe_engine(eng)
            if self._stop_evt.is_set() or self._closed:
                return  # stopped mid-probe: stay fenced, stay quiet
            with rep.lock:
                rep.state = "active"
                rep.wedged = False
            _flight.record("fleet", "readmit", replica=rep.name)
            logger.warning(
                "fleet: replica %s re-admitted (restart + probe ok)",
                rep.name,
            )
        except Exception:
            logger.warning(
                "fleet: replica %s probe failed; it stays fenced for the "
                "next watchdog attempt",
                rep.name,
                exc_info=True,
            )
        finally:
            rep.restarting = False

    def _poll_replicas(self) -> None:
        healthy = 0
        for rep in self._replicas:
            if rep.state == "active":
                try:
                    _chaos.site("fleet.replica_fault")
                    _chaos.site("fleet.replica_fault." + rep.name)
                except Exception as e:
                    self._kill_replica(rep, e)
            h = rep.engine.health()
            if rep.state in ("active", "draining"):
                wedged = (
                    h["last_step_age_s"] > self.wedge_timeout_s
                    and (h["queue_depth"] > 0 or h["active_slots"] > 0)
                    and bool(h["stepping_thread_alive"])
                    # a step that compiles its program takes as long as
                    # XLA does — past any sane wedge bound at real model
                    # widths; the engine reports it (absent on members
                    # that predate the field)
                    and not h.get("compiling")
                )
                if not h["healthy"] or wedged:
                    self._fence(
                        rep,
                        RuntimeError(
                            "replica health probe failed "
                            f"(healthy={h['healthy']}, "
                            f"last_step_age_s={h['last_step_age_s']})"
                        ),
                        wedged=wedged,
                    )
            if rep.state == "fenced" and not rep.wedged and self.auto_restart:
                with rep.lock:
                    # compare-and-set under the replica lock: a manual
                    # restart_replica() may own the replica already
                    spawn = rep.state == "fenced" and not rep.restarting
                    if spawn:
                        rep.restarting = True
                if spawn:
                    threading.Thread(
                        target=self._restart_worker, args=(rep,), daemon=True
                    ).start()
            if rep.state == "active":
                healthy += 1
            _m_rep_queue.set(float(h["queue_depth"]), replica=rep.name)
            _m_rep_pages.set(float(h["pages_in_use"]), replica=rep.name)
        _m_replicas_healthy.set(float(healthy))
        for tier in TIERS:
            _m_tier_replicas.set(
                float(
                    sum(
                        1
                        for rep in self._replicas
                        if rep.state == "active" and rep.tier == tier
                    )
                ),
                tier=tier,
            )

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "Fleet":
        """Start every replica's stepping thread plus the fleet's
        router/watchdog thread. A stopped fleet may start again."""
        if self._thread is not None:
            raise RuntimeError("fleet already started")
        self._closed = False
        self._stop_evt.clear()
        self._wake.clear()
        for rep in self._replicas:
            self._install_pressure_hook(rep)
            if rep.engine._thread is None:
                rep.engine.start()
        self._thread = threading.Thread(target=self._supervise, daemon=True)
        self._thread.start()
        # the SLO actuator's session re-placement hook (weakly held —
        # a stopped/collected fleet unregisters itself)
        _tenancy.register_fleet(self)
        return self

    def _tick(self) -> None:
        """One router iteration: health poll (fence/restart), tick
        hooks, queued KV migrations, the failover queue. The router
        thread loops over this; a fleet that was never started can be
        driven by calling it between hand-run engine steps (how the
        tier tests place a handoff at an exact point in a stream)."""
        self._poll_replicas()
        for hook in list(self._tick_hooks):
            try:
                hook()
            except Exception:
                logger.warning(
                    "fleet: tick hook %r failed", hook, exc_info=True
                )
        self._drain_migrations()
        self._drain_failovers()

    def _supervise(self) -> None:
        """The router thread: :meth:`_tick` every watchdog interval (or
        sooner when woken). Logs loudly if it ever dies — a silent
        watchdog death would turn the next replica fault back into an
        outage."""
        try:
            while not self._stop_evt.is_set():
                self._tick()
                self._wake.wait(self.watchdog_interval_s)
                self._wake.clear()
        except BaseException:
            if not self._stop_evt.is_set():
                logger.error(
                    "fleet supervisor thread died; failover and "
                    "re-admission are OFFLINE until restart",
                    exc_info=True,
                )
            raise

    def stop(self) -> None:
        """Stop the router and every replica; any still-open fleet
        handle fails (never strands its consumer)."""
        with self._lock:
            # under the fleet lock so a concurrent submit either
            # registers BEFORE this flag (and gets drained below) or
            # observes it at registration and sheds
            self._closed = True
        _tenancy.register_fleet(None)
        self._stop_evt.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            if self._thread.is_alive():
                # a zombie router fencing/replaying next to a future
                # start()'s router would double every failover action —
                # keep the reference (start() refuses while it is set)
                # and let a retried stop() join again; _stop_evt stays
                # set, so the thread exits whenever it unblocks
                logger.warning(
                    "fleet: router thread did not stop within 10s "
                    "(blocked in a drain?); stop() again to retry — "
                    "start() is refused until it exits"
                )
            else:
                self._thread = None
        for rep in self._replicas:
            try:
                rep.engine.stop()
            except Exception:
                logger.warning(
                    "fleet: replica %s stop failed", rep.name, exc_info=True
                )
        with self._lock:
            recs = list(self._inflight.values())
            self._inflight.clear()
            self._pending.clear()
            self._handoff.clear()
            self._imports.clear()
        err = RuntimeError("fleet stopped with the request in flight")
        for rec in recs:
            rec.handle._finish(err)  # no-op on already-settled handles

    def __enter__(self) -> "Fleet":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()
