"""Deterministic fault injection: chaos testing for the failure machinery.

The reference delegated failure handling to Spark's task retry and
lineage (SURVEY §5) and therefore inherited Spark's chaos tooling too;
this engine carries its own failure taxonomy (``utils/failures.py``, the
serving supervisor in ``serve/engine.py``), so it needs its own way to
PROVE that machinery under fault — on CPU, in CI, with deterministic
seeds — instead of waiting for a real TPU runtime to misbehave.

Named **injection sites** sit on the host-side dispatch paths:

- ``engine.dispatch`` — inside every batch-engine retry window
  (``map_blocks`` partitions, ``map_rows`` chunks, ``reduce_blocks``)
- ``serve.prefill`` / ``serve.prefill_chunk`` / ``serve.decode_step``
  / ``serve.verify`` — the generation engine's compiled-step
  dispatches (inside their retry windows); ``serve.verify`` is the
  speculative-decoding batched multi-token check — a ``transient``
  there retries the whole verify span invisibly, streams stay
  byte-identical
- ``kv_pages.alloc`` — the KV page-pool allocator
- ``serving.conn`` — the scoring server's per-connection handler
- ``jobs.block`` — inside a durable batch job's per-block execution
  (``engine/jobs.py``): a ``fatal`` here is the poison-block /
  quarantine drill
- ``jobs.journal_write`` — inside the job journal's write path (npz
  spool + ledger append): a ``fatal`` here simulates a crash between
  computing a block and recording it (the kill-and-resume drill)
- ``jobs.lease`` — inside a distributed-job worker's lease
  claim/reclaim path (``engine/dist_jobs.py``): a ``transient`` retries
  the claim; a ``fatal`` is the worker-dies-while-claiming drill
- ``jobs.heartbeat`` — inside the lease heartbeat renewal: ``latency``
  past the lease TTL is the presumed-dead drill (the lease expires and
  another worker reclaims the block; the stalled owner's late write is
  then fence-rejected)
- ``frame.h2d`` / ``frame.d2h`` — inside every streaming-transfer
  chunk's retry window (``frame/transfer.py``): a ``transient`` here is
  the flaky-link-during-ingest drill (one chunk retries; the column
  still lands byte-identical)
- ``fleet.place`` — inside the serving fleet's placement path
  (``serve/fleet.py``): a ``transient`` here retries invisibly; a
  ``fatal`` is the router-bug drill
- ``tier.handoff`` — inside a live KV-page migration's export read and
  import write retry windows (``serve/tiers.py``): a ``transient``
  retries the page transfer invisibly (reads are pure; the write
  re-sets the same rows); a ``fatal`` aborts the migration into the
  fallback ladder (failover replay / preemption) — the stream survives
  either way
- ``fleet.migrate`` — at the head of a fleet-level slot migration
  (``serve/fleet.py``: tier handoff drain and pool-pressure
  rebalance): a ``fatal`` is the migration-machinery-bug drill — the
  request must continue via replay/preemption with no duplicated or
  lost tokens
- ``fleet.replica_fault`` — polled once per replica per fleet watchdog
  tick: any raising kind KILLS the replica whose poll fired (device
  state scrambled, every attached handle failed — the hard-process-
  fault drill for failover/replay). Suffix the site with a replica
  name to target one: ``fleet.replica_fault.r1=fatal:every=8`` — this
  site composes such names at runtime, so its dotted suffixes (its
  FAMILY, see ``SITE_FAMILIES``) skip the unknown-site warning;
  suffixes on every other site warn like any typo.

A site is one call: ``chaos.site("serve.decode_step")``. When no
schedule is configured (the default) that compiles down to a single
module-global check — the same no-op-gate pattern as the ``TFT_OBS``
observability switch — so production paths pay one predicate and
nothing else, and the sites add **zero** compiled programs (they run on
the host, never inside a traced function).

A schedule is a spec string, via ``TFT_CHAOS`` in the environment or
``set_config(chaos=...)`` (the Config field wins when non-empty)::

    seed=42;serve.decode_step=transient:p=0.2;kv_pages.alloc=pool:every=7

``;``-separated entries; ``seed=N`` seeds the shared RNG (probability
schedules are deterministic given call order), every other entry is
``site=kind[:param=value]*``:

kinds
    ``transient``  raise a synthesized PJRT-style transient error
    (``UNAVAILABLE: ...`` — retried by ``run_with_retries``);
    ``oom``  raise :class:`~.failures.DeviceOOMError`
    (``RESOURCE_EXHAUSTED`` text);
    ``pool``  raise :class:`~.failures.PagePoolExhausted`
    (the scheduler's preempt-and-requeue cue);
    ``latency``  sleep instead of raising (watchdog / deadline fodder);
    ``fatal``  raise :class:`ChaosFault`, which deliberately matches
    NEITHER marker set — the fail-fast path.

params
    ``p=0.2``   fire with probability 0.2 (seeded RNG);
    ``every=7`` fire on every 7th call of this rule;
    ``times=3`` stop after 3 injections;
    ``ms=50``   latency duration (``latency`` kind only).

``p`` and ``every`` compose (the probability applies on the every-nth
calls); a rule with neither fires on every call. Every injection
increments ``chaos.injections_total{site,kind}`` and logs one warning.
See ``docs/fault_tolerance.md`` for the harness cookbook.
"""

from __future__ import annotations

import os
import random
import threading
import time
from typing import Dict, List, Optional, Tuple

from .logging import get_logger

__all__ = [
    "ChaosFault",
    "SITES",
    "SITE_FAMILIES",
    "active_spec",
    "enabled",
    "scoped",
    "site",
]

logger = get_logger("chaos")

from ..obs.metrics import counter as _counter  # noqa: E402

_m_injections = _counter(
    "chaos.injections_total",
    "Faults injected by the chaos harness, by site and kind",
    labels=("site", "kind"),
)


class ChaosFault(RuntimeError):
    """A chaos-injected FATAL fault. Its text matches neither the
    transient nor the OOM markers, so classification routes it to the
    fail-fast path (fail every in-flight handle, mark unhealthy) —
    the one failure mode retry and degradation must NOT absorb."""


#: canonical sites wired into the engine; ``site()`` accepts any name
#: (unknown sites simply never fire), these are the ones that exist
SITES = (
    "engine.dispatch",
    "serve.prefill",
    "serve.prefill_chunk",
    "serve.decode_step",
    "serve.verify",
    "kv_pages.alloc",
    "serving.conn",
    "jobs.block",
    "jobs.journal_write",
    "jobs.lease",
    "jobs.heartbeat",
    "frame.h2d",
    "frame.d2h",
    "fleet.place",
    "fleet.replica_fault",
    "fleet.member_heartbeat",
    "fleet.registry",
    "fleet.router_wal",
    "fleet.router_heartbeat",
    "tier.handoff",
    "fleet.migrate",
    "tune.trial",
    "tenancy.admit",
)

#: sites whose code COMPOSES dotted suffixes at runtime (their FAMILY):
#: ``fleet.replica_fault.<name>`` targets one replica. Only these skip
#: the unknown-site warning for suffixed names — a suffix on any other
#: wired site (``serve.decode_step.typo=...``) is still a typo that
#: would silently never fire, and must warn
SITE_FAMILIES = ("fleet.replica_fault",)

_KINDS = ("transient", "oom", "pool", "latency", "fatal")


class _Rule:
    """One ``site=kind:params`` entry with its firing state."""

    __slots__ = ("site", "kind", "p", "every", "times", "latency_s",
                 "calls", "fired")

    def __init__(
        self,
        site: str,
        kind: str,
        p: Optional[float] = None,
        every: Optional[int] = None,
        times: Optional[int] = None,
        latency_s: float = 0.05,
    ):
        if kind not in _KINDS:
            raise ValueError(
                f"unknown chaos kind {kind!r} for site {site!r}; "
                f"expected one of {_KINDS}"
            )
        if every is not None and every < 1:
            raise ValueError(f"chaos every= must be >= 1; got {every}")
        if p is not None and not 0.0 <= p <= 1.0:
            raise ValueError(f"chaos p= must be in [0, 1]; got {p}")
        self.site = site
        self.kind = kind
        self.p = p
        self.every = every
        self.times = times
        self.latency_s = latency_s
        self.calls = 0
        self.fired = 0

    def should_fire(self, rng: random.Random) -> bool:
        self.calls += 1
        if self.times is not None and self.fired >= self.times:
            return False
        if self.every is not None and self.calls % self.every != 0:
            return False
        if self.p is not None and rng.random() >= self.p:
            return False
        self.fired += 1
        return True


def _parse(spec: str) -> Tuple[int, Dict[str, List[_Rule]]]:
    """Spec string -> (seed, rules by site). Raises ``ValueError`` on a
    malformed spec — a typo'd chaos schedule silently doing nothing
    would defeat the whole point of a deterministic harness."""
    seed = 0
    by_site: Dict[str, List[_Rule]] = {}
    for part in spec.split(";"):
        part = part.strip()
        if not part:
            continue
        if part.startswith("seed="):
            seed = int(part[len("seed="):])
            continue
        name, sep, rest = part.partition("=")
        name = name.strip()
        if not sep or not name or not rest:
            raise ValueError(
                f"malformed chaos entry {part!r}; expected "
                "'site=kind[:param=value]*' or 'seed=N'"
            )
        kind, *params = rest.split(":")
        kw: Dict[str, object] = {}
        for prm in params:
            k, psep, v = prm.partition("=")
            if not psep:
                raise ValueError(
                    f"malformed chaos param {prm!r} in {part!r}"
                )
            if k == "p":
                kw["p"] = float(v)
            elif k == "every":
                kw["every"] = int(v)
            elif k == "times":
                kw["times"] = int(v)
            elif k == "ms":
                kw["latency_s"] = float(v) / 1e3
            else:
                raise ValueError(
                    f"unknown chaos param {k!r} in {part!r}; "
                    "expected p=, every=, times=, ms="
                )
        by_site.setdefault(name, []).append(_Rule(name, kind.strip(), **kw))
    return seed, by_site


#: environment spec, read once; the Config field (set_config(chaos=...))
#: takes precedence whenever it is non-empty
_ENV_SPEC = os.environ.get("TFT_CHAOS", "").strip()

_lock = threading.Lock()
_rules: Dict[str, List[_Rule]] = {}
_rng = random.Random(0)
_spec = ""

#: the hot-path gate — one module-global read when disabled, same
#: pattern as the TFT_OBS switch (obs/metrics.py)
_ON = False


def _refresh() -> None:
    from .config import get_config

    global _ON, _rules, _rng, _spec
    spec = get_config().chaos or _ENV_SPEC
    with _lock:
        if spec == _spec:
            # unrelated set_config: keep rule counters and RNG state so a
            # mid-run config touch cannot reset an every-nth schedule
            return
        seed, by_site = _parse(spec)
        for name in by_site:
            # dotted suffixes of a FAMILY site (SITE_FAMILIES — e.g. the
            # fleet's per-replica kills, fleet.replica_fault.r1) fire
            # because the code composes those names at runtime; suffixes
            # on any other site are typos and warn like unknown names
            if name not in SITES and not any(
                name.startswith(s + ".") for s in SITE_FAMILIES
            ):
                # not an error (tests inject at ad-hoc sites), but a
                # typo'd production schedule silently never firing would
                # defeat the harness — say so once at configure time
                logger.warning(
                    "chaos: site %r is not one of the wired injection "
                    "sites %s; its rules will never fire unless code "
                    "calls chaos.site(%r)",
                    name, SITES, name,
                )
        _rules = by_site
        _rng = random.Random(seed)
        _spec = spec
        _ON = bool(by_site)


from .config import register_on_change  # noqa: E402

register_on_change(_refresh)


def enabled() -> bool:
    """Whether any chaos schedule is active."""
    return _ON


def active_spec() -> str:
    """The spec string currently installed ("" when disabled)."""
    return _spec


def site(name: str) -> None:
    """A chaos injection point. No-op (one module-global check) unless a
    schedule names this site; otherwise may raise a synthesized failure
    or inject latency per the schedule."""
    if not _ON:
        return
    _fire(name)


def _fire(name: str) -> None:
    with _lock:
        todo = [r for r in _rules.get(name, ()) if r.should_fire(_rng)]
    for r in todo:
        _m_injections.inc(site=name, kind=r.kind)
        from ..obs import flight as _flight

        _flight.record("chaos", r.kind, site=name)
        logger.warning("chaos: injecting %s at %s", r.kind, name)
        if r.kind == "latency":
            time.sleep(r.latency_s)
        elif r.kind == "transient":
            raise RuntimeError(
                f"UNAVAILABLE: chaos-injected transient fault at {name}"
            )
        elif r.kind == "oom":
            from .failures import DeviceOOMError

            raise DeviceOOMError(
                f"RESOURCE_EXHAUSTED: chaos-injected device OOM at {name}"
            )
        elif r.kind == "pool":
            from .failures import PagePoolExhausted

            raise PagePoolExhausted(
                f"chaos-injected page-pool exhaustion at {name}"
            )
        else:  # fatal
            raise ChaosFault(f"chaos-injected fatal fault at {name}")


class scoped:
    """Context manager installing a chaos spec for a test block::

        with chaos.scoped("seed=1;serve.decode_step=transient:every=2"):
            ...

    Installs via ``set_config(chaos=...)`` (so the gate refresh runs) and
    restores the previous spec on exit."""

    def __init__(self, spec: str):
        self._new = spec
        self._prev: Optional[str] = None

    def __enter__(self) -> "scoped":
        from .config import get_config, set_config

        self._prev = get_config().chaos
        set_config(chaos=self._new)
        return self

    def __exit__(self, *exc) -> None:
        from .config import set_config

        set_config(chaos=self._prev or "")
