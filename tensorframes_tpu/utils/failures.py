"""Failure handling: retry transient device errors, degrade on OOM.

The reference has no failure machinery of its own — it rides Spark's task
retry and lineage (SURVEY §5: "fully delegated to Spark"). There is no
Spark here, so the engine carries its own, sized to how a PJRT/TPU runtime
actually fails:

- **transient runtime errors** (UNAVAILABLE / DEADLINE_EXCEEDED from
  the PJRT client, a preempted or dropped connection): the program
  and its inputs are still on the host or reproducible from it, so the
  dispatch is safe to retry with backoff — the same property Spark exploits
  (pure per-task functions, ``DebugRowOps.scala:766-803``).
- **RESOURCE_EXHAUSTED (HBM OOM)**: retrying identically cannot help; the
  caller must shrink the work. ``map_rows`` halves its bucket chunks
  (row programs are per-row independent, so splitting is semantics-free);
  block ops surface the error with a hint, since a block program may
  compute cross-row statistics and must see the whole partition.

Coverage note — jax dispatch is asynchronous, so a retry window only sees
errors raised before it returns. Ops that materialize results promptly
(``map_rows`` chunks, the reduces, the distributed programs) synchronize
*inside* their retry windows and get full coverage. ``map_blocks`` keeps
results device-resident to pipeline chained passes; there, only
dispatch-time failures are retried, and an error during async execution
surfaces at the first materialization point instead.

Everything here is policy-free mechanics; knobs live in
:class:`tensorframes_tpu.utils.config.Config`.
"""

from __future__ import annotations

import random
import re
import threading
import time
from typing import Callable, Iterator, Optional, TypeVar

from .logging import get_logger

__all__ = [
    "adopt_retry_deadline",
    "current_retry_deadline",
    "first_line",
    "is_oom",
    "is_transient",
    "retry_deadline",
    "run_with_retries",
    "record_oom_split",
    "record_preemption",
    "seed_backoff_jitter",
    "DeadlineExceededError",
    "DeviceOOMError",
    "PagePoolExhausted",
    "QuarantinedBlocksError",
    "StaleLeaseError",
    "StaleRouterEpochError",
    "TenantThrottledError",
]

logger = get_logger("failures")

from ..obs import flight as _flight  # noqa: E402
from ..obs.metrics import counter as _counter  # noqa: E402

#: one series per (op, failure reason): makes flaky-link behavior
#: graphable instead of a stream of warnings
_retries_total = _counter(
    "failures.retries_total",
    "Transient device-runtime failures retried, by op and reason",
    labels=("op", "reason"),
)
_retries_exhausted_total = _counter(
    "failures.retries_exhausted_total",
    "Transient failures that ran out of retry attempts",
    labels=("op",),
)
_oom_splits_total = _counter(
    "failures.oom_splits_total",
    "OOM-degrade work-unit splits (chunk halvings / cap lowerings), by op",
    labels=("op",),
)
_preemptions_total = _counter(
    "failures.preemptions_total",
    "Work units preempted and requeued on resource exhaustion, by op",
    labels=("op",),
)


def record_oom_split(op: str) -> None:
    """Count one OOM-degrade split. The splits themselves happen in the
    engine (``map_rows`` chunk halving, raised-chunk lowering); the counter
    lives here with the rest of the failure telemetry."""
    _oom_splits_total.inc(op=op)


def first_line(err: object, limit: int = 200) -> str:
    """First line of ``str(err)``, bounded — the log/label/flight-ring
    rendering of an exception. split, not splitlines: an exception
    classified off its CAUSE chain can have an empty ``str(e)``, and
    ``"".splitlines()`` is ``[]``."""
    return str(err).split("\n", 1)[0][:limit]


def record_preemption(op: str) -> None:
    """Count one preempt-and-requeue. Like :func:`record_oom_split`, the
    preemption itself happens at the resource owner (the serving
    scheduler evicting a sequence when its KV page pool runs dry); the
    counter lives here with the rest of the failure telemetry."""
    _preemptions_total.inc(op=op)
    _flight.record("preemptions", "preempt", op=op)

T = TypeVar("T")

#: status substrings that mark a dispatch worth retrying (PJRT surfaces
#: grpc-style statuses in the exception text). Matching is
#: case-insensitive — PJRT renders ``UNAVAILABLE``, grpc-python
#: ``unavailable``, wrappers anything in between — so every marker is
#: stored lowercase and compared against lowered exception text.
_TRANSIENT_MARKERS = (
    "unavailable",
    "deadline_exceeded",
    "aborted",
    "connection reset",
    "connection refused",
    "socket closed",
)

_OOM_MARKERS = (
    "resource_exhausted",
    "out of memory",
)

#: "OOM" must match as a WORD: plain substring matching (the old
#: behavior) classified "zoom"/"room"/"Bloom filter" messages as device
#: OOMs once matching went case-insensitive
_OOM_WORD = re.compile(r"\boom\b")


def _exc_chain(e: BaseException) -> Iterator[BaseException]:
    """``e`` and its explicit causes (``raise X from Y``), cycle-safe.
    PJRT statuses often arrive wrapped — a retry decision must see
    through ``RuntimeError("dispatch failed") from <UNAVAILABLE>``.
    Implicit ``__context__`` links are deliberately NOT followed: an
    unrelated error raised while handling a transient one must not
    inherit its retryability."""
    seen = set()
    cur: "BaseException | None" = e
    while cur is not None and id(cur) not in seen and len(seen) < 8:
        seen.add(id(cur))
        yield cur
        cur = cur.__cause__


def _exc_text(e: BaseException) -> str:
    """Lowered text of the whole cause chain, for marker matching."""
    return "\n".join(str(x) for x in _exc_chain(e)).lower()


class DeviceOOMError(RuntimeError):
    """Device memory exhausted and the op cannot shrink its work unit."""


class PagePoolExhausted(DeviceOOMError):
    """The serving engine's KV page pool has no free page for a growing
    sequence. A RESOURCE_EXHAUSTED sibling, but of a pool this framework
    owns: retrying identically cannot help, and the remedy is not a
    split but an eviction — the scheduler preempts a running sequence
    (freeing its pages) and requeues it for recompute rather than
    crashing the batch (see :mod:`tensorframes_tpu.serve.scheduler`)."""


class QuarantinedBlocksError(RuntimeError):
    """A strict-mode batch job finished with quarantined blocks.

    Quarantine (``engine/jobs.py``) records a block whose program failed
    deterministically — non-transient, non-OOM after retries — in the
    job's quarantine manifest and skips it, so one poison block cannot
    kill a million-row job. In strict mode (``run_job(strict=True)`` or
    ``Config.quarantine_blocks=False``) the job still completes every
    healthy block and journals them, then raises this instead of
    returning partial results. ``blocks`` holds the
    :class:`~tensorframes_tpu.engine.jobs.QuarantinedBlock` records,
    each carrying the real underlying error."""

    def __init__(self, message: str, blocks=()):
        super().__init__(message)
        self.blocks = list(blocks)


class StaleLeaseError(RuntimeError):
    """An epoch-fenced write was rejected: the lease is not ours.

    Raised by the lease primitive (``utils/leases.py``) and both of its
    tenants — the distributed batch-job layer (``engine/dist_jobs.py``)
    and the serving fleet's member registry (``serve/membership.py``,
    where a fenced member's late registration write is the "zombie
    process" rejection) — in situations that share one meaning — *this
    process does not own the shared state it is about to mutate*:

    - a worker whose block lease expired and was **reclaimed** by
      another worker (epoch bumped) tries to record its late result:
      the write fence rejects the spool/ledger mutation, so a zombie
      can never land a torn or duplicate block record;
    - :func:`~tensorframes_tpu.engine.jobs.resume_job` is asked to
      touch a journal that live workers are still draining (or another
      resume holds the journal-level lease).

    Deliberately **non-transient**: retrying cannot help — the lease is
    gone (another worker owns the block now; its recompute is
    byte-identical) or the journal is owned by someone alive. The
    remedy is to move on to the next block / wait for the drain, never
    to retry the fenced write."""


class StaleRouterEpochError(StaleLeaseError):
    """A serving member rejected a placement carrying a superseded
    router epoch (``x-router-epoch`` below the router-election lease's
    current epoch, ``serve/router_ha.py``): the placing router was
    fenced and a standby took over at epoch+1, so this is a ZOMBIE
    router's placement — admitting it would double-generate a request
    the new active router already resubmitted from the WAL. A
    :class:`StaleLeaseError` sibling on purpose: same meaning (*this
    process does not own the shared state it is mutating*), same
    non-transient classification, and the fleet's failover path treats
    it as non-replayable — a fenced router retrying the same stale
    epoch elsewhere is refused everywhere. HTTP maps it to ``409
    Conflict`` (``interop/serving.py``)."""


class TenantThrottledError(RuntimeError):
    """A generation request was refused by the multi-tenant QoS plane
    (:mod:`tensorframes_tpu.serve.tenancy`): the tenant is over its
    admission quota, its token-bucket rate limit is empty, or an SLO
    shed is active for its priority class. A per-*tenant* condition,
    not a per-*server* one — the engine has capacity, this tenant may
    not use it right now — so HTTP maps it to ``429 Too Many
    Requests`` with a ``Retry-After`` derived from ``retry_after``
    (the bucket's refill time), distinct from the all-full 503.
    Deliberately terminal: never retried by ``run_with_retries`` and
    never replayed by the fleet router (a replay would re-charge the
    tenant's budget for work it was refused)."""

    def __init__(
        self, message: str, *, retry_after: float = 1.0,
        reason: str = "quota", tenant: str = "",
    ):
        super().__init__(message)
        #: seconds until the refusing limiter expects to admit again
        self.retry_after = float(retry_after)
        #: which gate refused: ``"quota"`` | ``"rate"`` | ``"shed"``
        self.reason = str(reason)
        self.tenant = str(tenant)


class DeadlineExceededError(TimeoutError):
    """A generation request outlived its caller-supplied deadline and was
    evicted by the serving scheduler (queued or mid-generation). A
    terminal, caller-facing condition — never retried (the deadline has
    already passed) and deliberately NOT classified transient, unlike a
    PJRT ``DEADLINE_EXCEEDED`` dispatch status, which marks a retryable
    device call. HTTP maps it to 504 (``interop/serving.py``)."""


def is_oom(e: BaseException) -> bool:
    if any(isinstance(x, DeviceOOMError) for x in _exc_chain(e)):
        return True
    s = _exc_text(e)
    return any(m in s for m in _OOM_MARKERS) or _OOM_WORD.search(s) is not None


def is_transient(e: BaseException) -> bool:
    # explicitly-terminal types veto the text markers anywhere in the
    # chain: a StaleLeaseError raised `from` an UNAVAILABLE cause must
    # not inherit that cause's retryability — the lease is gone
    if any(
        isinstance(
            x,
            (DeadlineExceededError, StaleLeaseError, TenantThrottledError),
        )
        for x in _exc_chain(e)
    ) or is_oom(e):
        return False
    s = _exc_text(e)
    return any(m in s for m in _TRANSIENT_MARKERS)


def _failure_reason(e: BaseException) -> str:
    """Short label for a classified failure: the matched status marker
    (normalized), or the exception type when no marker matched."""
    if is_oom(e):
        return "OOM"
    s = _exc_text(e)
    for m in _TRANSIENT_MARKERS:
        if m in s:
            return m.upper().replace(" ", "_")
    return type(e).__name__


def _op_label(what: str) -> str:
    """Bounded op label from a human ``what`` string: ``"map_blocks
    partition 3"`` must not mint one counter series per partition."""
    return what.split(" ", 1)[0] if what else "unknown"


#: RNG behind the retry backoff's full jitter. A dedicated instance (not
#: the global ``random``) so :func:`seed_backoff_jitter` can make chaos
#: tests deterministic without perturbing any other random consumer.
_jitter_rng = random.Random()


def seed_backoff_jitter(seed: Optional[int]) -> None:
    """Re-seed the retry-backoff jitter RNG. ``None`` restores
    OS-entropy seeding. Chaos tests call this so the (jittered) delay
    sequence is reproducible run to run."""
    global _jitter_rng
    _jitter_rng = random.Random(seed)


#: thread-local retry-deadline window (absolute time.monotonic() value):
#: :class:`retry_deadline` installs it so every ``run_with_retries``
#: window reached from the calling thread — however deep in the engine —
#: is bounded without threading a parameter through every call site
_retry_deadline_tl = threading.local()


class retry_deadline:
    """Bound every ``run_with_retries`` window entered from this thread
    to a wall-clock budget::

        with retry_deadline(lease_ttl_s * 0.8):
            ledger.run_block(i, compute)   # retries stop before the TTL

    The distributed-job worker wraps each block's compute in this so a
    retrying-but-alive lease holder gives up (and lets the job fail
    resumable / the block be retried next pass) *before* its lease
    deadline passes — otherwise a long transient burst would eat the
    whole TTL mid-retry, the worker would be presumed dead, and its
    block stolen while it still intended to write. Nests: the inner
    window is clipped to the outer one. ``None``/``<= 0`` is a no-op."""

    def __init__(self, seconds: Optional[float]):
        self._seconds = seconds
        self._prev: Optional[float] = None

    def __enter__(self) -> "retry_deadline":
        self._prev = getattr(_retry_deadline_tl, "deadline", None)
        if self._seconds is not None and self._seconds > 0:
            mine = time.monotonic() + self._seconds
            _retry_deadline_tl.deadline = (
                mine if self._prev is None else min(mine, self._prev)
            )
        return self

    def __exit__(self, *exc) -> None:
        _retry_deadline_tl.deadline = self._prev


def current_retry_deadline() -> Optional[float]:
    """The calling thread's absolute retry deadline (``time.monotonic``
    scale) installed by :class:`retry_deadline`, or ``None``. Layers
    that hand work to a thread pool capture this at submit time and
    re-install it in the pool thread with :class:`adopt_retry_deadline`
    — a thread-local does not cross executor boundaries on its own, and
    a retry window running unbounded on a pool thread would defeat the
    lease-TTL clipping the window exists for (``engine/dist_jobs.py``)."""
    return getattr(_retry_deadline_tl, "deadline", None)


class adopt_retry_deadline:
    """Install an ABSOLUTE deadline (from :func:`current_retry_deadline`)
    in this thread for the duration; clips to any window already
    present. ``None`` is a no-op."""

    def __init__(self, deadline: Optional[float]):
        self._deadline = deadline
        self._prev: Optional[float] = None

    def __enter__(self) -> "adopt_retry_deadline":
        self._prev = getattr(_retry_deadline_tl, "deadline", None)
        if self._deadline is not None:
            _retry_deadline_tl.deadline = (
                self._deadline
                if self._prev is None
                else min(self._deadline, self._prev)
            )
        return self

    def __exit__(self, *exc) -> None:
        _retry_deadline_tl.deadline = self._prev


def _effective_retry_deadline(
    deadline_s: Optional[float],
) -> Optional[float]:
    """Absolute monotonic deadline for one retry window: the explicit
    ``deadline_s`` argument and the thread-local :class:`retry_deadline`
    window, whichever ends first."""
    deadline = getattr(_retry_deadline_tl, "deadline", None)
    if deadline_s is not None and deadline_s > 0:
        mine = time.monotonic() + deadline_s
        deadline = mine if deadline is None else min(mine, deadline)
    return deadline


def _backoff_delay(attempt: int, base: float) -> float:
    """Full-jitter exponential backoff: uniform over
    ``(0.05 * cap, cap]`` where ``cap = base * 2**n``.

    The deterministic ``base * 2**n`` schedule retried *synchronized*
    failures in lockstep — every client that lost the same connection or TPU
    runtime slammed it again at the same instant, each round. Full
    jitter (the AWS-architecture result) decorrelates the herd while
    keeping the same cap per attempt. The floor is a sliver of the cap
    rather than 0 so a retry is never an immediate hot spin."""
    cap = base * (2.0 ** attempt)
    return _jitter_rng.uniform(0.05 * cap, cap)


def run_with_retries(
    fn: Callable[[], T],
    what: str = "device dispatch",
    deadline_s: Optional[float] = None,
) -> T:
    """Run ``fn``, retrying transient runtime failures with full-jitter
    exponential backoff per the config (``max_retries`` /
    ``retry_backoff_s``; see :func:`_backoff_delay`). Raises the last
    error when attempts run out; non-transient errors propagate
    immediately.

    ``deadline_s`` (and/or an enclosing :class:`retry_deadline` window —
    the tighter bound wins) caps the *wall clock* the retry loop may
    consume: a retry whose backoff sleep would land past the deadline is
    not attempted and the last transient error raises instead. The
    attempt in progress is never interrupted — this bounds the loop, not
    the dispatch."""
    from .config import get_config

    cfg = get_config()
    deadline = _effective_retry_deadline(deadline_s)
    attempt = 0
    while True:
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 — classified below
            out_of_time = deadline is not None and (
                time.monotonic() >= deadline
            )
            if (
                not is_transient(e)
                or attempt >= cfg.max_retries
                or out_of_time
            ):
                if is_transient(e):
                    _retries_exhausted_total.inc(op=_op_label(what))
                    _flight.record(
                        "retries", "exhausted", what=what,
                        attempts=attempt + 1, error=first_line(e),
                    )
                    if out_of_time:
                        logger.warning(
                            "%s: retry deadline reached after %d "
                            "attempt(s); giving up on the transient error",
                            what, attempt + 1,
                        )
                raise
            delay = _backoff_delay(attempt, cfg.retry_backoff_s)
            if deadline is not None and time.monotonic() + delay >= deadline:
                _retries_exhausted_total.inc(op=_op_label(what))
                # this exhaustion must reach the flight ring too — a
                # bundle whose counters say "exhausted" but whose
                # retries ring shows none contradicts itself
                _flight.record(
                    "retries", "exhausted", what=what,
                    attempts=attempt + 1, reason="deadline",
                    error=first_line(e),
                )
                logger.warning(
                    "%s: backoff of %.2fs would pass the retry deadline; "
                    "giving up after %d attempt(s)",
                    what, delay, attempt + 1,
                )
                raise
            attempt += 1
            _retries_total.inc(op=_op_label(what), reason=_failure_reason(e))
            _flight.record(
                "retries", "retry", what=what, attempt=attempt,
                reason=_failure_reason(e), delay_s=round(delay, 4),
            )
            # split, not splitlines: an exception classified off its CAUSE
            # chain can have an empty str(e), and "".splitlines() is []
            logger.warning(
                "%s failed with a transient error (%s); retry %d/%d in %.1fs",
                what,
                str(e).split("\n", 1)[0][:200],
                attempt,
                cfg.max_retries,
                delay,
            )
            time.sleep(delay)
