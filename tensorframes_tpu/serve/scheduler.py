"""Continuous-batching scheduler: admission queue, slots, preemption.

Pure host logic — no jax imports — so the batching policy is unit-
testable without compiling anything. The
:class:`~tensorframes_tpu.serve.engine.GenerationEngine` drives it:

- :meth:`Scheduler.submit` parks requests in a BOUNDED admission queue
  (a full queue rejects or blocks — backpressure instead of unbounded
  host memory, the same stance the scoring server takes with its
  connection semaphore).
- :meth:`Scheduler.admit` moves queued requests into free decode slots,
  reserving prompt pages; with a :class:`~.kv_pages.PrefixCache`
  attached, the longest cached page-aligned prefix of the prompt is
  refcount-shared into the new sequence first, and only the uncached
  remainder is allocated fresh.
- :meth:`Scheduler.grow` reserves the next decode position's page for a
  running sequence; on :class:`PagePoolExhausted` it first EVICTS
  prefix-cache entries (cold cached prefixes go before live work), then
  PREEMPTS the youngest other sequence — pages freed, request requeued
  at the FRONT of the queue with its progress folded into the prompt
  (recompute-style preemption: the re-admitted prefill replays prompt +
  emitted tokens, so the consumer's stream continues without replay or
  loss).

Preemption rides the failure taxonomy in ``utils/failures.py``
(:func:`record_preemption`, :class:`PagePoolExhausted`) — pool
exhaustion is a RESOURCE_EXHAUSTED condition the scheduler degrades
through, never a crash.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, List, Optional, Tuple

import numpy as np

from ..utils.failures import (
    DeadlineExceededError,
    PagePoolExhausted,
    record_preemption,
)
from . import tenancy as _tenancy
from .kv_pages import PagePool, SequencePages, pages_needed

__all__ = [
    "GenerationHandle",
    "GenRequest",
    "QueueFullError",
    "Scheduler",
]


class QueueFullError(RuntimeError):
    """The bounded admission queue is at capacity (non-blocking submit)."""


class GenerationHandle:
    """The caller's end of one request: a token stream plus completion
    state. Iterating yields generated token ids as the engine emits them;
    :meth:`result` blocks for the full generation."""

    _DONE = object()

    def __init__(self, request_id: int):
        self.request_id = request_id
        self._q: "queue.Queue" = queue.Queue()
        self._tokens: List[int] = []
        self._done = threading.Event()
        self._error: Optional[BaseException] = None
        #: per-request timing breakdown, filled by the engine as the
        #: stream progresses: ``queue_wait_s`` (submit -> first admit),
        #: ``prefill_s`` (sum of prefill dispatch walls — replays and
        #: recompute-style preemptions accumulate), ``prefill_chunks``
        #: (chunked-prefill dispatches), ``decode_s`` (sum of
        #: inter-emission gaps), ``replays`` (fleet failovers); why and
        #: how long it waited in the queue, first admission and every
        #: re-admission (``wait_slots_s`` / ``wait_pages_s`` by what
        #: stopped :meth:`Scheduler.admit`, ``requeue_wait_s`` the part
        #: after a preemption) and what preemption cost it
        #: (``preemptions``, ``prefill_tokens`` put through a prefill
        #: program, ``recomputed_tokens`` of them computed before) — plus
        #: the cost-attribution keys the engine's finish hook records
        #: (``tokens``, ``kv_pages``, ``prefix_cached_tokens``,
        #: ``est_flops``, ``tenant``; ``obs/requests.py``). The
        #: serving endpoint echoes this dict in the HTTP response
        #: (docs/observability.md).
        self.timings: dict = {}

    # -- engine side -------------------------------------------------------

    def _emit(self, token: int) -> None:
        self._tokens.append(int(token))
        self._q.put(int(token))

    def _finish(self, error: Optional[BaseException] = None) -> None:
        self._error = error
        self._done.set()
        self._q.put(self._DONE)

    # -- caller side -------------------------------------------------------

    @property
    def done(self) -> bool:
        return self._done.is_set()

    @property
    def error(self) -> Optional[BaseException]:
        return self._error

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is self._DONE:
                if self._error is not None:
                    raise self._error
                return
            yield item

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Generated tokens (prompt excluded), blocking until done."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self.request_id} not done within {timeout}s"
            )
        if self._error is not None:
            raise self._error
        return np.asarray(self._tokens, np.int32)


@dataclass
class GenRequest:
    """One admission-queue entry. ``prompt`` already includes any tokens
    generated before a preemption (recompute-style requeue), and
    ``emitted`` counts them so re-admission emits only NEW tokens."""

    request_id: int
    prompt: np.ndarray  # [plen] int32
    max_new_tokens: int
    temperature: float = 0.0
    top_p: float = 1.0
    seed: int = 0
    eos_id: Optional[int] = None
    handle: GenerationHandle = None  # type: ignore[assignment]
    submitted_at: float = field(default_factory=time.monotonic)
    emitted: int = 0  # tokens already streamed (pre-preemption progress)
    #: absolute ``time.monotonic()`` deadline, or None for no deadline;
    #: the engine's step sweep evicts expired requests (queued OR
    #: mid-generation) with :class:`DeadlineExceededError`
    deadline_t: Optional[float] = None
    #: the request's :class:`~tensorframes_tpu.obs.TraceContext` — the
    #: engine's per-request spans (prefill, prefill chunks) join this
    #: trace on the stepping thread, so one trace_id follows the request
    #: from the HTTP ingress through placement, prefill, and any
    #: failover replay (docs/observability.md)
    trace: Optional[object] = None
    #: cost-attribution key (``obs/requests.py``): who this request is
    #: billed to. The serving layer defaults it to the fleet session id
    #: when the client names no tenant; empty means unattributed.
    tenant: str = ""
    #: scheduling rank from the tenant's QoS policy at submission
    #: (``serve/tenancy.py`` ``PRIORITIES``: 0 batch, 1 standard,
    #: 2 interactive). With the QoS plane off every request carries the
    #: default 1 and ordering degenerates to pure FIFO.
    priority: int = 1
    #: leading prompt tokens a preemption had already computed once (the
    #: folded-in progress, and the prompt it was prefilled with): what a
    #: re-admitted prefill of them recomputes. 0 for a fresh request.
    computed: int = 0
    #: ``time.monotonic()`` up to which this request's time in the queue
    #: is charged (:meth:`Scheduler.charge_wait`); None = ``submitted_at``
    wait_mark: Optional[float] = None
    #: what stopped the last :meth:`Scheduler.admit` that left this
    #: request waiting: ``"slots"`` or ``"pages"``. A request admit never
    #: refused waited for the step in progress to end, and the batch
    #: admits at step boundaries only: that is charged to the slots.
    wait_reason: str = "slots"


class _Active:
    """A slot's running sequence: request + page holdings + progress."""

    __slots__ = (
        "req", "seq", "generated", "admit_order", "last_emit_t",
        "prefill_pos", "cached_tokens", "cow_src", "draft_pos", "spec_k",
    )

    def __init__(self, req: GenRequest, seq: SequencePages, admit_order: int):
        self.req = req
        self.seq = seq
        self.generated: List[int] = []
        self.admit_order = admit_order
        self.last_emit_t: Optional[float] = None
        #: prompt positions whose k/v are already in this sequence's
        #: pages (a prefix-cache hit starts this > 0; chunked prefill
        #: advances it one chunk per engine step until it reaches the
        #: prompt length). The slot joins the decode batch only once the
        #: first token is emitted (``generated`` non-empty).
        self.prefill_pos = 0
        #: prompt positions covered by the prefix cache at admission
        self.cached_tokens = 0
        #: donor page to copy-on-write before prefilling (a cached
        #: prefix that ends inside this page); carries one temporary
        #: pool reference the holder must drop — the engine drops it
        #: after cloning, finish/preempt drop it when the slot dies
        #: first
        self.cow_src: Optional[int] = None
        #: SPECULATIVE-length bookkeeping (the engine's draft model,
        #: docs/serving_llm.md "Speculative decoding"): positions whose
        #: DRAFT-model KV is valid. Host state only — a preemption or
        #: restart re-admits through a fresh ``_Active``, so rejected or
        #: stale speculative draft KV "rolls back" by this counter (and
        #: the page tables) resetting, never by undoing page writes. A
        #: prefix-cache hit seeds it at ``cached_tokens`` (the shared
        #: pages carry the donor's draft KV rows too).
        self.draft_pos = 0
        #: the per-slot ADAPTIVE draft length: -1 until the engine's
        #: first speculative step seeds it from the compiled static k;
        #: the controller shrinks it on cold (low-acceptance) slots and
        #: grows it back on hot ones, bounded by the static k. Dies with
        #: the slot like ``draft_pos``.
        self.spec_k = -1

    @property
    def length(self) -> int:
        """Positions written to the KV pages so far."""
        return len(self.req.prompt) + len(self.generated)

    @property
    def remaining(self) -> int:
        return self.req.max_new_tokens - len(self.generated)


class Scheduler:
    """Slot + queue + page bookkeeping for one decode batch. Thread-safe
    for concurrent :meth:`submit`; the step-side methods (:meth:`admit`,
    :meth:`grow`, :meth:`finish`) are called by the engine's single
    stepping thread."""

    def __init__(
        self,
        pool: PagePool,
        max_slots: int,
        queue_capacity: int,
        max_seq_len: int,
        prefix_cache=None,
        layout=None,
    ):
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1; got {max_slots}")
        self.pool = pool
        self.max_slots = int(max_slots)
        self.max_seq_len = int(max_seq_len)
        self.queue_capacity = int(queue_capacity)
        #: optional :class:`~.kv_pages.PrefixCache`: admission shares
        #: cached prompt-prefix pages into new sequences, and pool
        #: exhaustion evicts cache entries before preempting live work
        self.prefix_cache = prefix_cache
        #: the model's cache kinds (:class:`~.kv_pages.CacheLayout`);
        #: None = one kind that keeps every position. Admission,
        #: :meth:`grow` and the feasibility check count every kind's
        #: pages, all from the one pool
        self.layout = layout
        self.slots: List[Optional[_Active]] = [None] * self.max_slots
        self._waiting: Deque[GenRequest] = deque()
        self._lock = threading.Condition()
        self._admit_counter = 0
        #: optional ``fn(act, error)`` called by :meth:`finish` while
        #: the slot still holds its pages — the engine hangs its
        #: per-request cost attribution here (page count, token totals)
        #: without the scheduler importing any observability
        self.on_request_done = None
        #: optional ``fn(victim_idx) -> bool`` consulted by :meth:`grow`
        #: BEFORE preempting a pool-pressure victim: return True after
        #: having freed the victim's pages some other way (the fleet
        #: installs live KV-page migration here, ``serve/tiers.py`` —
        #: the victim's stream continues on another replica instead of
        #: paying a recompute-style preemption). False, an exception,
        #: or no hook falls through to :meth:`preempt` — preemption is
        #: always the fallback, never removed.
        self.on_pressure = None
        #: optional ``fn()`` called before a request leaves its slot or
        #: the queue any other way than the engine's own finish:
        #: :meth:`finish`, :meth:`preempt`, :meth:`expire` with something
        #: to evict, :meth:`fail_all`. The engine delivers there what its
        #: steps still owe the handles (tokens produced and not yet
        #: handed over), so no end mark overtakes a token
        self.before_release = None
        #: why the last :meth:`admit` left requests waiting: ``"slots"``
        #: (every slot taken), ``"pages"`` (the pool could not supply
        #: the head's prompt pages), None (the queue drained)
        self.blocked_on: Optional[str] = None
        #: preemptions so far (the engine's ``serve.grow`` span reads
        #: the difference across one grow loop)
        self.preemptions = 0

    # -- admission ---------------------------------------------------------

    def submit(
        self,
        req: GenRequest,
        block: bool = True,
        timeout: Optional[float] = None,
    ) -> None:
        """Park ``req`` in the admission queue. A full queue blocks (the
        default — backpressure to the producer) or raises
        :class:`QueueFullError` with ``block=False``."""
        total = len(req.prompt) + req.max_new_tokens
        if total > self.max_seq_len:
            raise ValueError(
                f"prompt ({len(req.prompt)}) + max_new_tokens "
                f"({req.max_new_tokens}) = {total} exceeds max_seq_len "
                f"{self.max_seq_len}"
            )
        need = (
            pages_needed(total, self.pool.page_size)
            if self.layout is None
            else self.layout.units_needed(total)
        )
        if need > self.pool.num_pages:
            raise ValueError(
                f"request needs {need} "
                f"pages at full length but the pool holds only "
                f"{self.pool.num_pages} — it could never be scheduled"
            )
        with self._lock:
            deadline = None if timeout is None else time.monotonic() + timeout
            while len(self._waiting) >= self.queue_capacity:
                if not block:
                    raise QueueFullError(
                        f"admission queue full "
                        f"({self.queue_capacity} requests waiting)"
                    )
                rem = None if deadline is None else deadline - time.monotonic()
                if rem is not None and rem <= 0:
                    raise QueueFullError(
                        f"admission queue still full after {timeout}s"
                    )
                self._lock.wait(rem)
            self._waiting.append(req)
            self._lock.notify_all()

    def _requeue_front(self, req: GenRequest) -> None:
        """Preempted requests skip the line — they already waited once and
        hold the earliest arrival times. The queue bound is deliberately
        ignored here: a preemption must never deadlock against a full
        queue (the pages are already released; the request has nowhere
        else to live)."""
        with self._lock:
            self._waiting.appendleft(req)
            self._lock.notify_all()

    # -- stepping side -----------------------------------------------------

    @property
    def queue_depth(self) -> int:
        with self._lock:
            return len(self._waiting)

    @property
    def active(self) -> List[Tuple[int, _Active]]:
        """(slot index, active sequence) pairs, oldest admission first —
        the decode order, and the inverse of the preemption order."""
        pairs = [
            (i, a) for i, a in enumerate(self.slots) if a is not None
        ]
        pairs.sort(key=lambda p: p[1].admit_order)
        return pairs

    def has_work(self) -> bool:
        return any(s is not None for s in self.slots) or self.queue_depth > 0

    def admit(self) -> List[Tuple[int, _Active]]:
        """Fill free slots from the queue head, reserving each admitted
        prompt's pages. Stops at the first request whose prompt pages the
        pool cannot supply right now (it keeps its queue position; active
        sequences finishing will free pages — preemption is only for
        sequences already mid-flight, see :meth:`grow`). Returns the new
        (slot, active) pairs for the engine to prefill."""
        admitted: List[Tuple[int, _Active]] = []
        # what leaves requests waiting, if any are left at the end: the
        # loop ran out of free slots, unless the head's pages stopped it
        blocked: Optional[str] = "slots"
        for idx in range(self.max_slots):
            if self.slots[idx] is not None:
                continue
            with self._lock:
                if not self._waiting:
                    break
                if _tenancy.enabled():
                    # (priority, arrival): highest class first, and
                    # WITHIN a class the frontmost queue position —
                    # deque order is the arrival proxy, so preempted
                    # requests (requeued at the front) keep their
                    # earned seniority
                    best = max(
                        range(len(self._waiting)),
                        key=lambda j: (self._waiting[j].priority, -j),
                    )
                    req = self._waiting[best]
                    del self._waiting[best]
                else:
                    req = self._waiting.popleft()
                self._lock.notify_all()
            seq = SequencePages(self.pool, self.layout)
            cow_src: Optional[int] = None
            cached = 0
            if self.prefix_cache is not None:
                shared, cow_src, cached = self.prefix_cache.acquire(
                    req.prompt
                )
                seq.pages = shared  # refcounted by acquire; release() frees
            try:
                try:
                    seq.ensure(len(req.prompt))
                except PagePoolExhausted:
                    if self.prefix_cache is None:
                        raise
                    # cold cached prefixes go before live admissions —
                    # but only the SHORTFALL beyond the pool's free
                    # pages, so warm prefixes the pool could keep are
                    # not over-evicted; the retried ensure re-raises if
                    # eviction could not cover it
                    missing = pages_needed(
                        len(req.prompt), self.pool.page_size
                    ) - len(seq.pages)
                    shortfall = missing - self.pool.pages_free
                    if shortfall > 0:
                        self.prefix_cache.evict_pages(shortfall)
                    seq.ensure(len(req.prompt))
            except PagePoolExhausted:
                if cow_src is not None:
                    self.pool.free([cow_src])
                seq.release()
                self._requeue_front(req)
                blocked = "pages"
                break
            self.charge_wait(req, time.monotonic())
            act = _Active(req, seq, self._admit_counter)
            act.cached_tokens = cached
            act.cow_src = cow_src
            self._admit_counter += 1
            self.slots[idx] = act
            admitted.append((idx, act))
        now = time.monotonic()
        with self._lock:
            if not self._waiting:
                blocked = None
            for r in self._waiting:
                # behind a blocked head: the head's reason
                self.charge_wait(r, now, blocked)
        self.blocked_on = blocked
        return admitted

    @staticmethod
    def charge_wait(
        req: GenRequest, now: float, reason: Optional[str] = None
    ) -> None:
        """Charge ``req``'s time in the queue since its last charge to
        ``wait_<reason>_s`` on its handle (and to ``requeue_wait_s``
        after a preemption). ``reason`` None keeps the reason it
        carries: :meth:`admit` calls this for an admitted request, and
        the engine once more when the request's prefill starts (the
        moment ``queue_wait_s`` is taken), so that the two buckets add
        up to at least ``queue_wait_s``."""
        if reason is not None:
            req.wait_reason = reason
        mark = req.submitted_at if req.wait_mark is None else req.wait_mark
        req.wait_mark = now
        dt = now - mark
        if dt <= 0.0:
            return
        t = req.handle.timings
        key = "wait_pages_s" if req.wait_reason == "pages" else "wait_slots_s"
        t[key] = t.get(key, 0.0) + dt
        if "preemptions" in t:
            t["requeue_wait_s"] = t.get("requeue_wait_s", 0.0) + dt

    def grow(self, idx: int) -> bool:
        """Reserve the page holding slot ``idx``'s next decode position,
        preempting the YOUNGEST other active sequence per retry until the
        pool yields one. Returns False when ``idx``'s own sequence got
        preempted (it was the youngest left — the caller drops it from
        this step's batch)."""
        act = self.slots[idx]
        assert act is not None
        if self.layout is not None:
            # the next query is the pending token's: a window kind gives
            # back the pages that fell wholly behind its window
            act.seq.advance(act.length - 1)
        while True:
            try:
                # the pending token writes at position length - 1 (its
                # ``generated`` entry exists but is not yet in the cache)
                act.seq.ensure(act.length)
                return True
            except PagePoolExhausted:
                if (
                    self.prefix_cache is not None
                    and self.prefix_cache.evict_pages(1) > 0
                ):
                    continue  # a cold cached prefix paid instead
                victim_idx = self._victim_slot(exclude=idx)
                if victim_idx is None:
                    # nothing left to evict but the requester itself; its
                    # full-length feasibility was checked at submit, so
                    # alone it always fits — reaching here means it is
                    # NOT alone in page ownership yet no slot can be
                    # preempted, which cannot happen with slot-owned pages
                    self.preempt(idx)
                    return False
                if self.on_pressure is not None:
                    try:
                        if self.on_pressure(victim_idx):
                            # the victim's pages were freed by migration
                            # (its stream continues elsewhere) — retry
                            # the reservation before preempting anyone
                            continue
                    except Exception:
                        # a broken hook degrades to the ladder it
                        # fronts; it must never wedge the step loop
                        pass
                if self.slots[victim_idx] is None:
                    continue  # the hook consumed the victim after all
                self.preempt(victim_idx)

    def _youngest_active(self, exclude: int) -> Optional[int]:
        """Most recently admitted slot other than ``exclude`` — the
        preemption victim (least progress lost, and the inverse of
        admission order keeps the policy starvation-free: the evicted
        request re-enters at the queue FRONT)."""
        best, best_order = None, -1
        for i, a in enumerate(self.slots):
            if a is None or i == exclude:
                continue
            if a.admit_order > best_order:
                best, best_order = i, a.admit_order
        return best

    def _victim_slot(self, exclude: int) -> Optional[int]:
        """The preemption victim other than ``exclude``. QoS plane off:
        exactly :meth:`_youngest_active`. Plane on: lowest-PRIORITY
        slot first, youngest within a class — an interactive stream is
        never evicted while a batch slot can pay, and within one class
        the least progress is lost (still starvation-free: victims
        requeue at the front and re-admit ahead of their class)."""
        if not _tenancy.enabled():
            return self._youngest_active(exclude)
        best: Optional[int] = None
        best_key: Optional[Tuple[int, int]] = None
        for i, a in enumerate(self.slots):
            if a is None or i == exclude:
                continue
            key = (a.req.priority, -a.admit_order)
            if best_key is None or key < best_key:
                best, best_key = i, key
        return best

    def tenant_counts(self) -> Tuple[dict, dict]:
        """Per-tenant footprint: ({tenant: active slots},
        {tenant: queued requests}) — the admission gate's quota input
        and the ``/statusz`` per-tenant view."""
        active: dict = {}
        with self._lock:
            for a in self.slots:
                if a is not None:
                    active[a.req.tenant] = active.get(a.req.tenant, 0) + 1
            queued: dict = {}
            for r in self._waiting:
                queued[r.tenant] = queued.get(r.tenant, 0) + 1
        return active, queued

    def preempt(self, idx: int) -> GenRequest:
        """Evict slot ``idx``: release its pages and requeue the request
        at the queue front with progress folded into the prompt (the
        handle keeps streaming; re-admission emits only new tokens)."""
        act = self.slots[idx]
        assert act is not None
        self._settle()
        self._drop_cow(act)
        act.seq.release()
        self.slots[idx] = None
        req = act.req
        t = req.handle.timings
        t["preemptions"] = t.get("preemptions", 0) + 1
        self.preemptions += 1
        new_req = GenRequest(
            request_id=req.request_id,
            prompt=np.concatenate(
                [req.prompt, np.asarray(act.generated, np.int32)]
            ),
            max_new_tokens=req.max_new_tokens - len(act.generated),
            temperature=req.temperature,
            top_p=req.top_p,
            seed=req.seed,
            eos_id=req.eos_id,
            handle=req.handle,
            submitted_at=req.submitted_at,
            emitted=req.emitted + len(act.generated),
            deadline_t=req.deadline_t,
            trace=req.trace,
            tenant=req.tenant,
            priority=req.priority,
            # what its KV held: the prompt as far as it was prefilled,
            # and what it had generated since
            computed=max(
                req.computed,
                min(act.prefill_pos, len(req.prompt)) + len(act.generated),
            ),
            wait_mark=time.monotonic(),
        )
        record_preemption("serve")
        _tenancy.count_preemption(req.priority)
        self._requeue_front(new_req)
        return new_req

    def detach(self, idx: int) -> _Active:
        """Release slot ``idx``'s pages WITHOUT closing its handle or
        requeueing its request: the caller owns what happens to the
        handle next. Live migration (``serve/tiers.py``) has already
        serialized the slot's state and re-materializes it on another
        replica, where the SAME handle keeps streaming; the engine's
        own finish frees the slot for the next admission now and
        closes the handle when it delivers (``engine._emit``). Unlike
        :meth:`finish` this runs no terminal accounting and unlike
        :meth:`preempt` it records no preemption — nothing was lost.
        Returns the detached :class:`_Active` for the caller's
        bookkeeping."""
        act = self.slots[idx]
        assert act is not None
        self._drop_cow(act)
        act.seq.release()
        self.slots[idx] = None
        return act

    def _settle(self) -> None:
        """Run :attr:`before_release`; like the other hooks, a broken
        one must not leak pages or hang a handle."""
        if self.before_release is not None:
            try:
                self.before_release()
            except Exception:
                pass

    def _drop_cow(self, act: _Active) -> None:
        """Release a pending copy-on-write donor reference (taken by
        ``PrefixCache.acquire``) when the slot dies before the engine
        cloned the page. Idempotent — the engine clears ``cow_src``
        itself after cloning."""
        if act.cow_src is not None:
            self.pool.free([act.cow_src])
            act.cow_src = None

    def finish(self, idx: int, error: Optional[BaseException] = None) -> None:
        """Terminal slot release: pages back to the pool, handle closed.
        ``on_request_done`` observes the slot first (pages still held,
        so holdings are countable); its failures are swallowed — an
        accounting bug must not leak pages or hang a handle."""
        act = self.slots[idx]
        assert act is not None
        self._settle()
        if self.on_request_done is not None:
            try:
                self.on_request_done(act, error)
            except Exception:
                pass
        self._drop_cow(act)
        act.seq.release()
        self.slots[idx] = None
        act.req.handle._finish(error)

    # -- supervision -------------------------------------------------------

    def expire(self, now: float) -> int:
        """Evict every request whose deadline has passed: queued requests
        are failed in place (their handle raises
        :class:`DeadlineExceededError`), active ones release their slot
        and pages too. Returns the number evicted. Called from the
        engine's step sweep, so an expired request is gone within one
        step — it never occupies a slot the live traffic needs."""
        expired: List[GenRequest] = []
        with self._lock:
            if self._waiting:
                keep: Deque[GenRequest] = deque()
                for r in self._waiting:
                    if r.deadline_t is not None and now >= r.deadline_t:
                        expired.append(r)
                    else:
                        keep.append(r)
                if expired:
                    self._waiting = keep
                    self._lock.notify_all()  # queue shrank: wake submitters
        if expired:
            self._settle()
        for r in expired:
            r.handle._finish(
                DeadlineExceededError(
                    f"request {r.request_id} exceeded its deadline while "
                    f"queued for admission"
                )
            )
        n = len(expired)
        for i, a in enumerate(self.slots):
            if (
                a is not None
                and a.req.deadline_t is not None
                and now >= a.req.deadline_t
            ):
                self.finish(
                    i,
                    error=DeadlineExceededError(
                        f"request {a.req.request_id} exceeded its deadline "
                        f"mid-generation ({len(a.generated)} of "
                        f"{a.req.max_new_tokens} tokens emitted)"
                    ),
                )
                n += 1
        return n

    def fail_all(self, error: BaseException) -> int:
        """Terminal sweep: fail EVERY in-flight request — active slots
        and the whole admission queue — with ``error``, releasing their
        pages. Returns how many handles were closed. The supervisor's
        fail-fast path: a consumer must see a doomed engine's real error
        within a step, not hang to its timeout."""
        n = 0
        self._settle()
        for i, a in enumerate(self.slots):
            if a is not None:
                self.finish(i, error=error)
                n += 1
        with self._lock:
            drained = list(self._waiting)
            self._waiting.clear()
            if drained:
                self._lock.notify_all()
        for r in drained:
            r.handle._finish(error)
        return n + len(drained)
