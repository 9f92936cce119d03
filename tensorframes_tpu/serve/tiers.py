"""Live KV-page migration between serving replicas — the tier primitive.

Prompt ingestion (prefill) and token generation (decode) load move on
different curves, but a monolithic fleet makes every replica do both, so
a prefill burst stalls every concurrent stream's inter-token latency and
idle decode capacity cannot absorb it. This module is the primitive that
decouples them: one slot's COMPLETE decode state — request fields,
generated tokens, and the physical KV page rows (target model AND every
attached page group, e.g. the speculative draft's) — is serialized to
host memory (:func:`export_slot`), shipped through the streaming
transfer layer (``frame/transfer.py``: chunked, retried,
chaos-injectable at ``frame.h2d`` / ``frame.d2h``), and re-materialized
into a free slot on another replica (:func:`restore_slot`), where
generation continues **byte-identically**.

Why byte-identity holds: at a step boundary a slot's KV is valid for
positions ``[0, length - 2]`` and the newest generated token's KV write
is pending (the next decode writes it at ``length - 1``). The page
bytes plus ``prompt`` / ``generated`` / the sampling params therefore
fully determine the continuation — per-step sampling keys fold at
ABSOLUTE positions (``engine._sample_slot_tokens``), so greedy and
seeded streams alike continue exactly where they left off. Speculative
decoding keeps the property for free (exact-match acceptance never
changes emitted bytes; a draft group that cannot be restored just
resets ``draft_pos`` and re-ingests, degrading proposals, never
tokens). Heterogeneous tensor-parallel degrees work because pages are
exported at LOGICAL geometry — ``d2h`` gathers a sharded pool array
whole, and the import re-pins rows under the destination pool's own
KV-head sharding via ``place()``.

Two consumers (``serve/fleet.py``):

- **tier handoff** — a request prefills on a prefill-tier replica and
  migrates to a decode-tier replica at first token, so prefill bursts
  and decode streams stop contending for the same step loop;
- **decode rebalancing** — under pool pressure the scheduler offers its
  chosen preemption victim to ``Scheduler.on_pressure`` first: the
  fleet exports the victim's pages (freeing them synchronously, which
  is all ``grow`` needed) and re-imports them on the least-loaded
  decode replica, so the victim keeps its KV instead of paying a
  recompute-style preemption. Preemption stays the fallback — a failed
  import parks the record on the ordinary failover/replay path.

Chaos: ``tier.handoff`` fires inside both the export read and the
import write retry windows (reads are side-effect free; the write is
idempotent — re-setting the same rows), so a ``transient`` retries
invisibly and a ``fatal`` aborts the migration into the fallback
ladder. See docs/serving_llm.md "Disaggregated tiers".
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..frame.transfer import d2h as _d2h, h2d as _h2d, wire_dtype as _wire
from ..obs import span as _span
from ..obs.metrics import counter as _counter, histogram as _histogram
from ..utils import chaos as _chaos
from ..utils.failures import run_with_retries
from ..utils.logging import get_logger
from .kv_pages import SequencePages
from .scheduler import GenerationHandle, GenRequest, QueueFullError, _Active

__all__ = [
    "SlotSnapshot",
    "TIERS",
    "TierMigrationError",
    "export_slot",
    "restore_slot",
]

logger = get_logger("serve.tiers")

#: replica roles (``Fleet(tiers=...)`` / ``MemberAgent(tier=...)``):
#: ``prefill`` takes new requests and hands off at first token;
#: ``decode`` takes migrated streams (and new requests only when no
#: prefill capacity is healthy); ``mixed`` (the default) does both —
#: a fleet whose replicas are all ``mixed`` routes exactly like the
#: pre-tier router
TIERS = ("prefill", "decode", "mixed")

_m_migrations = _counter(
    "serve.kv_migrations_total",
    "Completed KV-page slot migrations by reason (handoff = prefill->"
    "decode tier transfer, rebalance = pool-pressure move, failed = "
    "aborted migrations that fell back to replay/preemption)",
    labels=("reason",),
)
_m_migration_s = _histogram(
    "serve.migration_seconds",
    "End-to-end wall of one slot migration: export (page d2h + detach) "
    "through restore (alloc + page write + slot attach)",
)


class TierMigrationError(RuntimeError):
    """A slot cannot migrate to this destination (geometry mismatch,
    unhealthy engine, infeasible length). Deliberately NOT transient:
    the caller falls back to replay or preemption, never retries the
    same doomed pairing."""


@dataclasses.dataclass
class SlotSnapshot:
    """One slot's complete migratable state, all host-side.

    ``k`` / ``v`` are ``[n_layers, n_pages, page_size, n_kv_heads,
    head_dim]`` rows gathered from the source pool in page-list order
    (LOGICAL geometry, whatever the pool's device layout is —
    ``PagePool.take_pages`` / ``put_pages`` convert, so a snapshot
    taken before the pool merged heads into its lane axis still
    restores; TP shards are merged by the export gather);
    ``groups`` maps each page-group name (e.g. ``"draft"``) to its own
    ``(k, v)`` row pair. Request fields are carried verbatim so the
    destination's :class:`~.scheduler.GenRequest` continues the same
    deadline / seed / budget arithmetic."""

    request_id: int
    prompt: np.ndarray
    generated: List[int]
    emitted: int
    max_new_tokens: int
    temperature: float
    top_p: float
    seed: int
    eos_id: Optional[int]
    tenant: str
    priority: int
    deadline_t: Optional[float]
    submitted_at: float
    trace: Optional[object]
    page_size: int
    k: np.ndarray
    v: np.ndarray
    groups: Dict[str, Tuple[np.ndarray, np.ndarray]]
    draft_pos: int
    reason: str
    source: str
    started_t: float

    @property
    def n_pages(self) -> int:
        return int(self.k.shape[1])

    @property
    def nbytes(self) -> int:
        n = self.k.nbytes + self.v.nbytes
        for gk, gv in self.groups.values():
            n += gk.nbytes + gv.nbytes
        return n


def _find_slot(engine, request_id: int):
    for idx, act in enumerate(engine.scheduler.slots):
        if act is not None and act.req.request_id == request_id:
            return idx, act
    return None, None


def export_slot(engine, request_id: int, reason: str = "handoff"):
    """Serialize and DETACH one decode-phase slot from ``engine``.

    Under the engine's step lock (re-entrant, so the scheduler's
    ``on_pressure`` hook may call this from inside ``grow``): gather
    the slot's page rows to host through the transfer layer, then
    release the slot WITHOUT closing its handle — the pages return to
    the source pool immediately and the stream continues wherever the
    snapshot is restored. Returns ``None`` when the request is not in
    a migratable state (unknown id, still prefilling, pending
    copy-on-write clone) — the caller falls back to its ordinary
    ladder. Raises only on a non-transient transfer failure."""
    with engine._step_lock:
        # the handle is about to stream from another engine: what this
        # one's steps still owe it goes out first, or the relay that
        # carries it would be stale by the time it is delivered
        engine._deliver_now()
        idx, act = _find_slot(engine, request_id)
        if act is None:
            return None
        if not act.generated or act.cow_src is not None:
            # mid-prefill (chunked) or pre-clone: the cheap recompute
            # path (replay/preempt) beats moving half-built state
            return None
        if getattr(engine, "layout", None) is not None:
            # a snapshot carries one cache kind's pages; a model with
            # window layers holds two: not migratable, the caller's
            # ordinary ladder (preempt and recompute) applies
            return None
        t0 = time.monotonic()
        pool = engine.pool
        rows = np.asarray(act.seq.pages, np.int32)

        def fetch():
            _chaos.site("tier.handoff")
            payload = {}
            for name, fam in pool.families().items():
                what = f"tier.kv.{name}" if name else "tier.kv"
                payload[name] = tuple(
                    _d2h(a, what=what) for a in fam.take_pages(rows)
                )
            return payload

        with _span(
            "tier.export",
            request=int(request_id),
            pages=int(rows.size),
            reason=reason,
        ):
            payload = run_with_retries(fetch, what="tier.handoff")
        k, v = payload.pop("")
        req = act.req
        snap = SlotSnapshot(
            request_id=req.request_id,
            prompt=req.prompt,
            generated=list(act.generated),
            emitted=req.emitted,
            max_new_tokens=req.max_new_tokens,
            temperature=req.temperature,
            top_p=req.top_p,
            seed=req.seed,
            eos_id=req.eos_id,
            tenant=req.tenant,
            priority=req.priority,
            deadline_t=req.deadline_t,
            submitted_at=req.submitted_at,
            trace=req.trace,
            page_size=engine.page_size,
            k=k,
            v=v,
            groups=payload,
            draft_pos=act.draft_pos,
            reason=reason,
            source=engine.name,
            started_t=t0,
        )
        # pages back to the pool only AFTER the bytes are on the host;
        # the handle stays open — the restore side keeps streaming it
        engine.scheduler.detach(idx)
        return snap


def _check_compat(engine, snap: SlotSnapshot) -> None:
    pool = engine.pool
    if snap.page_size != engine.page_size:
        raise TierMigrationError(
            f"page_size mismatch: snapshot {snap.page_size} vs "
            f"engine {engine.name} {engine.page_size} — page rows are "
            f"position-layout-bound and cannot be re-tiled"
        )
    want = (
        pool.n_layers, snap.n_pages, pool.page_size,
        pool.n_kv_heads, pool.head_dim,
    )
    if tuple(snap.k.shape) != want or snap.k.dtype != pool.k.dtype:
        raise TierMigrationError(
            f"KV geometry mismatch: snapshot rows "
            f"{tuple(snap.k.shape)}/{snap.k.dtype} vs engine "
            f"{engine.name} {want}/{np.dtype(pool.k.dtype)}"
        )
    total = len(snap.prompt) + snap.max_new_tokens
    if total > engine.max_seq_len:
        raise TierMigrationError(
            f"request needs {total} positions at full length but engine "
            f"{engine.name} caps sequences at {engine.max_seq_len}"
        )


def _write_rows(holder, rows: np.ndarray, k_host, v_host) -> None:
    """Scatter host page rows into ``holder`` (the pool or one group)
    at indices ``rows`` — the eager ``_apply_cow`` idiom: plain device
    indexing re-pinned by ``place()``, zero step programs. The upload
    rides ``h2d`` (chunked/retried/counted) when the holder is
    unsharded and no wire cast is configured; sharded pools and active
    wire casts take the raw-host operand path instead, so the scatter
    itself re-shards under the holder's own placement and the bytes
    are never rounded."""
    use_h2d = (
        holder.sharding is None
        and _wire(k_host.dtype) == np.dtype(k_host.dtype)
    )
    k_src = _h2d(k_host, what="tier.kv") if use_h2d else k_host
    v_src = _h2d(v_host, what="tier.kv") if use_h2d else v_host
    holder.put_pages(rows, k_src, v_src)


def restore_slot(engine, snap: SlotSnapshot, _handle_factory=None):
    """Re-materialize an exported slot on ``engine``; returns the new
    slot's :class:`~.scheduler.GenerationHandle` (or the relay handle
    ``_handle_factory`` builds — the fleet's stream-continuity hook,
    same contract as ``GenerationEngine.submit``).

    Raises :class:`TierMigrationError` on geometry/feasibility
    mismatch, :class:`~.scheduler.QueueFullError` when no slot is
    free, and :class:`~...utils.failures.PagePoolExhausted` when the
    pool cannot grant the page set — all three leave the engine
    untouched so the caller's fallback ladder (replay, preemption)
    still owns the request."""
    if not engine.healthy or engine._stop_wedged:
        raise TierMigrationError(
            f"engine {engine.name} is unhealthy; not importing a live slot"
        )
    with engine._step_lock:
        _check_compat(engine, snap)
        sched = engine.scheduler
        idx = next(
            (i for i, s in enumerate(sched.slots) if s is None), None
        )
        if idx is None:
            raise QueueFullError(
                f"engine {engine.name} has no free decode slot for a "
                f"migrated stream ({engine.max_slots} active)"
            )
        pool = engine.pool
        pages = pool.alloc(snap.n_pages)  # all-or-nothing
        rows = np.asarray(pages, np.int32)
        restored_groups: set = set()
        try:

            def write():
                _chaos.site("tier.handoff")
                _write_rows(pool, rows, snap.k, snap.v)
                for name, (gk, gv) in snap.groups.items():
                    g = pool.groups.get(name)
                    if g is None:
                        continue  # destination runs without this group
                    want = (
                        g.n_layers, rows.size, pool.page_size,
                        g.n_kv_heads, g.head_dim,
                    )
                    if tuple(gk.shape) != want or gk.dtype != g.k.dtype:
                        # e.g. a different draft model: leave the rows
                        # zeroed; draft_pos resets below and the draft
                        # re-ingests (proposals degrade, bytes do not)
                        continue
                    _write_rows(g, rows, gk, gv)
                    restored_groups.add(name)

            with _span(
                "tier.restore",
                request=int(snap.request_id),
                pages=int(rows.size),
                reason=snap.reason,
            ):
                run_with_retries(write, what="tier.handoff")
        except BaseException:
            pool.free(pages)
            raise
        with engine._submit_lock:
            engine._req_counter += 1
            rid = engine._req_counter
        handle = (
            GenerationHandle if _handle_factory is None else _handle_factory
        )(rid)
        req = GenRequest(
            request_id=rid,
            prompt=snap.prompt,
            max_new_tokens=snap.max_new_tokens,
            temperature=snap.temperature,
            top_p=snap.top_p,
            seed=snap.seed,
            eos_id=snap.eos_id,
            handle=handle,
            submitted_at=snap.submitted_at,
            emitted=snap.emitted,
            deadline_t=snap.deadline_t,
            trace=snap.trace,
            tenant=snap.tenant,
            priority=snap.priority,
        )
        seq = SequencePages(pool)
        seq.pages = pages
        act = _Active(req, seq, sched._admit_counter)
        sched._admit_counter += 1
        act.generated = list(snap.generated)
        # prefill is DONE by construction (export requires a generated
        # token); the slot joins the decode batch next step
        act.prefill_pos = len(snap.prompt)
        act.cached_tokens = 0
        act.cow_src = None
        # draft KV travelled with the pages iff the destination holds a
        # geometry-identical group; otherwise the draft re-ingests from
        # scratch — the bounded-stall catch-up discipline
        act.draft_pos = (
            snap.draft_pos if "draft" in restored_groups else 0
        )
        act.spec_k = -1  # re-seed from the destination's static k
        sched.slots[idx] = act
        _m_migrations.inc(reason=snap.reason)
        _m_migration_s.observe(time.monotonic() - snap.started_t)
        logger.info(
            "migrated request %s: %s -> %s (%d pages, %d tokens in, "
            "reason=%s)",
            snap.request_id, snap.source, engine.name, len(pages),
            len(snap.generated), snap.reason,
        )
        return handle
