"""Serving subsystem: paged KV cache, continuous batching, generate endpoint.

The correctness bar throughout: a request decoded through the shared
continuous batch must be BYTE-IDENTICAL to the same request decoded
alone through ``transformer_generate`` — the paged cache and slot
multiplexing are pure memory-layout concerns, invisible in the streams.
"""

import json
import socket
import threading
import time

import numpy as np
import pytest

from tensorframes_tpu.models import TransformerLM
from tensorframes_tpu.obs import metrics as obs_metrics
from tensorframes_tpu.serve import (
    EngineUnhealthyError,
    GenerationEngine,
    GenRequest,
    GenerationHandle,
    PagePool,
    QueueFullError,
    Scheduler,
    SequencePages,
    pages_needed,
)
from tensorframes_tpu.utils import chaos, get_config, set_config
from tensorframes_tpu.utils.failures import (
    DeadlineExceededError,
    PagePoolExhausted,
)


@pytest.fixture
def fast_retries():
    old = (get_config().max_retries, get_config().retry_backoff_s)
    set_config(max_retries=2, retry_backoff_s=0.001)
    yield
    set_config(max_retries=old[0], retry_backoff_s=old[1])

pytestmark = pytest.mark.serve

VOCAB = 32


@pytest.fixture(scope="module")
def lm():
    return TransformerLM.init(0, VOCAB, d_model=16, n_heads=4, max_len=48)


def _prompts(rng, lens):
    return [rng.integers(1, VOCAB, size=n).astype(np.int32).tolist() for n in lens]


def _solo(lm, prompt, n, **kw):
    return lm.generate(np.asarray([prompt], np.int32), n, **kw)[0, len(prompt):]


def _counter_value(name, **labels):
    try:
        return obs_metrics.registry().get(name).value(**labels)
    except KeyError:
        return 0.0


# ---------------------------------------------------------------------------


class TestPagePool:
    def _pool(self, num_pages=6, page_size=4):
        return PagePool(
            n_layers=2, n_kv_heads=2, head_dim=4,
            num_pages=num_pages, page_size=page_size,
        )

    def test_static_shape_and_trash_row(self):
        pool = self._pool()
        # num_pages + 1 trash row; heads merged into the lane axis
        assert pool.k.shape == (2, 7, 4, 2 * 4)
        assert pool.trash_page == 6

    def test_alloc_free_roundtrip(self):
        pool = self._pool()
        a = pool.alloc(2)
        b = pool.alloc(3)
        assert len(set(a) | set(b)) == 5 and pool.pages_in_use == 5
        pool.free(a)
        assert pool.pages_free == 3
        pool.free(b)
        assert pool.pages_in_use == 0

    def test_exhaustion_is_all_or_nothing(self):
        pool = self._pool(num_pages=4)
        pool.alloc(3)
        with pytest.raises(PagePoolExhausted):
            pool.alloc(2)  # only 1 free
        assert pool.pages_free == 1  # nothing leaked by the failed alloc

    def test_double_free_rejected(self):
        pool = self._pool()
        (p,) = pool.alloc(1)
        pool.free([p])
        with pytest.raises(ValueError, match="double free"):
            pool.free([p])

    def test_sequence_pages_growth_and_table(self):
        pool = self._pool(num_pages=6, page_size=4)
        seq = SequencePages(pool)
        seq.ensure(3)
        assert len(seq.pages) == 1 and seq.capacity == 4
        seq.ensure(4)  # fits the held page — no growth
        assert len(seq.pages) == 1
        seq.ensure(9)
        assert len(seq.pages) == 3
        tab = seq.table(5)
        assert tab.shape == (5,) and list(tab[:3]) == seq.pages
        assert all(tab[3:] == pool.trash_page)
        seq.release()
        assert pool.pages_in_use == 0
        seq.release()  # idempotent

    def test_pages_needed(self):
        assert pages_needed(1, 4) == 1
        assert pages_needed(4, 4) == 1
        assert pages_needed(5, 4) == 2

    def test_defragment_moves_contents_and_renumbers(self):
        pool = self._pool(num_pages=6, page_size=4)
        a, b = SequencePages(pool), SequencePages(pool)
        a.ensure(8)   # pages 0, 1
        b.ensure(8)   # pages 2, 3
        pool.free([a.pages[0]])  # punch a hole at page 0
        a.pages = a.pages[1:]
        # stamp each live page's contents with its page index
        for p in a.pages + b.pages:
            stamp = np.full((2, 1, 4, 2, 4), float(p), np.float32)
            pool.put_pages([p], stamp, -stamp)
        stamps = {p: float(p) for p in a.pages + b.pages}
        remap = pool.defragment([a, b])
        assert sorted(a.pages + b.pages) == [0, 1, 2]  # compacted prefix
        for old, new in remap.items():
            k, v = pool.take_pages([new])
            assert k.shape == (2, 1, 4, 2, 4)
            np.testing.assert_array_equal(np.asarray(k), stamps[old])
            np.testing.assert_array_equal(np.asarray(v), -stamps[old])
        # freed tail is allocatable again
        assert pool.pages_free == 3
        pool.alloc(3)


# ---------------------------------------------------------------------------


def _mk_request(rid, plen, max_new, pool_unused=None):
    return GenRequest(
        request_id=rid,
        prompt=np.arange(1, plen + 1, dtype=np.int32),
        max_new_tokens=max_new,
        handle=GenerationHandle(rid),
    )


class TestScheduler:
    def _sched(self, num_pages=8, page_size=4, max_slots=2, cap=4):
        pool = PagePool(1, 1, 4, num_pages, page_size)
        return Scheduler(pool, max_slots, cap, max_seq_len=num_pages * page_size)

    def test_infeasible_request_rejected_at_submit(self):
        s = self._sched(num_pages=2, page_size=4)  # max 8 tokens ever
        with pytest.raises(ValueError, match="exceeds max_seq_len"):
            s.submit(_mk_request(1, plen=6, max_new=4))

    def test_bounded_queue_rejects_nonblocking(self):
        s = self._sched(cap=2)
        s.submit(_mk_request(1, 2, 2))
        s.submit(_mk_request(2, 2, 2))
        with pytest.raises(QueueFullError):
            s.submit(_mk_request(3, 2, 2), block=False)
        with pytest.raises(QueueFullError):
            s.submit(_mk_request(4, 2, 2), timeout=0.05)

    def test_admit_fills_slots_and_reserves_prompt_pages(self):
        s = self._sched(max_slots=2)
        for i in range(3):
            s.submit(_mk_request(i, plen=5, max_new=2))
        admitted = s.admit()
        assert [idx for idx, _ in admitted] == [0, 1]
        assert s.queue_depth == 1  # third waits for a slot
        # 5 tokens at page_size 4 -> 2 pages each
        assert s.pool.pages_in_use == 4

    def test_grow_preempts_youngest_and_requeues_front(self):
        s = self._sched(num_pages=4, page_size=4, max_slots=2)
        s.submit(_mk_request(1, plen=4, max_new=8))
        s.submit(_mk_request(2, plen=4, max_new=8))
        (i1, a1), (i2, a2) = s.admit()
        assert s.pool.pages_free == 2
        # the YOUNGER sequence grows to own the rest of the pool
        a2.generated.extend([9] * 5)
        assert s.grow(i2) is True
        assert s.pool.pages_free == 0
        # now the OLDER one must grow: the younger gets evicted
        a1.generated.extend([7] * 5)
        assert s.grow(i1) is True
        assert s.slots[i2] is None and s.slots[i1] is a1
        requeued = s._waiting[0]
        assert requeued.request_id == 2
        # recompute-style: progress folded into the prompt, budget reduced
        np.testing.assert_array_equal(requeued.prompt[-5:], [9] * 5)
        assert requeued.max_new_tokens == 3 and requeued.emitted == 5
        assert _counter_value("failures.preemptions_total", op="serve") >= 1

    def test_finish_releases_pages_and_closes_handle(self):
        s = self._sched()
        req = _mk_request(1, 3, 2)
        s.submit(req)
        ((idx, act),) = s.admit()
        act.req.handle._emit(5)
        s.finish(idx)
        assert s.pool.pages_in_use == 0 and s.slots[idx] is None
        assert req.handle.done
        np.testing.assert_array_equal(req.handle.result(timeout=1), [5])


# ---------------------------------------------------------------------------


class TestGenerationEngine:
    def test_greedy_streams_match_solo(self, lm):
        rng = np.random.default_rng(2)
        eng = GenerationEngine(lm, max_slots=4, page_size=4, max_seq_len=32)
        prompts = _prompts(rng, (3, 5, 2, 7))
        outs = eng.generate(prompts, max_new_tokens=6)
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(o, _solo(lm, p, 6))
        assert eng.num_step_programs <= 2

    def test_sampled_streams_match_solo(self, lm):
        rng = np.random.default_rng(3)
        eng = GenerationEngine(lm, max_slots=3, page_size=4, max_seq_len=32)
        prompts = _prompts(rng, (4, 2, 6))
        handles = [
            eng.submit(p, 7, temperature=0.8, top_p=0.9, seed=50 + i)
            for i, p in enumerate(prompts)
        ]
        eng.run_until_idle()
        for i, (p, h) in enumerate(zip(prompts, handles)):
            np.testing.assert_array_equal(
                h.result(timeout=1),
                _solo(lm, p, 7, temperature=0.8, top_p=0.9, seed=50 + i),
            )
        assert eng.num_step_programs <= 2

    def test_eos_frees_slot_early(self, lm):
        rng = np.random.default_rng(4)
        eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
        # find a prompt whose greedy stream's third token is its first
        # occurrence, so eos_id cuts exactly there
        for _ in range(50):
            p = _prompts(rng, (4,))[0]
            solo = _solo(lm, p, 8)
            if solo[2] not in solo[:2]:
                break
        else:
            pytest.skip("no prompt with a fresh third token found")
        eos = int(solo[2])
        h = eng.submit(p, 8, eos_id=eos)
        eng.run_until_idle()
        np.testing.assert_array_equal(h.result(timeout=1), solo[:3])
        assert eng.pool.pages_in_use == 0

    def test_infeasible_submit_rejected(self, lm):
        eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=16)
        with pytest.raises(ValueError, match="exceeds max_seq_len"):
            eng.submit([1] * 10, max_new_tokens=10)
        with pytest.raises(ValueError):
            eng.submit([], max_new_tokens=2)

    def test_streaming_iteration_with_background_thread(self, lm):
        rng = np.random.default_rng(5)
        p = _prompts(rng, (3,))[0]
        with GenerationEngine(
            lm, max_slots=2, page_size=4, max_seq_len=32
        ) as eng:
            h = eng.submit(p, 5)
            got = list(h)  # streams as the background loop steps
        np.testing.assert_array_equal(got, _solo(lm, p, 5))

    def test_defragment_mid_generation_is_transparent(self, lm):
        rng = np.random.default_rng(6)
        eng = GenerationEngine(lm, max_slots=2, page_size=2, max_seq_len=32)
        prompts = _prompts(rng, (5, 3))
        handles = [eng.submit(p, 8) for p in prompts]
        for _ in range(3):
            eng.step()
        # punch holes: nothing guarantees compactness mid-run, so compact
        remap = eng.defragment()
        live = sorted(
            p for _, a in eng.scheduler.active for p in a.seq.pages
        )
        assert live == list(range(len(live)))  # contiguous prefix
        assert set(remap.values()) == set(live)
        eng.run_until_idle()
        for p, h in zip(prompts, handles):
            np.testing.assert_array_equal(h.result(timeout=1), _solo(lm, p, 8))


class TestPreemption:
    def test_starved_pool_preempts_requeues_and_stays_correct(self, lm):
        rng = np.random.default_rng(7)
        # 4 slots x up to 8 pages needed, but only 10 pages: sequences
        # evict each other and recompute; streams must not notice
        eng = GenerationEngine(
            lm, max_slots=4, page_size=4, max_seq_len=32, num_pages=10
        )
        before = _counter_value("failures.preemptions_total", op="serve")
        prompts = _prompts(rng, (6, 9, 4, 8))
        outs = eng.generate(prompts, max_new_tokens=10)
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(o, _solo(lm, p, 10))
        after = _counter_value("failures.preemptions_total", op="serve")
        assert after > before  # the pool really was contended
        assert eng.pool.pages_in_use == 0  # nothing leaked
        assert eng.num_step_programs <= 2  # preemption did not recompile


class TestSupervisor:
    def test_fatal_step_failure_fails_all_handles_fast(self, lm):
        """REGRESSION: a stepping-thread exception must fail every
        in-flight handle within a second — queued ones included — not
        strand them until the result timeout (the pre-fix behavior hung
        the full 300 s)."""
        from tensorframes_tpu.utils.chaos import ChaosFault

        rng = np.random.default_rng(20)
        eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
        prompts = _prompts(rng, (3, 4, 2, 5))  # 2 active + 2 queued
        with chaos.scoped("serve.decode_step=fatal:times=1"):
            with eng:
                handles = [eng.submit(p, 6) for p in prompts]
                # wait out compile + the injected failure on the first one
                with pytest.raises(ChaosFault):
                    handles[0].result(timeout=30)
                # every other handle must already be (or instantly be) dead
                t0 = time.monotonic()
                for h in handles[1:]:
                    with pytest.raises(ChaosFault):
                        h.result(timeout=1)
                assert time.monotonic() - t0 < 1.0
                assert not eng.healthy
                # unhealthy engine sheds instead of queueing doomed work
                with pytest.raises(EngineUnhealthyError):
                    eng.submit(prompts[0], 4)
                assert _counter_value(
                    "serve.handles_failed_total", reason="fatal"
                ) >= 4

    def test_transient_step_failures_retry_invisibly(self, lm, fast_retries):
        rng = np.random.default_rng(21)
        eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
        prompts = _prompts(rng, (4, 3))
        before = _counter_value(
            "chaos.injections_total", site="serve.decode_step",
            kind="transient",
        )
        with chaos.scoped("seed=5;serve.decode_step=transient:every=3"):
            outs = eng.generate(prompts, max_new_tokens=6)
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(o, _solo(lm, p, 6))
        assert eng.healthy
        assert _counter_value(
            "chaos.injections_total", site="serve.decode_step",
            kind="transient",
        ) > before
        assert eng.num_step_programs <= 2

    def test_decode_oom_recovers_by_defrag_and_preempt(
        self, lm, fast_retries
    ):
        rng = np.random.default_rng(22)
        eng = GenerationEngine(lm, max_slots=3, page_size=4, max_seq_len=32)
        prompts = _prompts(rng, (4, 6, 3))
        before = _counter_value("failures.preemptions_total", op="serve")
        with chaos.scoped("serve.decode_step=oom:every=4:times=2"):
            outs = eng.generate(prompts, max_new_tokens=8)
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(o, _solo(lm, p, 8))
        assert eng.healthy  # OOM was degraded through, not fatal
        assert _counter_value("failures.preemptions_total", op="serve") > before
        assert eng.num_step_programs <= 2

    def test_prefill_oom_requeues_recompute_style(self, lm, fast_retries):
        """A device OOM during prefill degrades like a decode OOM does —
        the request (nothing emitted yet) requeues for a retry — instead
        of escalating to a fail-everything terminal error."""
        rng = np.random.default_rng(25)
        eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
        prompts = _prompts(rng, (4, 3))
        with chaos.scoped("serve.prefill=oom:every=1:times=1"):
            outs = eng.generate(prompts, max_new_tokens=6)
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(o, _solo(lm, p, 6))
        assert eng.healthy
        assert eng.pool.pages_in_use == 0

    def test_empty_message_exception_does_not_kill_the_loop(self, lm):
        """str(e) == "" (bare asserts and friends) must not crash the
        supervisor's own logging: handles still fail with the real
        error and the loop thread survives."""
        rng = np.random.default_rng(26)
        eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
        with eng:

            def boom(ready):
                raise RuntimeError()

            eng._decode_batch = boom
            h = eng.submit(_prompts(rng, (3,))[0], 4)
            with pytest.raises(RuntimeError):
                h.result(timeout=30)
            assert eng._thread.is_alive()  # the supervisor survived
            # the handle fails inside step(); the unhealthy flip happens
            # a beat later in the supervisor — give it that beat
            for _ in range(200):
                if not eng.healthy:
                    break
                time.sleep(0.01)
            assert not eng.healthy

    def test_restart_rebuilds_device_state_mid_run(self, lm):
        """Crash recovery: device KV state is corrupted mid-run; restart()
        preempts every live sequence (progress folded into prompts),
        re-zeroes the pool, and the streams stay byte-identical — with
        zero new compiled programs."""
        rng = np.random.default_rng(23)
        eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
        prompts = _prompts(rng, (5, 3))
        handles = [eng.submit(p, 8) for p in prompts]
        for _ in range(3):
            eng.step()
        before = _counter_value("serve.engine_restarts_total")
        eng.pool.fill(7.25, -3.5)  # simulated device loss
        eng.restart()
        eng.run_until_idle()
        for p, h in zip(prompts, handles):
            np.testing.assert_array_equal(h.result(timeout=1), _solo(lm, p, 8))
        assert _counter_value("serve.engine_restarts_total") == before + 1
        assert eng.num_step_programs <= 2
        assert eng.pool.pages_in_use == 0

    def test_stop_join_failure_flips_unhealthy(self, lm):
        """stop() must not pretend a wedged stepping thread stopped: it
        flags the engine unhealthy and keeps the thread for a retry."""

        class _WedgedThread:
            def join(self, timeout=None):
                pass

            def is_alive(self):
                return True

        eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
        eng.start()
        real = eng._thread
        eng._thread = _WedgedThread()
        eng.stop()
        assert eng._stop_wedged and not eng.healthy
        h = eng.health()
        assert h["healthy"] is False and h["stop_wedged"] is True
        # a wedged engine must refuse work AND refuse a restart that
        # could not actually step (the old thread still owns the loop)
        with pytest.raises(EngineUnhealthyError):
            eng.submit([1, 2], 2)
        with pytest.raises(RuntimeError, match="wedged"):
            eng.restart()
        # the retry path: the real thread exits on the stop event
        eng._thread = real
        eng.stop()
        assert eng._thread is None and not eng._stop_wedged
        eng.restart()
        assert eng.health()["healthy"] is True


class TestRestartSubmitRace:
    def test_submits_racing_restart_shed_or_complete_never_hang(self, lm):
        """restart() racing concurrent submit() on one engine: every
        submit must either shed fast with ``EngineUnhealthyError`` (or
        fail with the crash's own error, when a crash preceded the
        restart) or complete BYTE-IDENTICALLY — and no accepted handle
        may hang past its timeout. Phase 1 races restarts against a
        healthy engine (restart preempts-and-requeues, so nothing may
        shed or fail); phase 2 interleaves crashes, where shedding is
        the correct outcome for unlucky submits."""
        eng = GenerationEngine(lm, max_slots=3, page_size=4, max_seq_len=32)
        accepted = []  # (prompt, handle), under hlock
        sheds = []
        hlock = threading.Lock()
        stop = threading.Event()
        crash_allowed = threading.Event()

        def submitter(tid):
            trng = np.random.default_rng(300 + tid)
            while not stop.is_set():
                p = trng.integers(
                    1, VOCAB, size=int(trng.integers(2, 6))
                ).tolist()
                try:
                    h = eng.submit(p, 4)
                except EngineUnhealthyError:
                    with hlock:
                        sheds.append(tid)
                    assert crash_allowed.is_set(), (
                        "submit shed while only healthy restarts were "
                        "racing it"
                    )
                    time.sleep(0.002)
                    continue
                with hlock:
                    accepted.append((p, h))
                time.sleep(0.005)

        with eng:
            threads = [
                threading.Thread(target=submitter, args=(i,))
                for i in range(3)
            ]
            for t in threads:
                t.start()
            # phase 1: pure restarts — legal mid-run, streams must not
            # notice and submits must not shed
            for _ in range(5):
                time.sleep(0.03)
                eng.restart()
            # phase 2: crash + restart — submits may now shed, accepted
            # handles may fail with the injected crash
            crash_allowed.set()
            for _ in range(5):
                time.sleep(0.03)
                eng._fail_inflight(RuntimeError("injected crash"))
                time.sleep(0.005)
                eng.restart()
            stop.set()
            for t in threads:
                t.join(timeout=30)
            assert not any(t.is_alive() for t in threads)
            # every accepted handle settles — byte-identical or failed
            # with the crash — well inside the timeout (TimeoutError
            # here would be the hang this test exists to catch)
            for p, h in accepted:
                try:
                    toks = h.result(timeout=60)
                except RuntimeError:
                    continue  # crashed mid-flight in phase 2 — legal
                np.testing.assert_array_equal(toks, _solo(lm, p, 4))
        assert accepted, "the race never accepted a submit"


class TestDeadlines:
    def test_queued_request_expires(self, lm):
        eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
        before = _counter_value("serve.deadline_expired_total")
        h = eng.submit([1, 2, 3], 4, deadline=0.01)
        time.sleep(0.05)
        eng.step()
        assert h.done and isinstance(h.error, DeadlineExceededError)
        with pytest.raises(DeadlineExceededError):
            h.result(timeout=1)
        assert _counter_value("serve.deadline_expired_total") == before + 1
        assert _counter_value(
            "serve.handles_failed_total", reason="deadline"
        ) >= 1

    def test_mid_generation_deadline_releases_slot_and_pages(self, lm):
        eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=48)
        h = eng.submit([1, 2, 3, 4], 40, deadline=0.05)
        eng.step()  # admit + prefill + first decode
        assert not h.done
        time.sleep(0.06)
        eng.step()  # expiry sweep evicts the running sequence
        assert h.done and isinstance(h.error, DeadlineExceededError)
        assert eng.pool.pages_in_use == 0
        assert all(s is None for s in eng.scheduler.slots)

    def test_deadline_must_be_positive(self, lm):
        eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
        with pytest.raises(ValueError, match="deadline"):
            eng.submit([1, 2], 4, deadline=0.0)


class TestAdmissionPressure:
    def test_submit_timeout_races_queue_drain(self, lm):
        """A blocked submit(timeout=) must win the race when the stepping
        side drains the queue before the timeout — and lose it cleanly
        (QueueFullError, request not enqueued) when nothing drains."""
        rng = np.random.default_rng(24)
        eng = GenerationEngine(
            lm, max_slots=1, page_size=4, max_seq_len=32, queue_capacity=1
        )
        p1, p2 = _prompts(rng, (3, 4))
        h1 = eng.submit(p1, 5)  # fills the capacity-1 queue
        # no drain: the timed submit must give up on time
        t0 = time.monotonic()
        with pytest.raises(QueueFullError):
            eng.submit(p2, 5, timeout=0.05)
        assert time.monotonic() - t0 < 5
        # racing drain: stepping empties the queue while submit waits
        # (the admission pop notifies submitters immediately — the win
        # happens mid-step, before the drain thread's step returns)
        def drain():
            time.sleep(0.15)
            eng.step()  # admits h1 -> queue has room

        t = threading.Thread(target=drain)
        t.start()
        t1 = time.monotonic()
        h2 = eng.submit(p2, 5, timeout=30)  # parks, then wins the race
        waited = time.monotonic() - t1
        assert 0.14 <= waited < 30, waited  # parked until the drain ran
        t.join()
        eng.run_until_idle()
        np.testing.assert_array_equal(h1.result(timeout=1), _solo(lm, p1, 5))
        np.testing.assert_array_equal(h2.result(timeout=1), _solo(lm, p2, 5))


@pytest.mark.slow
class TestSoak:
    def test_sixteen_staggered_requests_byte_identical(self, lm):
        """The acceptance soak: N=16 requests, staggered arrivals, mixed
        prompt/output lengths, a pool small enough to force turnover —
        every stream byte-identical to its solo decode, with at most two
        compiled step programs for the whole run."""
        rng = np.random.default_rng(8)
        eng = GenerationEngine(
            lm, max_slots=6, page_size=4, max_seq_len=40, num_pages=24
        )
        plens = [int(rng.integers(1, 13)) for _ in range(16)]
        nnews = [int(rng.integers(3, 15)) for _ in range(16)]
        prompts = _prompts(rng, plens)
        handles = []
        # staggered arrivals: waves of submissions between live steps
        waves = [prompts[:5], prompts[5:9], prompts[9:13], prompts[13:]]
        k = 0
        for wave in waves:
            for p in wave:
                handles.append(eng.submit(p, nnews[k]))
                k += 1
            for _ in range(2):
                eng.step()
        eng.run_until_idle()
        for p, n, h in zip(prompts, nnews, handles):
            assert h.done and h.error is None
            np.testing.assert_array_equal(
                h.result(timeout=1), _solo(lm, p, n),
                err_msg=f"stream diverged (plen={len(p)}, n={n})",
            )
        assert eng.num_step_programs <= 2, eng.program_signatures
        assert eng.pool.pages_in_use == 0


# ---------------------------------------------------------------------------


def _http(addr, req: bytes) -> bytes:
    host, port = addr.rsplit(":", 1)
    with socket.create_connection((host, int(port)), timeout=30) as c:
        c.sendall(req)
        out = b""
        while True:
            b = c.recv(65536)
            if not b:
                break
            out += b
    return out


def _post_generate(addr, spec) -> tuple:
    body = json.dumps(spec).encode()
    req = (
        b"POST /generate HTTP/1.1\r\nContent-Length: "
        + str(len(body)).encode()
        + b"\r\n\r\n"
        + body
    )
    resp = _http(addr, req)
    status = int(resp.split(b" ", 2)[1])
    payload = json.loads(resp.split(b"\r\n\r\n", 1)[1] or b"{}")
    return status, payload


class TestGenerateEndpoint:
    def test_post_generate_matches_solo_and_scrape_shows_serve_metrics(
        self, lm
    ):
        from tensorframes_tpu.interop.serving import ScoringServer

        rng = np.random.default_rng(9)
        eng = GenerationEngine(lm, max_slots=4, page_size=4, max_seq_len=32)
        p = _prompts(rng, (4,))[0]
        with ScoringServer(engine=eng) as addr:
            status, payload = _post_generate(
                addr, {"prompt": p, "max_new_tokens": 6}
            )
            assert status == 200
            np.testing.assert_array_equal(payload["tokens"], _solo(lm, p, 6))
            counted = 'tft_serving_requests_total{kind="generate",status="ok"}'
            # the connection's thread counts its request once the client
            # has closed, which the next connection's scrape can outrun
            # on a loaded machine: ask again, for a bounded while
            for _ in range(100):
                scrape = _http(
                    addr, b"GET /metrics HTTP/1.1\r\n\r\n"
                ).decode()
                if counted in scrape:
                    break
                time.sleep(0.05)
            for name in (
                "tft_serve_queue_depth",
                "tft_serve_active_slots",
                "tft_serve_pages_in_use",
                "tft_serve_ttft_seconds_count",
                "tft_serve_inter_token_seconds_count",
                counted,
            ):
                assert name in scrape, name
        assert eng._thread is None  # server stop also stopped its engine

    def test_concurrent_connections_share_the_batch(self, lm):
        from tensorframes_tpu.interop.serving import ScoringServer

        rng = np.random.default_rng(10)
        eng = GenerationEngine(lm, max_slots=4, page_size=4, max_seq_len=32)
        prompts = _prompts(rng, (3, 5, 2, 6))
        results = [None] * len(prompts)
        with ScoringServer(engine=eng) as addr:

            def worker(i):
                results[i] = _post_generate(
                    addr, {"prompt": prompts[i], "max_new_tokens": 5}
                )

            threads = [
                threading.Thread(target=worker, args=(i,))
                for i in range(len(prompts))
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        for i, p in enumerate(prompts):
            status, payload = results[i]
            assert status == 200
            np.testing.assert_array_equal(
                payload["tokens"], _solo(lm, p, 5)
            )

    def test_bad_request_and_backpressure_status_codes(self, lm):
        from tensorframes_tpu.interop.serving import ScoringServer

        eng = GenerationEngine(
            lm, max_slots=2, page_size=4, max_seq_len=16, queue_capacity=0
        )
        with ScoringServer(engine=eng) as addr:
            status, payload = _post_generate(addr, {"prompt": [1, 2]})
            assert status == 400 and "error" in payload  # no max_new_tokens
            status, payload = _post_generate(
                addr, {"prompt": [1] * 12, "max_new_tokens": 10}
            )
            assert status == 400  # infeasible for max_seq_len=16
            # capacity-0 admission queue: instant 503 backpressure
            status, payload = _post_generate(
                addr, {"prompt": [1, 2], "max_new_tokens": 2}
            )
            assert status == 503

    def test_healthz_reports_engine_state(self, lm):
        from tensorframes_tpu.interop.serving import ScoringServer

        eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
        with ScoringServer(engine=eng) as addr:
            resp = _http(addr, b"GET /healthz HTTP/1.1\r\n\r\n")
            status = int(resp.split(b" ", 2)[1])
            body = json.loads(resp.split(b"\r\n\r\n", 1)[1])
            assert status == 200 and body["healthy"] is True
            for key in (
                "last_step_age_s",
                "compiling",
                "queue_depth",
                "active_slots",
                "pages_in_use",
                "pages_capacity",
                "stepping_thread_alive",
                "stop_wedged",
            ):
                assert key in body, key
            assert body["stepping_thread_alive"] is True
            # the supervisor flipping unhealthy turns the probe red
            eng.healthy = False
            resp = _http(addr, b"GET /healthz HTTP/1.1\r\n\r\n")
            assert int(resp.split(b" ", 2)[1]) == 503
            eng.healthy = True

    def test_healthz_without_engine_is_healthy(self):
        from tensorframes_tpu.interop.serving import ScoringServer

        with ScoringServer(lambda x: {"y": x}) as addr:
            resp = _http(addr, b"GET /healthz HTTP/1.1\r\n\r\n")
            assert int(resp.split(b" ", 2)[1]) == 200
            body = json.loads(resp.split(b"\r\n\r\n", 1)[1])
            assert body["healthy"] is True and body["engine"] is None
            # batch-job status rides along (engine/jobs.py)
            assert "runs_total" in body["jobs"]

    def test_shedding_answers_503_with_retry_after(self, lm):
        from tensorframes_tpu.interop.serving import ScoringServer

        eng = GenerationEngine(
            lm, max_slots=2, page_size=4, max_seq_len=16, queue_capacity=0
        )
        with ScoringServer(engine=eng) as addr:
            # full admission queue: fast 503, caller told when to retry
            resp = _http(
                addr,
                b"POST /generate HTTP/1.1\r\nContent-Length: 40\r\n\r\n"
                b'{"prompt": [1, 2], "max_new_tokens": 2}\n',
            )
            assert int(resp.split(b" ", 2)[1]) == 503
            assert b"Retry-After: 1" in resp
            # unhealthy engine: same shedding, not a hang
            eng.healthy = False
            status, payload = _post_generate(
                addr, {"prompt": [1, 2], "max_new_tokens": 2}
            )
            assert status == 503 and "unhealthy" in payload["error"]
            eng.healthy = True

    def test_deadline_s_maps_to_504(self, lm):
        from tensorframes_tpu.interop.serving import ScoringServer

        eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=48)
        # slow every decode step down so a 150 ms budget cannot fit the
        # requested 40 tokens — the sweep evicts mid-generation
        with chaos.scoped("serve.decode_step=latency:ms=60"):
            with ScoringServer(engine=eng) as addr:
                status, payload = _post_generate(
                    addr,
                    {
                        "prompt": [1, 2, 3],
                        "max_new_tokens": 40,
                        "deadline_s": 0.15,
                    },
                )
        assert status == 504
        assert "deadline" in payload["error"].lower()
        assert eng.pool.pages_in_use == 0

    def test_generate_only_server_refuses_arrow_scoring(self, lm):
        from tensorframes_tpu.interop.serving import (
            ScoringServer,
            remote_arrow_mapper,
        )

        pa = pytest.importorskip("pyarrow")
        eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=16)
        with ScoringServer(engine=eng) as addr:
            fn = remote_arrow_mapper(addr)
            batch = pa.record_batch({"x": pa.array([1.0, 2.0])})
            with pytest.raises(RuntimeError, match="no scoring program"):
                list(fn([batch]))

    def test_server_requires_program_or_engine(self):
        from tensorframes_tpu.interop.serving import ScoringServer

        with pytest.raises(ValueError, match="fetches"):
            ScoringServer()
