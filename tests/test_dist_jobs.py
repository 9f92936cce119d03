"""Distributed journaled jobs: leasing, heartbeats, reclamation, fencing.

The acceptance bar (ISSUE 8): a K-worker drain of one manifest is
byte-identical to a solo run — including under a kill -9 of one worker
mid-block (lease reclaimed, block recomputed exactly once) and a zombie
worker writing after lease theft (write fence-rejected, zero
duplicate/torn ledger records) — verified by a REAL 3-subprocess soak
with obs counters asserting ≥ 1 reclaim and ≥ 1 fence reject.
Everything else here is CPU-only, seeded, deterministic, and fast;
``make test-distjobs`` selects the suite.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import tensorframes_tpu as tft
from tensorframes_tpu.engine import run_job, resume_job, run_worker, wait_job
from tensorframes_tpu.engine.dist_jobs import (
    LeaseManager,
    journal_status,
)
from tensorframes_tpu.engine.jobs import BlockLedger, jobs_status
from tensorframes_tpu.obs import metrics as obs_metrics
from tensorframes_tpu.utils import (
    StaleLeaseError,
    chaos,
    get_config,
    retry_deadline,
    run_with_retries,
    set_config,
)
from tensorframes_tpu.utils.chaos import ChaosFault

pytestmark = pytest.mark.distjobs


@pytest.fixture
def small_chunks():
    old = get_config().max_rows_per_device_call
    set_config(max_rows_per_device_call=16)
    yield
    set_config(max_rows_per_device_call=old)


@pytest.fixture
def fast_retries():
    old = (get_config().max_retries, get_config().retry_backoff_s)
    set_config(max_retries=3, retry_backoff_s=0.001)
    yield
    set_config(max_retries=old[0], retry_backoff_s=old[1])


def _counter(name, **labels):
    try:
        return obs_metrics.registry().get(name).value(**labels)
    except KeyError:
        return 0.0


def _frame(n=96, width=4, parts=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, width)).astype(np.float32)
    return (
        tft.TensorFrame.from_columns({"x": x}).analyze().repartition(parts)
    )


def _fn(x):
    return {"y": x * 3.0 + 1.0}


def _col(frame, name="y"):
    return np.asarray(frame.column_data(name).host())


def _done_records(path):
    return [
        json.loads(ln)
        for ln in open(os.path.join(path, "ledger.jsonl"))
        if '"done"' in ln
    ]


# ---------------------------------------------------------------------------


class TestLeaseManager:
    def test_claim_is_exclusive_while_live(self, tmp_path):
        a = LeaseManager(str(tmp_path), "a", ttl_s=30.0, heartbeat_s=1e6)
        b = LeaseManager(str(tmp_path), "b", ttl_s=30.0, heartbeat_s=1e6)
        assert a.try_acquire(0) == 0
        assert b.try_acquire(0) is None  # live, a's
        assert a.try_acquire(0) == 0  # idempotent for the holder
        assert b.try_acquire(1) == 0  # a different block is free
        a.stop(), b.stop()

    def test_expired_lease_reclaims_with_epoch_bump(self, tmp_path):
        r0 = _counter("jobs.leases_reclaimed_total")
        a = LeaseManager(str(tmp_path), "a", ttl_s=0.2, heartbeat_s=1e6)
        b = LeaseManager(str(tmp_path), "b", ttl_s=30.0, heartbeat_s=1e6)
        assert a.try_acquire(0) == 0
        time.sleep(0.35)
        assert b.try_acquire(0) == 1  # epoch bumped — the fencing token
        assert b.reclaimed_total == 1
        assert _counter("jobs.leases_reclaimed_total") == r0 + 1
        # the loser (previous holder) cannot re-enter at its old epoch
        assert a.try_acquire(0) is None
        a.stop(), b.stop()

    def test_done_marker_is_terminal(self, tmp_path):
        a = LeaseManager(str(tmp_path), "a", ttl_s=0.2, heartbeat_s=1e6)
        b = LeaseManager(str(tmp_path), "b", ttl_s=30.0, heartbeat_s=1e6)
        assert a.try_acquire(0) == 0
        a.mark_done(0, 0)
        time.sleep(0.3)  # well past the ttl: done markers never expire
        assert b.try_acquire(0) is None
        a.stop(), b.stop()

    def test_release_makes_block_claimable_again(self, tmp_path):
        a = LeaseManager(str(tmp_path), "a", ttl_s=30.0, heartbeat_s=1e6)
        b = LeaseManager(str(tmp_path), "b", ttl_s=30.0, heartbeat_s=1e6)
        assert a.try_acquire(0) == 0
        a.release(0)
        assert b.try_acquire(0) == 0  # fresh claim, not a reclaim
        assert b.reclaimed_total == 0
        a.stop(), b.stop()

    def test_heartbeat_extends_the_deadline(self, tmp_path):
        h0 = _counter("jobs.lease_heartbeats_total")
        a = LeaseManager(str(tmp_path), "a", ttl_s=0.6, heartbeat_s=0.1)
        b = LeaseManager(str(tmp_path), "b", ttl_s=30.0, heartbeat_s=1e6)
        assert a.try_acquire(0) == 0
        time.sleep(1.2)  # two ttls: only renewals keep it alive
        assert b.try_acquire(0) is None
        assert _counter("jobs.lease_heartbeats_total") > h0
        a.stop()
        # stop() released (unlinked) the lease: claimable immediately
        assert b.try_acquire(0) == 0
        b.stop()

    def test_fence_check_raises_after_steal(self, tmp_path):
        f0 = _counter("jobs.fence_rejects_total")
        a = LeaseManager(str(tmp_path), "a", ttl_s=0.2, heartbeat_s=1e6)
        b = LeaseManager(str(tmp_path), "b", ttl_s=30.0, heartbeat_s=1e6)
        assert a.try_acquire(3) == 0
        a.fence_check(3, 0)  # still ours: passes
        time.sleep(0.35)
        assert b.try_acquire(3) == 1
        with pytest.raises(StaleLeaseError, match="superseded by epoch 1"):
            a.fence_check(3, 0)
        assert _counter("jobs.fence_rejects_total") == f0 + 1
        a.stop(), b.stop()

    def test_heartbeat_does_not_resurrect_a_superseded_lease(
        self, tmp_path
    ):
        """Regression: renew_all's os.replace would re-CREATE a
        superseded epoch file the reclaimer already unlinked, leaving a
        phantom stale lease the old worker renews forever."""
        a = LeaseManager(str(tmp_path), "a", ttl_s=0.2, heartbeat_s=1e6)
        b = LeaseManager(str(tmp_path), "b", ttl_s=30.0, heartbeat_s=1e6)
        assert a.try_acquire(0) == 0
        time.sleep(0.3)
        assert b.try_acquire(0) == 1  # housekeeping unlinked a's e0 file
        a.renew_all()  # a manual sweep on the stale holder
        names = os.listdir(os.path.join(str(tmp_path), "leases"))
        assert "block-00000.e000000.lease" not in names
        assert not a._held  # a dropped the lost lease
        a.stop(), b.stop()

    def test_concurrent_reclaim_has_one_winner(self, tmp_path):
        dead = LeaseManager(str(tmp_path), "dead", ttl_s=0.1,
                            heartbeat_s=1e6)
        assert dead.try_acquire(0) == 0
        time.sleep(0.25)
        managers = [
            LeaseManager(str(tmp_path), f"m{i}", ttl_s=30.0,
                         heartbeat_s=1e6)
            for i in range(4)
        ]
        results = [None] * 4
        barrier = threading.Barrier(4)

        def race(i):
            barrier.wait()
            results[i] = managers[i].try_acquire(0)

        ts = [threading.Thread(target=race, args=(i,)) for i in range(4)]
        [t.start() for t in ts]
        [t.join(10) for t in ts]
        winners = [r for r in results if r is not None]
        assert winners == [1]  # exactly one claims epoch 1
        for m in managers:
            m.stop()
        dead.stop()


# ---------------------------------------------------------------------------


class TestRetryDeadline:
    def test_deadline_stops_the_retry_loop(self, monkeypatch):
        old = (get_config().max_retries, get_config().retry_backoff_s)
        set_config(max_retries=50, retry_backoff_s=0.02)
        calls = []

        def flaky():
            calls.append(1)
            raise RuntimeError("UNAVAILABLE: connection dropped")

        try:
            t0 = time.monotonic()
            with pytest.raises(RuntimeError, match="UNAVAILABLE"):
                run_with_retries(flaky, what="test", deadline_s=0.15)
            assert time.monotonic() - t0 < 2.0
            assert 1 <= len(calls) < 50
        finally:
            set_config(max_retries=old[0], retry_backoff_s=old[1])

    def test_thread_local_window_applies(self):
        old = (get_config().max_retries, get_config().retry_backoff_s)
        set_config(max_retries=50, retry_backoff_s=0.02)
        calls = []

        def flaky():
            calls.append(1)
            raise RuntimeError("UNAVAILABLE: connection dropped")

        try:
            with retry_deadline(0.1):
                with pytest.raises(RuntimeError, match="UNAVAILABLE"):
                    run_with_retries(flaky, what="test")
            assert 1 <= len(calls) < 50
        finally:
            set_config(max_retries=old[0], retry_backoff_s=old[1])

    def test_no_deadline_is_unbounded_and_nesting_clips(self):
        # None window is a no-op; an inner window is clipped to the outer
        with retry_deadline(None):
            assert run_with_retries(lambda: 42, what="test") == 42
        from tensorframes_tpu.utils.failures import (
            _effective_retry_deadline,
        )

        with retry_deadline(10.0):
            outer = _effective_retry_deadline(None)
            with retry_deadline(100.0):
                assert _effective_retry_deadline(None) == outer

    def test_stale_lease_error_is_not_transient(self):
        from tensorframes_tpu.utils.failures import is_transient

        assert not is_transient(StaleLeaseError("lease gone"))
        # even when chained from a transient cause
        try:
            try:
                raise RuntimeError("UNAVAILABLE: flaky")
            except RuntimeError as cause:
                raise StaleLeaseError("stale") from cause
        except StaleLeaseError as e:
            assert not is_transient(e)


# ---------------------------------------------------------------------------


class TestMultiWorkerDrain:
    def test_three_workers_drain_byte_identical(
        self, tmp_path, small_chunks
    ):
        df = _frame()
        ref = _col(tft.map_rows(_fn, df))
        path = str(tmp_path / "drain")
        reports = []

        def w(i):
            reports.append(
                run_worker(
                    "map_rows", _fn, df, path=path, worker_id=f"w{i}",
                    lease_ttl_s=15.0, poll_s=0.05,
                )
            )

        ts = [threading.Thread(target=w, args=(i,)) for i in range(3)]
        [t.start() for t in ts]
        [t.join(120) for t in ts]
        assert len(reports) == 3 and all(r.complete for r in reports)
        # all 6 blocks computed exactly once, split across the workers
        assert sum(r.blocks_computed for r in reports) == 6
        recs = _done_records(path)
        assert len(recs) == 6
        assert len({r["block"] for r in recs}) == 6
        assert all("worker" in r and "epoch" in r for r in recs)
        # assembly from ANY process is the ordinary resume path
        res = wait_job(path, _fn, df, timeout_s=30)
        assert res.blocks_restored == 6 and res.blocks_computed == 0
        assert np.array_equal(_col(res.completed), ref)
        status = journal_status(path)
        assert status["terminal"] and status["blocks"]["done"] == 6

    @pytest.mark.chaos
    def test_zombie_late_write_is_fence_rejected(
        self, tmp_path, small_chunks
    ):
        """The zombie-writer drill, full write path: a worker with no
        heartbeats stalls inside its first block past its TTL (chaos
        latency), the block is reclaimed and recomputed by a healthy
        worker, and the zombie's late spool+append is rejected by the
        write fence — no duplicate or torn record lands."""
        df = _frame()
        ref = _col(tft.map_rows(_fn, df))
        path = str(tmp_path / "zombie")
        f0 = _counter("jobs.fence_rejects_total")
        r0 = _counter("jobs.leases_reclaimed_total")
        reports = {}

        def zombie():
            reports["zombie"] = run_worker(
                "map_rows", _fn, df, path=path, worker_id="zombie",
                lease_ttl_s=0.8, heartbeat_s=1e6, poll_s=0.05,
            )

        def healthy():
            time.sleep(1.2)  # let the zombie claim + its lease expire
            reports["healthy"] = run_worker(
                "map_rows", _fn, df, path=path, worker_id="healthy",
                lease_ttl_s=15.0, poll_s=0.05,
            )

        # only the zombie's FIRST block stalls (times=1)
        with chaos.scoped("jobs.block=latency:ms=2500:times=1"):
            tz = threading.Thread(target=zombie)
            th = threading.Thread(target=healthy)
            tz.start(), th.start()
            tz.join(120), th.join(120)
        assert reports["zombie"].fence_rejects >= 1
        assert reports["healthy"].leases_reclaimed >= 1
        assert _counter("jobs.fence_rejects_total") >= f0 + 1
        assert _counter("jobs.leases_reclaimed_total") >= r0 + 1
        recs = _done_records(path)
        assert len(recs) == 6 and len({r["block"] for r in recs}) == 6
        res = wait_job(path, _fn, df, timeout_s=30)
        assert np.array_equal(_col(res.completed), ref)

    def test_replay_ignores_superseded_records(
        self, tmp_path, small_chunks
    ):
        """Belt-and-braces replay arbitration: a stale-epoch done-record
        appended AFTER a higher-epoch one (the fence-slip shape) is
        ignored on open_ and counted as a fence reject."""
        df = _frame()
        res = run_job("map_rows", _fn, df, job_dir=str(tmp_path))
        rel = os.path.join("blocks", "block-00000.npz")
        with open(os.path.join(res.path, "ledger.jsonl"), "ab") as f:
            f.write(
                json.dumps(
                    {"block": 0, "status": "done", "npz": rel,
                     "rows": 16, "worker": "a", "epoch": 2}
                ).encode() + b"\n"
            )
            f.write(
                json.dumps(
                    {"block": 0, "status": "done", "npz": rel,
                     "rows": 16, "worker": "zombie", "epoch": 1}
                ).encode() + b"\n"
            )
        f0 = _counter("jobs.fence_rejects_total")
        led = BlockLedger.open_(res.path)
        assert led._done_epoch[0] == 2
        assert _counter("jobs.fence_rejects_total") == f0 + 1
        res2 = resume_job(res.path, _fn, df)
        assert res2.blocks_restored == 6
        assert np.array_equal(_col(res2.completed), _col(res.completed))

    @pytest.mark.chaos
    def test_quarantine_shared_across_workers(
        self, tmp_path, small_chunks
    ):
        """A poison block quarantined by one worker stays quarantined
        for the whole job: the drain completes around it, wait_job
        returns the partial result, and strict assembly raises."""
        from tensorframes_tpu.utils import QuarantinedBlocksError

        df = _frame()
        path = str(tmp_path / "poison")
        with chaos.scoped("jobs.block=fatal:every=3:times=1"):
            rep = run_worker(
                "map_rows", _fn, df, path=path, worker_id="solo",
                lease_ttl_s=15.0, poll_s=0.05,
            )
        assert rep.complete and rep.blocks_quarantined == 1
        res = wait_job(path, _fn, df, timeout_s=30)
        assert len(res.quarantined) == 1
        assert res.completed.num_rows == 96 - 16
        with pytest.raises(QuarantinedBlocksError):
            wait_job(path, _fn, df, timeout_s=30, strict=True)

    def test_all_ops_drain_through_workers(self, tmp_path):
        """map_blocks / reduce_blocks / aggregate share the leasing
        layer with map_rows: 2 workers each, byte-identical assembly."""
        df = _frame()

        def drain(op, fetches, data, name):
            path = str(tmp_path / name)
            rs = []

            def w(i):
                rs.append(
                    run_worker(
                        op, fetches, data, path=path,
                        worker_id=f"w{i}", lease_ttl_s=15.0, poll_s=0.05,
                    )
                )

            ts = [
                threading.Thread(target=w, args=(i,)) for i in range(2)
            ]
            [t.start() for t in ts]
            [t.join(120) for t in ts]
            assert len(rs) == 2 and all(r.complete for r in rs)
            return wait_job(path, fetches, data, timeout_s=30)

        fnb = lambda x: {"y": x * 2.0}  # noqa: E731
        res = drain("map_blocks", fnb, df, "mb")
        assert np.array_equal(
            _col(res.completed), _col(tft.map_blocks(fnb, df))
        )

        red = lambda x_input: {"x": x_input.sum()}  # noqa: E731
        res = drain("reduce_blocks", red, df, "rb")
        assert np.allclose(res.completed, tft.reduce_blocks(red, df))

        keys = (np.arange(96) % 5).astype(np.int64)
        adf = tft.TensorFrame.from_columns(
            {"k": keys, "x": np.arange(96, dtype=np.float32)}
        ).analyze()
        agg = lambda x_input: {"x": x_input.sum()}  # noqa: E731
        res = drain("aggregate", agg, adf.group_by("k"), "ag")
        aref = tft.aggregate(agg, adf.group_by("k"))
        assert np.array_equal(
            _col(res.completed, "x"), _col(aref, "x")
        )

    def test_worker_rejects_wrong_op(self, tmp_path, small_chunks):
        df = _frame()
        path = str(tmp_path / "op")
        run_worker(
            "map_rows", _fn, df, path=path, worker_id="a",
            lease_ttl_s=15.0,
        )
        with pytest.raises(ValueError, match="map_rows"):
            run_worker(
                "map_blocks", _fn, df, path=path, worker_id="b",
                lease_ttl_s=15.0,
            )

    def test_wait_job_polls_over_terminal_but_leased_journal(
        self, tmp_path, small_chunks
    ):
        """Regression: a worker that dies between recording its last
        block and settling its lease leaves a TERMINAL journal with a
        live lease. wait_job must keep polling until the lease expires
        — not crash with the resume guard's StaleLeaseError."""
        df = _frame()
        ref = _col(tft.map_rows(_fn, df))
        res = run_job("map_rows", _fn, df, job_dir=str(tmp_path))
        lm = LeaseManager(res.path, "dying-worker", ttl_s=1.0,
                          heartbeat_s=1e6)
        assert lm.try_acquire(0) == 0
        lm._stop.set()  # simulate death: lease stays, never renewed
        t0 = time.monotonic()
        out = wait_job(res.path, _fn, df, timeout_s=30, poll_s=0.1)
        assert time.monotonic() - t0 >= 0.5  # it actually waited
        assert np.array_equal(_col(out.completed), ref)

    def test_block_claims_stand_down_under_a_journal_lease(
        self, tmp_path
    ):
        """The guard/worker handshake: while a resume/assembly holds
        the journal lease, block claims return None (both the pre- and
        the post-claim check), and resume after release works."""
        guard = LeaseManager(str(tmp_path), "resume-guard", ttl_s=30.0,
                             heartbeat_s=1e6)
        worker = LeaseManager(str(tmp_path), "worker", ttl_s=30.0,
                              heartbeat_s=1e6)
        assert guard.try_acquire(None) == 0
        assert worker.journal_locked()
        assert worker.try_acquire(0) is None
        # the retreat left no block-lease file behind
        assert not [
            n for n in os.listdir(guard.dir) if n.startswith("block-")
        ]
        guard.release(None)
        assert not worker.journal_locked()
        assert worker.try_acquire(0) == 0
        guard.stop(), worker.stop()

    def test_wait_job_times_out(self, tmp_path):
        with pytest.raises(TimeoutError, match="not terminal"):
            wait_job(
                str(tmp_path / "never"), _fn, _frame(),
                timeout_s=0.3, poll_s=0.05,
            )


# ---------------------------------------------------------------------------


class TestResumeGuard:
    def _crashed_journal(self, tmp_path, df):
        path = str(tmp_path / "crashed")
        with chaos.scoped("jobs.journal_write=fatal:every=3:times=1"):
            with pytest.raises(ChaosFault):
                run_job(
                    "map_rows", _fn, df,
                    job_dir=str(tmp_path), job_id="crashed",
                )
        return path

    @pytest.mark.chaos
    def test_resume_refuses_while_block_leases_live(
        self, tmp_path, small_chunks
    ):
        df = _frame()
        path = self._crashed_journal(tmp_path, df)
        lm = LeaseManager(path, "worker-x", ttl_s=30.0, heartbeat_s=1e6)
        assert lm.try_acquire(4) == 0
        with pytest.raises(StaleLeaseError, match="live block lease"):
            resume_job(path, _fn, df)
        # the retry_quarantined variant refuses identically — clearing
        # quarantine.json under a live drain is the race the guard exists
        # for
        with pytest.raises(StaleLeaseError, match="live block lease"):
            resume_job(path, _fn, df, retry_quarantined=True)
        lm.stop()  # releases the lease
        res = resume_job(path, _fn, df)
        assert np.array_equal(_col(res.completed), _col(tft.map_rows(_fn, df)))

    @pytest.mark.chaos
    def test_expired_leases_do_not_block_resume(
        self, tmp_path, small_chunks
    ):
        df = _frame()
        path = self._crashed_journal(tmp_path, df)
        lm = LeaseManager(path, "dead-worker", ttl_s=0.1, heartbeat_s=1e6)
        assert lm.try_acquire(2) == 0
        lm._stop.set()  # simulate death: no heartbeat, no release
        time.sleep(0.25)
        res = resume_job(path, _fn, df)  # expired lease: no refusal
        assert np.array_equal(_col(res.completed), _col(tft.map_rows(_fn, df)))

    @pytest.mark.chaos
    def test_concurrent_resume_refused_by_journal_lease(
        self, tmp_path, small_chunks
    ):
        df = _frame()
        path = self._crashed_journal(tmp_path, df)
        other = LeaseManager(path, "resume-other", ttl_s=30.0,
                             heartbeat_s=1e6)
        assert other.try_acquire(None) == 0  # the journal-level lease
        with pytest.raises(StaleLeaseError, match="locked"):
            resume_job(path, _fn, df)
        other.stop()
        res = resume_job(path, _fn, df)
        assert res.blocks_restored + res.blocks_computed == 6

    @pytest.mark.chaos
    def test_worker_refused_while_journal_lease_held(
        self, tmp_path, small_chunks
    ):
        df = _frame()
        path = self._crashed_journal(tmp_path, df)
        other = LeaseManager(path, "resume-other", ttl_s=30.0,
                             heartbeat_s=1e6)
        assert other.try_acquire(None) == 0
        with pytest.raises(StaleLeaseError, match="held by"):
            run_worker(
                "map_rows", _fn, df, path=path, worker_id="late",
                lease_ttl_s=15.0,
            )
        other.stop()


# ---------------------------------------------------------------------------


class TestHealthz:
    def test_jobs_status_carries_the_journal_lease_view(
        self, tmp_path, small_chunks
    ):
        df = _frame()
        res = run_job("map_rows", _fn, df, job_dir=str(tmp_path))
        status = jobs_status()
        j = status["journal"]
        assert j is not None and j["manifest"]
        assert j["blocks"]["total"] == 6 and j["blocks"]["done"] == 6
        assert j["terminal"] and j["workers"] == []
        # a live lease from ANOTHER process's worker shows up: the view
        # is read from the journal, not this process's registry
        lm = LeaseManager(res.path, "other-proc", ttl_s=30.0,
                          heartbeat_s=1e6)
        # (claim a fresh key: all blocks are done, so use the journal
        #  lease to stand in for activity plus a raw block lease file)
        lm._create_excl(
            "block-00099.e000000.lease", lm._payload(0)
        )
        status = jobs_status()
        workers = status["journal"]["workers"]
        assert [w["worker"] for w in workers] == ["other-proc"]
        assert workers[0]["live_leases"] == 1
        lm.stop()

    def test_journal_status_liveness_is_never_cached(
        self, tmp_path, small_chunks
    ):
        """Regression: the mtime-keyed memo must cache only
        time-independent data — a lease EXPIRES without any filesystem
        change (kill -9 the fleet and no mtime moves), so a probe after
        the TTL must reclassify it stale even on a cache hit."""
        df = _frame()
        res = run_job("map_rows", _fn, df, job_dir=str(tmp_path))
        lm = LeaseManager(res.path, "doomed", ttl_s=0.4, heartbeat_s=1e6)
        lm._create_excl("block-00099.e000000.lease", lm._payload(0))
        s1 = journal_status(res.path)
        assert s1["blocks"]["leased_live"] == 1
        assert s1["workers"][0]["live_leases"] == 1
        time.sleep(0.5)  # TTL passes; no file is touched
        s2 = journal_status(res.path)
        assert s2["blocks"]["leased_live"] == 0
        assert s2["workers"][0]["stale_leases"] == 1
        lm.stop()

    def test_healthz_endpoint_embeds_journal_view(
        self, tmp_path, small_chunks
    ):
        import urllib.request

        from tensorframes_tpu.interop.serving import ScoringServer

        df = _frame()
        run_job("map_rows", _fn, df, job_dir=str(tmp_path))
        with ScoringServer(lambda x: {"y": x * 2.0}) as addr:
            with urllib.request.urlopen(
                f"http://{addr}/healthz", timeout=10
            ) as r:
                payload = json.loads(r.read())
        j = payload["jobs"]["journal"]
        assert j["manifest"] and j["blocks"]["done"] == j["blocks"]["total"]


class TestChaosSites:
    def test_new_sites_are_declared(self):
        assert "jobs.lease" in chaos.SITES
        assert "jobs.heartbeat" in chaos.SITES

    @pytest.mark.chaos
    def test_transient_lease_claim_retries(
        self, tmp_path, small_chunks, fast_retries
    ):
        df = _frame()
        path = str(tmp_path / "flaky-lease")
        with chaos.scoped("jobs.lease=transient:every=2"):
            rep = run_worker(
                "map_rows", _fn, df, path=path, worker_id="w",
                lease_ttl_s=15.0, poll_s=0.05,
            )
        assert rep.complete and rep.blocks_computed == 6
        res = wait_job(path, _fn, df, timeout_s=30)
        assert np.array_equal(
            _col(res.completed), _col(tft.map_rows(_fn, df))
        )

    @pytest.mark.chaos
    def test_heartbeat_stall_is_survivable(
        self, tmp_path, small_chunks
    ):
        # a latency injection on the heartbeat sweep delays renewals;
        # with a generous ttl the drain still completes untouched
        df = _frame()
        path = str(tmp_path / "hb-stall")
        with chaos.scoped("jobs.heartbeat=latency:ms=50"):
            rep = run_worker(
                "map_rows", _fn, df, path=path, worker_id="w",
                lease_ttl_s=15.0, heartbeat_s=0.05, poll_s=0.05,
            )
        assert rep.complete
        res = wait_job(path, _fn, df, timeout_s=30)
        assert np.array_equal(
            _col(res.completed), _col(tft.map_rows(_fn, df))
        )


# ---------------------------------------------------------------------------
# the acceptance soak: 3 REAL subprocess workers, kill -9, zombie
# ---------------------------------------------------------------------------

_WORKER_SCRIPT = r"""
import json, sys
import numpy as np
import tensorframes_tpu as tft
from tensorframes_tpu.obs import metrics as obs_metrics
from tensorframes_tpu.utils import set_config

path, wid, ttl, hb, report_path = sys.argv[1:6]
set_config(max_rows_per_device_call=16)
x = np.arange(768, dtype=np.float32).reshape(192, 4)
df = tft.TensorFrame.from_columns({"x": x}).analyze().repartition(3)
rep = tft.run_worker(
    "map_rows", lambda x: {"y": x * 3.0 + 1.0}, df, path=path,
    worker_id=wid, lease_ttl_s=float(ttl), heartbeat_s=float(hb),
    poll_s=0.2, transient_pass_retries=10,
)
reg = obs_metrics.registry()
out = rep.as_dict()
out["obs"] = {
    "reclaims": reg.get("jobs.leases_reclaimed_total").value(),
    "fence_rejects": reg.get("jobs.fence_rejects_total").value(),
    "claims": reg.get("jobs.leases_claimed_total").value(),
}
with open(report_path, "w") as f:
    json.dump(out, f)
print("WORKER_EXIT", wid)
"""


def _spawn_worker(path, wid, ttl, hb, report_path, chaos_spec):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("TFT_CHAOS", None)
    if chaos_spec:
        env["TFT_CHAOS"] = chaos_spec
    return subprocess.Popen(
        [
            sys.executable, "-c", _WORKER_SCRIPT,
            path, wid, str(ttl), str(hb), report_path,
        ],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True,
    )


def _victim_lease(path, worker_id):
    """The (block, fname) of a live lease held by ``worker_id``, or
    None."""
    lease_dir = os.path.join(path, "leases")
    try:
        names = os.listdir(lease_dir)
    except FileNotFoundError:
        return None
    for n in sorted(names):
        if not (n.startswith("block-") and n.endswith(".lease")):
            continue
        try:
            with open(os.path.join(lease_dir, n)) as f:
                d = json.load(f)
        except (OSError, ValueError):
            continue
        if d.get("worker") == worker_id and d.get("state") != "done":
            return int(n.split(".e")[0][len("block-"):]), n
    return None


@pytest.mark.chaos
class TestKillSoak:
    def test_multiprocess_kill_and_zombie_soak(self, tmp_path):
        """The ISSUE 8 acceptance soak. 3 REAL subprocess workers drain
        one 12-block manifest:

        - ``w-healthy`` runs under ``jobs.block`` transients (p=0.25,
          seeded) — absorbed by the worker's transient-pass retry;
        - ``w-victim`` stalls forever inside its first block (chaos
          latency) while heartbeating, and is **kill -9**'d once its
          lease is on disk — a genuine mid-block process death;
        - ``w-zombie`` stalls 12 s inside its first block with
          heartbeats disabled and a 1.2 s TTL — it is presumed dead,
          its block stolen (by ``w-healthy``, which starts once the
          zombie holds its lease and has the stall to come up in), and
          its late write must be fence-rejected.

        Asserts: byte-identity with a solo run, ≥ 1 reclaim and ≥ 1
        fence reject on the obs counters, the victim's block reclaimed
        exactly once (surviving record at epoch 1, exactly one done
        record), and zero duplicate/torn ledger records."""
        old_chunk = get_config().max_rows_per_device_call
        set_config(max_rows_per_device_call=16)
        try:
            x = np.arange(768, dtype=np.float32).reshape(192, 4)
            df = (
                tft.TensorFrame.from_columns({"x": x})
                .analyze().repartition(3)
            )
            ref = _col(tft.map_rows(_fn, df))
            path = str(tmp_path / "soak")
            reports = {
                w: str(tmp_path / f"report-{w}.json")
                for w in ("w-healthy", "w-victim", "w-zombie")
            }
            victim = _spawn_worker(
                path, "w-victim", 2.0, 0.0, reports["w-victim"],
                "jobs.block=latency:ms=120000",
            )
            zombie = _spawn_worker(
                path, "w-zombie", 1.2, 1e6, reports["w-zombie"],
                "jobs.block=latency:ms=12000:times=1",
            )
            healthy = None
            try:
                # the healthy worker starts once the zombie holds its
                # lease: started together, a healthy worker that wins
                # the start-up race on a loaded machine drains all 12
                # blocks before the zombie claims one, and there is no
                # late write left to fence
                deadline = time.monotonic() + 120
                while _victim_lease(path, "w-zombie") is None:
                    assert time.monotonic() < deadline, (
                        "zombie never claimed a lease"
                    )
                    assert zombie.poll() is None, zombie.stderr.read()
                    time.sleep(0.05)
                healthy = _spawn_worker(
                    path, "w-healthy", 20.0, 0.0, reports["w-healthy"],
                    "seed=5;jobs.block=transient:p=0.25",
                )
                # kill -9 the victim the moment it holds a lease
                deadline = time.monotonic() + 120
                victim_block = None
                while victim_block is None:
                    assert time.monotonic() < deadline, (
                        "victim never claimed a lease"
                    )
                    assert victim.poll() is None, victim.stderr.read()
                    hit = _victim_lease(path, "w-victim")
                    if hit is not None:
                        victim_block = hit[0]
                    else:
                        time.sleep(0.1)
                victim.send_signal(signal.SIGKILL)
                assert victim.wait(timeout=30) == -signal.SIGKILL
                out_h = healthy.communicate(timeout=240)
                out_z = zombie.communicate(timeout=240)
                assert healthy.returncode == 0, out_h[1][-4000:]
                assert zombie.returncode == 0, out_z[1][-4000:]
            finally:
                for p in (healthy, victim, zombie):
                    if p is not None and p.poll() is None:
                        p.kill()
            rep_h = json.load(open(reports["w-healthy"]))
            rep_z = json.load(open(reports["w-zombie"]))
            assert not os.path.exists(reports["w-victim"])  # it died
            # the acceptance counters, from the workers' own registries
            reclaims = rep_h["obs"]["reclaims"] + rep_z["obs"]["reclaims"]
            fences = (
                rep_h["obs"]["fence_rejects"]
                + rep_z["obs"]["fence_rejects"]
            )
            assert reclaims >= 1, (rep_h, rep_z)
            assert fences >= 1, (rep_h, rep_z)
            assert rep_z["fence_rejects"] >= 1  # the zombie specifically
            # no duplicate or torn records: 12 blocks, 12 unique dones
            recs = _done_records(path)
            assert len(recs) == 12
            assert len({r["block"] for r in recs}) == 12
            # the victim's block was reclaimed EXACTLY once: its
            # surviving record sits at epoch 1, by someone else
            vrec = [r for r in recs if r["block"] == victim_block]
            assert len(vrec) == 1
            assert vrec[0]["epoch"] == 1
            assert vrec[0]["worker"] in ("w-healthy", "w-zombie")
            # byte-identity with the solo run, assembled in THIS process
            # (which computed nothing)
            res = wait_job(path, _fn, df, timeout_s=60)
            assert res.blocks_restored == 12 and res.blocks_computed == 0
            assert np.array_equal(_col(res.completed), ref)
            assert res.completed.num_partitions == df.num_partitions
        finally:
            set_config(max_rows_per_device_call=old_chunk)
