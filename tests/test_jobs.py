"""Durable batch jobs: journaling, crash-resume, poison-block quarantine.

The acceptance bar (ISSUE 4): a kill-and-resume soak whose resumed
output is byte-identical to a clean (unjournaled) run with only
unfinished blocks recomputed (asserted via ``jobs.blocks_total``), and a
poison block that quarantines with the real error instead of failing the
job. Everything here is CPU-only, seeded, and deterministic — the suite
is tier-1 (``make test-durability`` selects just it).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import tensorframes_tpu as tft
from tensorframes_tpu.engine import (
    load_quarantine,
    resume_job,
    run_job,
)
from tensorframes_tpu.engine.jobs import BlockLedger, jobs_status
from tensorframes_tpu.obs import metrics as obs_metrics
from tensorframes_tpu.utils import (
    QuarantinedBlocksError,
    chaos,
    get_config,
    seed_backoff_jitter,
    set_config,
)
from tensorframes_tpu.utils.chaos import ChaosFault
from tensorframes_tpu.utils.failures import _backoff_delay, run_with_retries

pytestmark = pytest.mark.durability


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


# ---------------------------------------------------------------------------


class TestJournalBasics:
    def test_journaled_map_rows_matches_plain(self, tmp_path, small_chunks):
        df = _frame()
        ref = _col(tft.map_rows(_fn, df))
        res = run_job("map_rows", _fn, df, job_dir=str(tmp_path))
        assert res.blocks_total == 6  # 96 rows / 16-row chunks
        assert res.blocks_computed == 6 and res.blocks_restored == 0
        assert np.array_equal(_col(res.completed), ref)
        # journal layout on disk
        assert sorted(os.listdir(res.path))[:3] == [
            "blocks", "ledger.jsonl", "manifest.json",
        ]
        manifest = json.loads(
            (tmp_path / res.job_id / "manifest.json").read_text()
        )
        assert manifest["op"] == "map_rows"
        assert len(manifest["plan"]) == 6
        assert len(os.listdir(os.path.join(res.path, "blocks"))) == 6

    def test_resume_of_complete_job_recomputes_nothing(
        self, tmp_path, small_chunks
    ):
        df = _frame()
        res = run_job("map_rows", _fn, df, job_dir=str(tmp_path))
        before = _counter("jobs.blocks_total", status="computed")
        res2 = resume_job(res.path, _fn, df)
        assert res2.resumed
        assert res2.blocks_computed == 0 and res2.blocks_restored == 6
        assert _counter("jobs.blocks_total", status="computed") == before
        assert np.array_equal(_col(res2.completed), _col(res.completed))

    def test_unjournaled_mode_writes_nothing(self, tmp_path, small_chunks):
        df = _frame()
        res = run_job(
            "map_rows", _fn, df, job_dir=str(tmp_path), journal=False
        )
        assert res.path is None
        assert os.listdir(tmp_path) == []
        assert np.array_equal(_col(res.completed), _col(tft.map_rows(_fn, df)))

    def test_map_blocks_and_reduce_and_aggregate_jobs(self, tmp_path):
        df = _frame()
        bres = run_job("map_blocks", _fn, df, job_dir=str(tmp_path))
        assert bres.blocks_total == 3  # one per partition
        assert np.array_equal(_col(bres.completed), _col(tft.map_blocks(_fn, df)))

        red = lambda x_input: {"x": x_input.sum()}  # noqa: E731
        rres = run_job("reduce_blocks", red, df, job_dir=str(tmp_path))
        assert np.allclose(rres.completed, tft.reduce_blocks(red, df))
        rres2 = resume_job(rres.path, red, df)
        assert rres2.blocks_computed == 0 and rres2.blocks_restored == 3
        assert np.allclose(rres2.completed, rres.completed)

        keys = (np.arange(96) % 5).astype(np.int64)
        adf = tft.TensorFrame.from_columns(
            {"k": keys, "x": np.arange(96, dtype=np.float32)}
        ).analyze()
        agg = lambda x_input: {"x": x_input.sum()}  # noqa: E731
        ares = run_job(
            "aggregate", agg, adf.group_by("k"), job_dir=str(tmp_path)
        )
        aref = tft.aggregate(agg, adf.group_by("k"))
        assert np.array_equal(
            _col(ares.completed, "x"), _col(aref, "x")
        )
        ares2 = resume_job(ares.path, agg, adf.group_by("k"))
        assert ares2.blocks_restored == 1 and ares2.blocks_computed == 0
        assert np.array_equal(_col(ares2.completed, "x"), _col(aref, "x"))

    def test_binary_key_aggregate_journal_round_trip(self, tmp_path):
        keys = [b"a", b"b", b"a", b"c", b"b", b"a"] * 4
        df = tft.TensorFrame.from_columns(
            {"k": keys, "x": np.arange(24, dtype=np.float32)}
        ).analyze()
        agg = lambda x_input: {"x": x_input.sum()}  # noqa: E731
        aref = tft.aggregate(agg, df.group_by("k"))
        ares = run_job("aggregate", agg, df.group_by("k"), job_dir=str(tmp_path))
        ares2 = resume_job(ares.path, agg, df.group_by("k"))
        assert ares2.blocks_restored == 1
        for got in (ares.completed, ares2.completed):
            assert list(got.column_data("k").iter_cells()) == list(
                aref.column_data("k").iter_cells()
            )
            assert np.array_equal(_col(got, "x"), _col(aref, "x"))

    def test_ragged_bucketed_map_rows_journal(self, tmp_path, small_chunks):
        # ragged cells bucket by shape: the journaled plan must walk the
        # buckets in first-appearance order and resume byte-identically
        rng = np.random.default_rng(3)
        cells = [
            rng.normal(size=(3 + (i % 2),)).astype(np.float32)
            for i in range(48)
        ]
        df = tft.TensorFrame.from_columns({"v": cells}).analyze()
        fn = lambda v: {"s": v.sum()}  # noqa: E731
        ref = _col(tft.map_rows(fn, df), "s")
        res = run_job("map_rows", fn, df, job_dir=str(tmp_path))
        assert res.blocks_total == 4  # 2 buckets x 24 rows / 16-row chunks
        assert np.array_equal(_col(res.completed, "s"), ref)
        res2 = resume_job(res.path, fn, df)
        assert res2.blocks_computed == 0 and res2.blocks_restored == 4
        assert np.array_equal(_col(res2.completed, "s"), ref)

    @pytest.mark.chaos
    def test_ragged_quarantine_drops_the_bucket_chunk_rows(
        self, tmp_path, small_chunks
    ):
        rng = np.random.default_rng(3)
        cells = [
            rng.normal(size=(3 + (i % 2),)).astype(np.float32)
            for i in range(48)
        ]
        df = tft.TensorFrame.from_columns({"v": cells}).analyze()
        fn = lambda v: {"s": v.sum()}  # noqa: E731
        ref = _col(tft.map_rows(fn, df), "s")
        with chaos.scoped("jobs.block=fatal:every=2:times=1"):
            res = run_job("map_rows", fn, df, job_dir=str(tmp_path))
        assert [b.index for b in res.quarantined] == [1]
        # block 1 = rows 32..46 step 2 of bucket 0 (even rows, shape [3])
        dropped = set(range(32, 48, 2))
        keep = [i for i in range(48) if i not in dropped]
        assert np.array_equal(_col(res.completed, "s"), ref[keep])
        got_cells = list(res.completed.column_data("v").iter_cells())
        assert all(
            np.array_equal(a, cells[i]) for a, i in zip(got_cells, keep)
        )

    def test_resume_rejects_a_different_job(self, tmp_path, small_chunks):
        df = _frame()
        res = run_job("map_rows", _fn, df, job_dir=str(tmp_path))
        other = _frame(n=80, parts=2, seed=1)
        with pytest.raises(ValueError, match="fingerprint|block plan"):
            resume_job(res.path, _fn, other)

    def test_aggregate_resume_rejects_a_different_program(self, tmp_path):
        keys = (np.arange(24) % 3).astype(np.int64)
        df = tft.TensorFrame.from_columns(
            {"k": keys, "x": np.arange(24, dtype=np.float32)}
        ).analyze()
        res = run_job(
            "aggregate",
            lambda x_input: {"x": x_input.sum()},
            df.group_by("k"),
            job_dir=str(tmp_path),
        )
        with pytest.raises(ValueError, match="fingerprint"):
            resume_job(
                res.path,
                lambda x_input: {"other": x_input.min()},
                df.group_by("k"),
            )

    def test_fetch_named_file_spools_fine(self, tmp_path, small_chunks):
        # "file" is an np.savez parameter name; the spool must not care
        df = _frame()
        fn = lambda x: {"file": x * 2.0}  # noqa: E731
        res = run_job("map_rows", fn, df, job_dir=str(tmp_path))
        assert not res.quarantined
        res2 = resume_job(res.path, fn, df)
        assert res2.blocks_restored == 6
        assert np.array_equal(
            _col(res2.completed, "file"), _col(tft.map_rows(fn, df), "file")
        )

    def test_fresh_job_refuses_an_occupied_directory(
        self, tmp_path, small_chunks
    ):
        df = _frame()
        run_job("map_rows", _fn, df, job_dir=str(tmp_path), job_id="j1")
        with pytest.raises(ValueError, match="already holds"):
            run_job("map_rows", _fn, df, job_dir=str(tmp_path), job_id="j1")


# ---------------------------------------------------------------------------


class TestCrashResume:
    @pytest.mark.chaos
    def test_kill_and_resume_soak_byte_identical(
        self, tmp_path, small_chunks
    ):
        """The acceptance soak: a journaled map_rows job is killed (chaos
        ``fatal`` inside the journal-write path — after the block
        computed, before its record landed) after every k-th write,
        resumed, and killed again until it completes. The final output
        must be byte-identical to an unjournaled run, and each attempt
        must recompute only blocks without completion records."""
        df = _frame(n=128, parts=4)  # 8 blocks of 16
        ref = _col(tft.map_rows(_fn, df))
        path = str(tmp_path / "soak")
        k = 3
        res = None
        attempts = 0
        recorded_before = 0
        while res is None:
            attempts += 1
            assert attempts < 20, "soak failed to converge"
            c0 = _counter("jobs.blocks_total", status="computed")
            r0 = _counter("jobs.blocks_total", status="restored")
            try:
                with chaos.scoped(
                    f"seed=7;jobs.journal_write=fatal:every={k}:times=1"
                ):
                    if attempts == 1:
                        res = run_job(
                            "map_rows", _fn, df,
                            job_dir=str(tmp_path), job_id="soak",
                        )
                    else:
                        res = resume_job(path, _fn, df)
            except ChaosFault:
                res = None
            restored = _counter("jobs.blocks_total", status="restored") - r0
            computed = _counter("jobs.blocks_total", status="computed") - c0
            # every attempt restores exactly what previous attempts
            # recorded, and computes only the rest — never a redo of a
            # journaled block
            assert restored == recorded_before
            assert computed <= 8 - recorded_before
            recorded_before += computed
        assert res.blocks_total == 8
        assert attempts > 2, "the kill schedule never fired"
        assert np.array_equal(_col(res.completed), ref)
        # partition structure survives the journal round-trip
        assert res.completed.num_partitions == df.num_partitions

    @pytest.mark.chaos
    def test_transient_journal_write_failures_retry(
        self, tmp_path, small_chunks, fast_retries
    ):
        df = _frame()
        with chaos.scoped("jobs.journal_write=transient:every=2"):
            res = run_job("map_rows", _fn, df, job_dir=str(tmp_path))
        assert res.blocks_computed == 6 and not res.quarantined
        assert np.array_equal(_col(res.completed), _col(tft.map_rows(_fn, df)))

    def test_cross_process_crash_then_resume(self, tmp_path):
        """A REAL process death: a child runs the journaled job with a
        chaos kill in the journal-write path and exits nonzero; this
        process then resumes from the on-disk journal alone."""
        job_dir = str(tmp_path)
        script = (
            "import numpy as np, tensorframes_tpu as tft\n"
            "from tensorframes_tpu.engine import run_job\n"
            "from tensorframes_tpu.utils import set_config\n"
            "set_config(max_rows_per_device_call=16)\n"
            "x = np.arange(384, dtype=np.float32).reshape(96, 4)\n"
            "df = tft.TensorFrame.from_columns({'x': x}).analyze()"
            ".repartition(3)\n"
            "run_job('map_rows', lambda x: {'y': x * 3.0 + 1.0}, df,\n"
            f"        job_dir={job_dir!r}, job_id='child')\n"
        )
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            TFT_CHAOS="jobs.journal_write=fatal:every=4:times=1",
        )
        proc = subprocess.run(
            [sys.executable, "-c", script],
            env=env, capture_output=True, text=True, timeout=180,
        )
        assert proc.returncode != 0, proc.stdout + proc.stderr
        assert "ChaosFault" in proc.stderr
        path = os.path.join(job_dir, "child")
        assert os.path.exists(os.path.join(path, "manifest.json"))
        # resume in THIS process from disk state only
        old = get_config().max_rows_per_device_call
        set_config(max_rows_per_device_call=16)
        try:
            x = np.arange(384, dtype=np.float32).reshape(96, 4)
            df = (
                tft.TensorFrame.from_columns({"x": x})
                .analyze().repartition(3)
            )
            res = resume_job(path, _fn, df)
            assert res.blocks_restored >= 1, "child recorded nothing"
            assert res.blocks_restored + res.blocks_computed == 6
            assert np.array_equal(
                _col(res.completed), _col(tft.map_rows(_fn, df))
            )
        finally:
            set_config(max_rows_per_device_call=old)

    def test_torn_ledger_tail_is_ignored(self, tmp_path, small_chunks):
        df = _frame()
        res = run_job("map_rows", _fn, df, job_dir=str(tmp_path))
        ledger_path = os.path.join(res.path, "ledger.jsonl")
        with open(ledger_path, "ab") as f:
            f.write(b'{"block": 99, "status": "do')  # torn append
        led = BlockLedger.open_(res.path)
        assert led.num_blocks == 6
        res2 = resume_job(res.path, _fn, df)
        assert np.array_equal(_col(res2.completed), _col(res.completed))

    def test_missing_spool_recomputes_that_block(
        self, tmp_path, small_chunks
    ):
        df = _frame()
        res = run_job("map_rows", _fn, df, job_dir=str(tmp_path))
        os.remove(os.path.join(res.path, "blocks", "block-00002.npz"))
        res2 = resume_job(res.path, _fn, df)
        assert res2.blocks_computed == 1 and res2.blocks_restored == 5
        assert np.array_equal(_col(res2.completed), _col(res.completed))

    @pytest.mark.chaos
    def test_resume_never_reuploads_completed_blocks(
        self, tmp_path, small_chunks
    ):
        """Block plans align with transfer chunks and feeds cross the
        link per block (``frame/transfer.py``), so a resume's
        ``frame.h2d_bytes_total`` delta is EXACTLY the unfinished
        blocks' input bytes — journaled blocks restore from their npz
        spools without touching the link."""
        df = _frame()  # 96 rows x 4 f32 -> 6 blocks of 16 at the cap
        block_bytes = 16 * 4 * 4
        path = str(tmp_path / "noreup")
        with chaos.scoped("jobs.journal_write=fatal:every=3:times=1"):
            with pytest.raises(ChaosFault):
                run_job(
                    "map_rows", _fn, df,
                    job_dir=str(tmp_path), job_id="noreup",
                )
        recorded = len(
            [
                ln
                for ln in open(os.path.join(path, "ledger.jsonl"))
                if '"done"' in ln
            ]
        )
        assert 0 < recorded < 6, "the kill left a partial journal"
        h0 = _counter("frame.h2d_bytes_total")
        res = resume_job(path, _fn, df)
        uploaded = _counter("frame.h2d_bytes_total") - h0
        assert res.blocks_restored == recorded
        assert res.blocks_computed == 6 - recorded
        assert uploaded == (6 - recorded) * block_bytes
        assert uploaded < df.num_rows * 4 * 4  # never the whole column
        assert np.array_equal(_col(res.completed), _col(tft.map_rows(_fn, df)))

    @pytest.mark.chaos
    def test_resume_survives_transfer_knob_retune(
        self, tmp_path, small_chunks
    ):
        """The dense block plan is rebuilt from the journal's manifest
        on resume, so retuning transfer_chunk_bytes (the knob
        docs/ingest.md tells operators to tune) between a crash and its
        resume must restore completed blocks, not reject the journal."""
        df = _frame()
        path = str(tmp_path / "retune")
        with chaos.scoped("jobs.journal_write=fatal:every=3:times=1"):
            with pytest.raises(ChaosFault):
                run_job(
                    "map_rows", _fn, df,
                    job_dir=str(tmp_path), job_id="retune",
                )
        old = get_config().transfer_chunk_bytes
        set_config(transfer_chunk_bytes=64)  # would re-plan 4-row blocks
        try:
            res = resume_job(path, _fn, df)
        finally:
            set_config(transfer_chunk_bytes=old)
        assert res.blocks_total == 6  # the journaled 16-row plan held
        assert res.blocks_restored > 0
        assert np.array_equal(_col(res.completed), _col(tft.map_rows(_fn, df)))

    def test_plan_aligns_with_transfer_chunks(self, tmp_path):
        """A journal block never spans transfer chunks: with a 128-byte
        chunk over 16-byte rows, the plan caps blocks at 8 rows even
        though the device-call cap allows far more."""
        old = (
            get_config().transfer_chunk_bytes,
            get_config().max_rows_per_device_call,
        )
        set_config(transfer_chunk_bytes=128, max_rows_per_device_call=8192)
        try:
            df = _frame()  # 96 rows x 4 f32 = 16 B/row
            res = run_job("map_rows", _fn, df, job_dir=str(tmp_path))
            assert res.blocks_total == 12  # 96 rows / 8-row chunks
            assert np.array_equal(
                _col(res.completed), _col(tft.map_rows(_fn, df))
            )
        finally:
            set_config(
                transfer_chunk_bytes=old[0],
                max_rows_per_device_call=old[1],
            )


# ---------------------------------------------------------------------------


class TestQuarantine:
    @pytest.mark.chaos
    def test_poison_block_quarantines_with_the_real_error(
        self, tmp_path, small_chunks
    ):
        df = _frame()
        ref = _col(tft.map_rows(_fn, df))
        q0 = _counter("jobs.quarantined_total")
        with chaos.scoped("jobs.block=fatal:every=3:times=1"):
            res = run_job("map_rows", _fn, df, job_dir=str(tmp_path))
        assert len(res.quarantined) == 1
        qb = res.quarantined[0]
        assert qb.index == 2 and qb.rows == 16
        assert qb.error_type == "ChaosFault"
        assert "chaos-injected fatal" in qb.error
        assert _counter("jobs.quarantined_total") == q0 + 1
        # partial result: the poisoned block's rows are gone, the rest
        # are byte-identical and stay aligned with the carried column
        assert res.completed.num_rows == 96 - 16
        keep = np.r_[0:32, 48:96]
        assert np.array_equal(_col(res.completed), ref[keep])
        assert np.array_equal(
            _col(res.completed, "x"),
            np.asarray(df.column_data("x").host())[keep],
        )

    @pytest.mark.chaos
    def test_quarantine_manifest_round_trip(self, tmp_path, small_chunks):
        df = _frame()
        with chaos.scoped("jobs.block=fatal:every=3:times=1"):
            res = run_job("map_rows", _fn, df, job_dir=str(tmp_path))
        blocks = load_quarantine(res.path)
        assert [(b.index, b.error_type) for b in blocks] == [
            (2, "ChaosFault")
        ]
        assert "chaos-injected fatal" in blocks[0].error
        assert blocks[0].traceback  # the real traceback is preserved
        # resume without retry keeps the quarantine and recomputes nothing
        res2 = resume_job(res.path, _fn, df)
        assert len(res2.quarantined) == 1 and res2.blocks_computed == 0
        # retry_quarantined re-attempts the poisoned block (now healthy)
        res3 = resume_job(res.path, _fn, df, retry_quarantined=True)
        assert not res3.quarantined and res3.blocks_computed == 1
        assert np.array_equal(_col(res3.completed), _col(tft.map_rows(_fn, df)))
        assert load_quarantine(res.path) == []

    @pytest.mark.chaos
    def test_strict_mode_raises_quarantined_blocks_error(
        self, tmp_path, small_chunks
    ):
        df = _frame()
        with chaos.scoped("jobs.block=fatal:every=3:times=1"):
            with pytest.raises(QuarantinedBlocksError) as ei:
                run_job(
                    "map_rows", _fn, df, job_dir=str(tmp_path),
                    job_id="strict", strict=True,
                )
        assert [b.index for b in ei.value.blocks] == [2]
        # healthy blocks journaled before the raise: a retry resume
        # completes with ONE recompute (the poison, healthy now)
        res = resume_job(
            str(tmp_path / "strict"), _fn, df, retry_quarantined=True
        )
        assert res.blocks_computed == 1 and res.blocks_restored == 5

    def test_config_strict_default(self, tmp_path, small_chunks):
        old = get_config().quarantine_blocks
        set_config(quarantine_blocks=False)
        try:
            df = _frame()
            with chaos.scoped("jobs.block=fatal:every=3:times=1"):
                with pytest.raises(QuarantinedBlocksError):
                    run_job("map_rows", _fn, df, job_dir=str(tmp_path))
        finally:
            set_config(quarantine_blocks=old)

    @pytest.mark.chaos
    def test_map_blocks_quarantine_keeps_alignment(self, tmp_path):
        df = _frame()
        ref = _col(tft.map_blocks(_fn, df))
        with chaos.scoped("jobs.block=fatal:every=2:times=1"):
            res = run_job("map_blocks", _fn, df, job_dir=str(tmp_path))
        assert [b.index for b in res.quarantined] == [1]
        keep = np.r_[0:32, 64:96]  # partition 1 of 3 dropped
        assert np.array_equal(_col(res.completed), ref[keep])
        assert np.array_equal(
            _col(res.completed, "x"),
            np.asarray(df.column_data("x").host())[keep],
        )
        assert res.completed.num_partitions == 3  # structure kept, 0 rows

    @pytest.mark.chaos
    def test_reduce_blocks_quarantine_folds_survivors(self, tmp_path):
        x = np.arange(90, dtype=np.float64)
        df = tft.TensorFrame.from_columns({"x": x}).analyze().repartition(3)
        red = lambda x_input: {"x": x_input.sum()}  # noqa: E731
        with chaos.scoped("jobs.block=fatal:every=2:times=1"):
            res = run_job("reduce_blocks", red, df, job_dir=str(tmp_path))
        assert [b.index for b in res.quarantined] == [1]
        # partitions 0 and 2 survive: rows 0..29 and 60..89
        assert np.allclose(
            res.completed, x[:30].sum() + x[60:].sum()
        )

    @pytest.mark.chaos
    def test_all_blocks_quarantined_yields_none(self, tmp_path):
        x = np.arange(30, dtype=np.float64)
        df = tft.TensorFrame.from_columns({"x": x}).analyze()
        red = lambda x_input: {"x": x_input.sum()}  # noqa: E731
        with chaos.scoped("jobs.block=fatal"):
            res = run_job("reduce_blocks", red, df, job_dir=str(tmp_path))
        assert res.completed is None
        assert len(res.quarantined) == 1

    @pytest.mark.chaos
    def test_transient_and_oom_failures_are_never_quarantined(
        self, tmp_path, small_chunks, fast_retries
    ):
        df = _frame()
        # a transient that outlives the retry budget fails the JOB
        # (resumable), it does not poison the block
        with chaos.scoped("jobs.block=transient"):
            with pytest.raises(RuntimeError, match="UNAVAILABLE"):
                run_job(
                    "map_rows", _fn, df,
                    job_dir=str(tmp_path), job_id="transient-job",
                )
        assert load_quarantine(str(tmp_path / "transient-job")) == []


# ---------------------------------------------------------------------------


class TestReduceOomDegrade:
    @pytest.mark.chaos
    def test_streaming_partial_halves_on_oom(self, fast_retries):
        x = np.arange(64, dtype=np.float64)
        df = tft.TensorFrame.from_columns({"x": x}).analyze().repartition(2)
        red = lambda x_input: {"x": x_input.sum()}  # noqa: E731
        clean = tft.reduce_blocks(red, df)
        old = get_config().device_cache_bytes
        set_config(device_cache_bytes=64)  # force the streaming path
        before = _counter("failures.oom_splits_total", op="reduce_blocks")
        try:
            with chaos.scoped("engine.dispatch=oom:times=1"):
                got = tft.reduce_blocks(red, df)
        finally:
            set_config(device_cache_bytes=old)
        assert np.allclose(got, clean)
        assert (
            _counter("failures.oom_splits_total", op="reduce_blocks")
            == before + 1
        )

    @pytest.mark.chaos
    def test_grouped_dispatch_oom_falls_back_per_partition(
        self, fast_retries
    ):
        x = np.arange(64, dtype=np.float64)
        df = tft.TensorFrame.from_columns({"x": x}).analyze().repartition(4)
        red = lambda x_input: {"x": x_input.sum()}  # noqa: E731
        clean = tft.reduce_blocks(red, df)
        with chaos.scoped("engine.dispatch=oom:times=1"):
            got = tft.reduce_blocks(red, df)
        assert np.allclose(got, clean)


# ---------------------------------------------------------------------------


class TestBackoffJitter:
    def test_full_jitter_bounded_and_seeded(self):
        seed_backoff_jitter(13)
        d1 = [_backoff_delay(a, base=0.5) for a in range(6)]
        seed_backoff_jitter(13)
        d2 = [_backoff_delay(a, base=0.5) for a in range(6)]
        assert d1 == d2  # seeded -> reproducible
        for a, d in enumerate(d1):
            cap = 0.5 * 2.0 ** a
            assert 0.0 < d <= cap
        # jitter actually jitters: the sequence is not the deterministic
        # lockstep schedule base * 2**n
        assert any(
            abs(d - 0.5 * 2.0 ** a) > 1e-9 for a, d in enumerate(d1)
        )
        seed_backoff_jitter(None)

    def test_retry_sleeps_use_jitter(self, fast_retries, monkeypatch):
        import tensorframes_tpu.utils.failures as failures

        slept = []
        monkeypatch.setattr(failures.time, "sleep", slept.append)
        seed_backoff_jitter(7)
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 4:
                raise RuntimeError("UNAVAILABLE: connection dropped")
            return 1

        assert run_with_retries(flaky) == 1
        assert len(slept) == 3
        for a, d in enumerate(slept):
            assert 0.0 < d <= 0.001 * 2.0 ** a
        seed_backoff_jitter(None)


# ---------------------------------------------------------------------------


class TestHealthzJobs:
    def test_healthz_reports_job_status(self):
        import urllib.request

        from tensorframes_tpu.interop.serving import ScoringServer

        df = _frame(n=16, parts=1)
        run_job("map_rows", _fn, df, journal=False)
        status = jobs_status()
        assert status["runs_total"] >= 1
        assert status["last"]["state"] == "complete"
        with ScoringServer(lambda x: {"y": x * 2.0}) as addr:
            with urllib.request.urlopen(
                f"http://{addr}/healthz", timeout=10
            ) as r:
                payload = json.loads(r.read())
        assert payload["healthy"] is True
        jobs = payload["jobs"]
        assert jobs["runs_total"] >= 1
        assert jobs["last"]["op"] == "map_rows"
        assert jobs["last"]["blocks_computed"] >= 1
