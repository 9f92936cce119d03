"""Logical-plan suite (engine/plan.py — ISSUE 11).

The acceptance bars:

- **per-pass byte-identity**: each rewrite pass toggled alone (and all
  together) must produce byte-identical results vs all-off, across the
  map_rows / map_blocks / mixed / select / filter / reduce / aggregate
  matrix — including under ``jobs.block`` chaos and a REAL subprocess
  kill + cross-process resume of a journaled fused plan;
- **one compiled program**: a 3-op map chain + reduce lowers to exactly
  one jit build (the existing ``engine.jit_cache_builds_total``
  accounting);
- **pruning is provable**: a source column bound only by a dead op
  never crosses the link (``frame.h2d_bytes_total`` delta assert).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import tensorframes_tpu as tft
from tensorframes_tpu.engine import plan as plan_mod
from tensorframes_tpu.engine import resume_job, run_job, run_worker, wait_job
from tensorframes_tpu.obs import metrics as obs_metrics
from tensorframes_tpu.utils import chaos, get_config, set_config

pytestmark = pytest.mark.plan

_PLAN_KNOBS = (
    "plan_lazy_ops", "plan_fuse_maps", "plan_prune_columns",
    "plan_hoist_reduce",
)


@pytest.fixture(autouse=True)
def _restore_plan_config():
    prev = {k: getattr(get_config(), k) for k in _PLAN_KNOBS}
    yield
    set_config(**prev)


def _toggles(**on):
    """Config dict with the plan layer on and ONLY the named passes."""
    d = {
        "plan_lazy_ops": True,
        "plan_fuse_maps": False,
        "plan_prune_columns": False,
        "plan_hoist_reduce": False,
    }
    d.update(on)
    return d


#: the per-pass matrix: all-off is the reference the others must match
TOGGLE_SETS = {
    "legacy": {"plan_lazy_ops": False},
    "all_off": _toggles(),
    "fuse_only": _toggles(plan_fuse_maps=True),
    "prune_only": _toggles(plan_prune_columns=True),
    "hoist_only": _toggles(plan_hoist_reduce=True),
    "all_on": _toggles(
        plan_fuse_maps=True, plan_prune_columns=True, plan_hoist_reduce=True
    ),
}


def _counter(name, **labels):
    try:
        return obs_metrics.registry().get(name).value(**labels)
    except KeyError:
        return 0.0


def _frame_bytes(df):
    """(schema names, per-column raw bytes) — the byte-identity probe."""
    df.cache()
    out = {}
    for name in df.schema.names:
        cd = df.column_data(name)
        if cd.dense is not None:
            h = np.asarray(cd.host())
            out[name] = (str(h.dtype), h.shape, h.tobytes())
        else:
            out[name] = [
                (c if isinstance(c, bytes) else np.asarray(c).tobytes())
                for c in cd.iter_cells()
            ]
    return df.schema.names, out


def _reduce_bytes(val):
    vals = val if isinstance(val, list) else [val]
    return [(str(np.asarray(v).dtype), np.asarray(v).tobytes()) for v in vals]


# module-level programs: defined once so graph memos hold across runs
def _f1(x):
    return {"h1": x * 2.0 + 1.0}


def _f2(h1):
    return {"h2": h1 @ np.full((4, 4), 0.5, np.float32) + h1}


def _f3(h2):
    return {"h3": h2.sum(axis=-1) if h2.ndim == 1 else h2}


def _fb1(x):
    return {"a": x * 3.0}


def _fb2(a, x):
    return {"b": a + x}


def _fdead(y):
    return {"dead": (y * y).sum(axis=-1)}


def _fred(h1_input):
    return {"h1": h1_input.sum(axis=0)}


def _fred3(h3_input):
    return {"h3": h3_input.sum(axis=0)}


def _ov2(a):
    return {"o2": a + 1.0}


def _ov3(o2):
    return {"o3": o2 * 0.5}


def _ovred(o3_input):
    return {"o3": o3_input.sum(axis=0)}


def _fagg(h1_input):
    return {"h1": h1_input.sum(axis=0)}


def _src(n=96, parts=3, seed=0):
    rng = np.random.default_rng(seed)
    return tft.TensorFrame.from_columns(
        {
            "x": rng.normal(size=(n, 4)).astype(np.float32),
            "y": rng.normal(size=(n, 8)).astype(np.float32),
            "k": (np.arange(n) % 5).astype(np.int32),
        },
        num_partitions=parts,
    ).analyze()


# the pipeline matrix: name -> builder(df) -> lazy frame or eager value
PIPELINES = {
    "map_rows_chain": lambda df: _f3_chain(df),
    "map_blocks_chain": lambda df: _fb_chain(df),
    "mixed_chain": lambda df: _mixed_chain(df),
    "chain_select": lambda df: _f3_chain(df).select(("h3", "z"), "x"),
    "chain_filter": lambda df: _f3_chain(df).filter_rows(
        np.arange(df.num_rows) % 2 == 0
    ),
    "chain_dead_op_select": lambda df: _dead_chain(df).select("h1", "k"),
    "chain_reduce": lambda df: tft.reduce_blocks(_fred, _f1_only(df)),
    "chain_aggregate": lambda df: _f1_only(df)
    .group_by("k")
    .aggregate(_fagg),
}


def _f1_only(df):
    m1 = tft.map_rows(_f1, df)
    return tft.map_rows(_fdead, m1)  # dead for reduce/aggregate demand


def _f3_chain(df):
    m1 = tft.map_rows(_f1, df)
    m2 = tft.map_rows(_f2, m1)
    return tft.map_rows(_f3, m2)


def _fb_chain(df):
    m1 = tft.map_blocks(_fb1, df)
    m2 = tft.map_blocks(_fb2, m1)
    return m2


def _mixed_chain(df):
    m1 = tft.map_rows(_f1, df)
    m2 = tft.map_blocks(lambda h1: {"m": h1 * 0.25}, m1)
    return tft.map_rows(lambda m: {"q": m.sum()}, m2)


def _dead_chain(df):
    m1 = tft.map_rows(_f1, df)
    return tft.map_rows(_fdead, m1)


def _run(pipeline, toggles, seed=0):
    set_config(**toggles)
    try:
        out = PIPELINES[pipeline](_src(seed=seed))
        if isinstance(out, tft.TensorFrame):
            return _frame_bytes(out)
        return _reduce_bytes(out)
    finally:
        set_config(**TOGGLE_SETS["all_on"])


class TestByteIdentityMatrix:
    """Each pass alone (and all together) vs all-off, per pipeline."""

    @pytest.mark.parametrize("pipeline", sorted(PIPELINES))
    @pytest.mark.parametrize(
        "mode", [m for m in TOGGLE_SETS if m != "all_off"]
    )
    def test_pass_matrix_byte_identical(self, pipeline, mode):
        ref = _run(pipeline, TOGGLE_SETS["all_off"])
        got = _run(pipeline, TOGGLE_SETS[mode])
        assert got == ref

    def test_mixed_chain_with_ragged_column_falls_back(self):
        # a ragged source column in a block-lowered group: the group
        # must degrade to op-at-a-time, not miscompute or crash
        cells = [np.arange(k, dtype=np.float32) for k in (3, 5, 3, 7, 5, 3)]
        df = tft.TensorFrame.from_columns({"r": cells})

        def build(d):
            m1 = tft.map_rows(lambda r: {"s": r.sum()}, d)
            return tft.map_blocks(lambda s: {"t": s * 2.0}, m1)

        set_config(**TOGGLE_SETS["all_on"])
        got = _frame_bytes(build(df))
        set_config(plan_lazy_ops=False)
        ref = _frame_bytes(
            build(tft.TensorFrame.from_columns({"r": cells}))
        )
        assert got == ref

    def test_constants_fuse_without_collision(self):
        x = np.arange(16, dtype=np.float32)

        def build(d):
            c1 = tft.map_blocks(
                lambda x, c: {"a": x + c}, d, constants={"c": np.float32(2)}
            )
            return tft.map_blocks(
                lambda a, c: {"b": a * c}, c1,
                constants={"c": np.float32(3)},
            )

        set_config(**TOGGLE_SETS["all_on"])
        got = _frame_bytes(
            build(tft.TensorFrame.from_columns({"x": x}))
        )
        set_config(plan_lazy_ops=False)
        ref = _frame_bytes(
            build(tft.TensorFrame.from_columns({"x": x}))
        )
        assert got == ref


class TestProgramCount:
    def test_three_map_chain_plus_reduce_is_one_program(self):
        """The tentpole acceptance: 3 chained maps + reduce on one
        partition lower to exactly ONE jit build (the fused hoisted
        partial program; no merge program is ever built for a single
        partition). Fresh lambdas guarantee fresh graphs, so the delta
        in the existing program accounting is exactly this chain's."""
        set_config(**TOGGLE_SETS["all_on"])
        x = np.arange(64, dtype=np.float32).reshape(16, 4)
        df = tft.TensorFrame.from_columns({"x": x}).analyze()
        j0 = _counter("engine.jit_cache_builds_total")
        f0 = _counter("plan.fused_ops_total")
        m1 = tft.map_rows(lambda x: {"c1": x * 2.0}, df)
        m2 = tft.map_rows(lambda c1: {"c2": c1 + 1.0}, m1)
        m3 = tft.map_rows(lambda c2: {"c3": c2 * 0.5}, m2)
        out = tft.reduce_blocks(
            lambda c3_input: {"c3": c3_input.sum(axis=0)}, m3
        )
        assert np.asarray(out).shape == (4,)
        assert _counter("engine.jit_cache_builds_total") - j0 == 1
        # 3 maps + the reduce absorbed into the one program
        assert _counter("plan.fused_ops_total") - f0 == 4
        assert _counter("plan.passes_total", **{"pass": "hoist_reduce"}) > 0

    def test_fused_map_chain_is_one_program(self):
        set_config(**TOGGLE_SETS["all_on"])
        x = np.arange(64, dtype=np.float32).reshape(16, 4)
        df = tft.TensorFrame.from_columns({"x": x}).analyze()
        m1 = tft.map_rows(lambda x: {"d1": x * 2.0}, df)
        m2 = tft.map_rows(lambda d1: {"d2": d1 + 1.0}, m1)
        m3 = tft.map_rows(lambda d2: {"d3": d2 * 0.5}, m2)
        j0 = _counter("engine.jit_cache_builds_total")
        m3.cache()
        assert _counter("engine.jit_cache_builds_total") - j0 == 1

    def test_fused_program_reused_across_forces(self):
        """Repeated pipelines over the same functions reuse ONE
        composite (and its jit program) — the compile-once contract."""
        set_config(**TOGGLE_SETS["all_on"])
        x = np.arange(64, dtype=np.float32).reshape(16, 4)
        for i in range(3):
            df = tft.TensorFrame.from_columns({"x": x}).analyze()
            out = _f3_chain(df)
            j0 = _counter("engine.jit_cache_builds_total")
            out.cache()
            if i:
                assert (
                    _counter("engine.jit_cache_builds_total") - j0 == 0
                )


class TestColumnPruning:
    def test_pruned_column_never_crosses_the_link(self):
        """The provable h2d delta: `y` is bound only by a dead op, so a
        fused+pruned run uploads exactly `x`'s bytes; the op-at-a-time
        run uploads both."""
        n = 256
        rng = np.random.default_rng(3)
        x = rng.normal(size=(n, 4)).astype(np.float32)
        y = rng.normal(size=(n, 16)).astype(np.float32)

        def build(d):
            m1 = tft.map_rows(_f1, d)
            m2 = tft.map_rows(_fdead_y, m1)
            return m2.select("h1", "x")

        set_config(**TOGGLE_SETS["all_on"])
        df = tft.TensorFrame.from_columns({"x": x, "y": y}).analyze()
        p0 = _counter("plan.pruned_columns_total")
        h0 = _counter("frame.h2d_bytes_total")
        got = build(df).cache()
        assert _counter("frame.h2d_bytes_total") - h0 == x.nbytes
        assert _counter("plan.pruned_columns_total") - p0 >= 2  # dead+y
        # the unfused reference uploads BOTH columns
        set_config(plan_lazy_ops=False)
        df2 = tft.TensorFrame.from_columns({"x": x, "y": y}).analyze()
        h1 = _counter("frame.h2d_bytes_total")
        ref = build(df2).cache()
        assert (
            _counter("frame.h2d_bytes_total") - h1 == x.nbytes + y.nbytes
        )
        assert _frame_bytes(got) == _frame_bytes(ref)

    def test_reduce_demand_prunes_dead_op(self):
        set_config(**TOGGLE_SETS["all_on"])
        df = _src()
        h0 = _counter("frame.h2d_bytes_total")
        out = tft.reduce_blocks(_fred, _f1_only(df))
        uploaded = _counter("frame.h2d_bytes_total") - h0
        # only x (the live op's input) crossed; y (dead op) never did
        assert uploaded == df.column_data("x").host().nbytes
        assert np.asarray(out).shape == (4,)


def _fdead_y(y):
    return {"dead": (y * y).sum(axis=-1)}


class TestLaziness:
    def test_select_and_filter_do_not_force(self):
        set_config(**TOGGLE_SETS["all_on"])
        df = _src()
        m = _f3_chain(df)
        s = m.select("h3")
        f = m.filter_rows(np.arange(96) % 2 == 0)
        assert m.is_lazy and s.is_lazy and f.is_lazy

    def test_intermediates_stay_lazy_and_force_correctly_later(self):
        set_config(**TOGGLE_SETS["all_on"])
        df = _src()
        m1 = tft.map_rows(_f1, df)
        m2 = tft.map_rows(_f2, m1)
        m2.cache()
        assert m1.is_lazy
        # forcing the intermediate later re-runs its own prefix,
        # byte-identically to a standalone run
        got = np.asarray(m1.column_data("h1").host())
        set_config(plan_lazy_ops=False)
        ref = np.asarray(
            tft.map_rows(_f1, _src()).column_data("h1").host()
        )
        assert got.tobytes() == ref.tobytes()

    def test_forced_intermediate_acts_as_source(self):
        set_config(**TOGGLE_SETS["all_on"])
        df = _src()
        m1 = tft.map_rows(_f1, df).cache()  # concrete
        m2 = tft.map_rows(_f2, m1)
        node = m2._plan_node
        src, ops = plan_mod._chain(node)
        assert src is m1 and len(ops) == 1

    def test_errors_still_surface_at_the_call_site(self):
        set_config(**TOGGLE_SETS["all_on"])
        df = _src()
        m = tft.map_rows(_f1, df)
        with pytest.raises(KeyError):
            m.select("nope")
        from tensorframes_tpu.engine import InputNotFoundError

        with pytest.raises(InputNotFoundError):
            tft.map_rows(lambda missing: {"o": missing}, m)


class TestExplain:
    def test_explain_renders_plan_without_forcing(self):
        set_config(**TOGGLE_SETS["all_on"])
        df = _src()
        out = _dead_chain(df).select("h1", "k")
        txt = tft.explain(out)
        assert out.is_lazy  # rendering must not execute
        assert "== Logical plan ==" in txt
        assert "map_rows" in txt and "select" in txt
        assert "prune_columns" in txt
        assert "dead" in txt  # the dead fetch is named
        assert "y" in txt  # the pruned source column is named
        assert "fused programs: 1" in txt
        assert "== Schema ==" in txt  # schema text still included

    def test_explain_concrete_frame_is_schema_only(self):
        df = _src()
        assert tft.explain(df).startswith("root")


class TestJournaledPipelines:
    def _chain(self, df):
        m1 = tft.map_rows(_f1, df)
        m2 = tft.map_rows(_f2, m1)
        return tft.map_rows(_f3, m2).select("h3", "x")

    def _jsrc(self, n=96):
        x = np.arange(n * 4, dtype=np.float32).reshape(n, 4)
        return (
            tft.TensorFrame.from_columns({"x": x}).analyze().repartition(3)
        )

    def _ref(self):
        set_config(plan_lazy_ops=False)
        try:
            return _frame_bytes(self._chain(self._jsrc()))
        finally:
            set_config(**TOGGLE_SETS["all_on"])

    def test_pipeline_job_byte_identical_and_one_fingerprint(
        self, tmp_path
    ):
        set_config(**TOGGLE_SETS["all_on"])
        res = run_job(
            "pipeline", None, self._chain(self._jsrc()),
            job_dir=str(tmp_path), job_id="p1",
        )
        assert res.op == "map_rows" and res.blocks_computed > 0
        assert _frame_bytes(res.completed) == self._ref()
        # resume with a REBUILT plan (fresh lambdas upstream are fine:
        # the fingerprint is structural) restores every block
        res2 = resume_job(
            os.path.join(str(tmp_path), "p1"), None,
            self._chain(self._jsrc()),
        )
        assert res2.blocks_restored == res2.blocks_total
        assert _frame_bytes(res2.completed) == self._ref()

    @pytest.mark.chaos
    def test_fused_plan_under_jobs_block_chaos(self, tmp_path):
        """Transient jobs.block faults inside a journaled fused plan
        retry per block; the output stays byte-identical."""
        set_config(**TOGGLE_SETS["all_on"])
        with chaos.scoped("seed=11;jobs.block=transient:every=2"):
            res = run_job(
                "pipeline", None, self._chain(self._jsrc()),
                job_dir=str(tmp_path), job_id="pc",
            )
        assert not res.quarantined
        assert _frame_bytes(res.completed) == self._ref()

    @pytest.mark.chaos
    def test_kill_and_resume_journaled_fused_plan(self, tmp_path):
        """A REAL process death mid-pipeline: the child journals a
        fused 3-op plan and is killed by a chaos fatal in the journal
        writer; this process rebuilds the plan from scratch and resumes
        — restored + recomputed blocks assemble byte-identically."""
        job_dir = str(tmp_path)
        script = (
            "import numpy as np, tensorframes_tpu as tft\n"
            "from tensorframes_tpu.engine import run_job\n"
            "from tensorframes_tpu.utils import set_config\n"
            "set_config(max_rows_per_device_call=16)\n"
            "x = np.arange(384, dtype=np.float32).reshape(96, 4)\n"
            "df = tft.TensorFrame.from_columns({'x': x}).analyze()"
            ".repartition(3)\n"
            "m1 = tft.map_rows(lambda x: {'h1': x * 2.0 + 1.0}, df)\n"
            "m2 = tft.map_rows(lambda h1: {'h2': h1 @ np.full((4, 4), "
            "0.5, np.float32) + h1}, m1)\n"
            # the EXACT program _f3 traces: the fingerprint is
            # structural, so a different body with the same signature
            # is the caller's contract to avoid (same as resume_job)
            "m3 = tft.map_rows(lambda h2: {'h3': h2.sum(axis=-1) "
            "if h2.ndim == 1 else h2}, m2)\n"
            "run_job('pipeline', None, m3.select('h3', 'x'),\n"
            f"        job_dir={job_dir!r}, job_id='child')\n"
        )
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            TFT_CHAOS="jobs.journal_write=fatal:every=3:times=1",
        )
        proc = subprocess.run(
            [sys.executable, "-c", script],
            env=env, capture_output=True, text=True, timeout=180,
        )
        assert proc.returncode != 0, proc.stdout + proc.stderr
        assert "ChaosFault" in proc.stderr
        path = os.path.join(job_dir, "child")
        assert os.path.exists(os.path.join(path, "manifest.json"))

        set_config(**TOGGLE_SETS["all_on"])
        prev = get_config().max_rows_per_device_call
        set_config(max_rows_per_device_call=16)
        try:
            res = resume_job(path, None, self._chain(self._jsrc()))
            assert res.blocks_restored >= 1, "child recorded nothing"
            assert res.blocks_computed >= 1, "kill left a full journal"
            set_config(plan_lazy_ops=False)
            ref = _frame_bytes(self._chain(self._jsrc()))
            set_config(plan_lazy_ops=True)
            assert _frame_bytes(res.completed) == ref
        finally:
            set_config(max_rows_per_device_call=prev)

    def test_distributed_worker_drains_fused_plan(self, tmp_path):
        set_config(**TOGGLE_SETS["all_on"])
        path = os.path.join(str(tmp_path), "dp")
        rep = run_worker(
            "pipeline", None, self._chain(self._jsrc()), path=path
        )
        assert rep.complete and rep.blocks_computed > 0
        res = wait_job(path, None, self._chain(self._jsrc()), timeout_s=60)
        assert _frame_bytes(res.completed) == self._ref()

    def test_journaled_hoisted_reduce_resumes(self, tmp_path):
        set_config(**TOGGLE_SETS["all_on"])
        df = self._jsrc()
        m1 = tft.map_rows(_f1, df)
        res = run_job(
            "reduce_blocks", _fred, m1,
            job_dir=str(tmp_path), job_id="hr",
        )
        ref = _reduce_bytes(res.completed)
        res2 = resume_job(
            os.path.join(str(tmp_path), "hr"), _fred,
            tft.map_rows(_f1, self._jsrc()),
        )
        assert res2.blocks_restored == res2.blocks_total
        assert _reduce_bytes(res2.completed) == ref

    @pytest.mark.chaos
    def test_quarantined_pipeline_skips_trailing_filter(self, tmp_path):
        """A trailing filter_rows mask is recorded against FULL-frame
        row positions; when quarantine drops a block's rows the mask no
        longer lines up, so post-ops must be skipped (partial result
        surfaces untouched) rather than silently selecting wrong rows."""
        set_config(**TOGGLE_SETS["all_on"])
        mask = np.arange(96) % 2 == 0

        def chain():
            m1 = tft.map_rows(_f1, self._jsrc())
            return m1.filter_rows(mask)

        prev = get_config().max_rows_per_device_call
        set_config(max_rows_per_device_call=16)  # 6 journal blocks
        try:
            with chaos.scoped("seed=5;jobs.block=fatal:every=3:times=1"):
                res = run_job(
                    "pipeline", None, chain(),
                    job_dir=str(tmp_path), job_id="qf", strict=False,
                )
            assert res.quarantined, "the chaos fatal never quarantined"
            # the partial result keeps the surviving blocks' FULL rows —
            # the misaligned mask was not applied
            dropped = sum(q.rows for q in res.quarantined)
            assert res.completed.num_rows == 96 - dropped
            # a clean run applies the filter normally
            res2 = run_job(
                "pipeline", None, chain(),
                job_dir=str(tmp_path), job_id="qf2",
            )
            assert not res2.quarantined
            assert res2.completed.num_rows == int(mask.sum())
        finally:
            set_config(max_rows_per_device_call=prev)

    def test_pipeline_rejects_concrete_frames(self, tmp_path):
        set_config(**TOGGLE_SETS["all_on"])
        with pytest.raises(ValueError, match="pending lazy planned"):
            run_job("pipeline", None, self._jsrc(), job_dir=str(tmp_path))


class TestOverhead:
    def test_fused_framework_overhead_is_lower(self):
        """No benchmark cell measures the fused pipeline's win yet
        (ROADMAP S6, W5–W7); this test pins a conservative floor so a
        regression that erodes it fails loudly without making CI
        timing-flaky."""
        import time

        set_config(**TOGGLE_SETS["all_on"])
        rng = np.random.default_rng(0)
        x = rng.normal(size=(64, 4)).astype(np.float32)
        y = rng.normal(size=(64, 8)).astype(np.float32)
        # one frame, built outside the timed loop: frame construction +
        # analyze cost the same in both modes and would swamp the
        # per-op framework overhead being compared. The pipeline is
        # a map_blocks chain + a dead decoy op + a hoisted
        # reduce — 5 logical ops collapsing to one program.
        df = tft.TensorFrame.from_columns({"x": x, "y": y}).analyze()

        def run_once():
            m1 = tft.map_blocks(_fb1, df)
            m2 = tft.map_blocks(_ov2, m1)
            m3 = tft.map_blocks(_ov3, m2)
            m4 = tft.map_blocks(_fdead_y, m3)
            return tft.reduce_blocks(_ovred, m4)

        def best_of(k=25):
            best = float("inf")
            for _ in range(k):
                t0 = time.perf_counter()
                run_once()
                best = min(best, time.perf_counter() - t0)
            return best

        run_once()  # warm compiles
        fused = best_of()
        set_config(plan_lazy_ops=False)
        run_once()  # warm the unfused programs too
        eager = best_of()
        # a deliberately loose floor: min-of-25 wall clocks on shared CI
        # boxes still jitter by tens of µs, and the honest ratio moves
        # with workload shape (a sandbox-CPU round once read 2.3×).
        # What must never regress is the *direction*: the fused pipeline
        # strictly beats op-at-a-time on framework overhead.
        assert fused < eager / 1.1, (fused, eager)


class TestObs:
    def test_plan_metrics_and_span(self, tmp_path):
        set_config(**TOGGLE_SETS["all_on"])
        from tensorframes_tpu import obs as obs_pkg

        sink = tmp_path / "spans.jsonl"
        obs_pkg.set_trace_sink(str(sink))
        try:
            p0 = _counter("plan.passes_total", **{"pass": "fuse_maps"})
            _f3_chain(_src()).cache()
            assert (
                _counter("plan.passes_total", **{"pass": "fuse_maps"})
                == p0 + 1
            )
        finally:
            obs_pkg.set_trace_sink(None)
        assert '"plan.optimize"' in sink.read_text()
