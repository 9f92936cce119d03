"""The performance observatory (ISSUE 12): the time-series store +
background sampler (``obs/timeseries.py``), the per-program
cost/roofline registry (``obs/programs.py``), the SLO burn-rate
monitors (``obs/slo.py``), and their serving surfaces (``GET /varz``,
the ``/statusz`` programs/slo tables, the degraded ``/healthz``
state).

The acceptance soak at the bottom drives the whole loop on one live
server: real generations populate the store, ``/varz`` serves
non-empty queue-depth/pages/TTFT-p99 series, ``/statusz`` lists every
compiled step program with flops/bytes/invocations/cumulative time,
and a chaos-injected decode latency burns the TTFT SLO until
``/healthz`` reports ``degraded`` with a flight-recorder event.
"""

import json
import socket
import threading
import time

import numpy as np
import pytest

import tensorframes_tpu as tft
from tensorframes_tpu import obs
from tensorframes_tpu.obs import programs, slo, timeseries
from tensorframes_tpu.obs.timeseries import TimeSeriesStore, _Ring, _Series
from tensorframes_tpu.utils import get_config, set_config

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _isolated_observatory():
    """Each test sees an empty store/monitor/program registry and
    leaves them empty (the default store is process-global)."""
    timeseries.store().reset()
    slo.monitor().clear()
    yield
    slo.monitor().clear()
    timeseries.store().reset()


@pytest.fixture(scope="module")
def lm():
    from tensorframes_tpu.models import TransformerLM

    return TransformerLM.init(0, 64, d_model=16, n_heads=4, max_len=48)


def _http(host, port, path):
    c = socket.create_connection((host, port))
    try:
        c.sendall(f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
        buf = b""
        while True:
            chunk = c.recv(65536)
            if not chunk:
                break
            buf += chunk
    finally:
        c.close()
    head, _, body = buf.partition(b"\r\n\r\n")
    status = head.split(b"\r\n")[0].decode()
    return status, body


# ---------------------------------------------------------------------------
# ring + retention tiers
# ---------------------------------------------------------------------------


class TestRing:
    def test_wraparound_keeps_newest(self):
        r = _Ring(4)
        for i in range(10):
            r.append(float(i), float(i * 10))
        pts = r.points()
        assert len(pts) == 4
        assert pts == [(6.0, 60.0), (7.0, 70.0), (8.0, 80.0), (9.0, 90.0)]
        # append after wrap keeps rolling
        r.append(10.0, 100.0)
        assert r.points()[0] == (7.0, 70.0)
        assert r.points()[-1] == (10.0, 100.0)

    def test_partial_fill_returns_in_order(self):
        r = _Ring(8)
        r.append(1.0, 1.0)
        r.append(2.0, 2.0)
        assert r.points() == [(1.0, 1.0), (2.0, 2.0)]

    def test_downsample_cascade_means_and_timestamps(self):
        """Every `factor` tier-0 appends produce one tier-1 point whose
        value is the MEAN of the collapsed span and whose timestamp is
        the span's last; tier 2 cascades the same way."""
        s = _Series("t", cap=16, factor=4, n_tiers=3)
        for i in range(16):
            s.append(float(i), float(i))
        t1 = s.tiers[1].points()
        assert len(t1) == 4
        # spans [0..3], [4..7], ... -> means 1.5, 5.5, 9.5, 13.5
        assert [v for _, v in t1] == [1.5, 5.5, 9.5, 13.5]
        assert [ts for ts, _ in t1] == [3.0, 7.0, 11.0, 15.0]
        t2 = s.tiers[2].points()
        assert len(t2) == 1
        assert t2[0] == (15.0, 7.5)  # mean of the four tier-1 means

    def test_tier_retention_outlives_raw_ring(self):
        """Once tier 0 wraps, tier 1 still covers the evicted span —
        the whole point of retention tiers."""
        store = TimeSeriesStore(samples_per_tier=8, downsample=4, tiers=2)
        for i in range(64):
            store.record("s", float(i), float(i))
        raw = store.points("s", 0)
        assert len(raw) == 8 and raw[0][0] == 56.0  # newest 8 only
        merged = store.window("s", seconds=60.0, now=63.0)
        # the window reaches back to t=3: tier 1 supplies the old span
        assert merged[0][0] < 56.0
        assert merged == sorted(merged)

    def test_window_merges_tiers_without_overlap(self):
        store = TimeSeriesStore(samples_per_tier=4, downsample=2, tiers=2)
        for i in range(12):
            store.record("s", float(i), float(i))
        pts = store.window("s", seconds=100.0, now=11.0)
        ts = [t for t, _ in pts]
        assert ts == sorted(ts)
        assert len(ts) == len(set(ts))  # no duplicated timestamps
        assert ts[-1] == 11.0  # the newest raw point is included


# ---------------------------------------------------------------------------
# store sampling semantics
# ---------------------------------------------------------------------------


class TestStoreSampling:
    def test_gauge_counter_histogram_series_shapes(self):
        store = TimeSeriesStore()
        obs.gauge("t.ob_g", "x").set(5.0)
        c = obs.counter("t.ob_total", "x")
        c.inc(10)
        h = obs.histogram("t.ob_seconds", "x")
        h.observe(0.01)
        store.sample(now=100.0)
        c.inc(20)
        h.observe(0.01)
        store.sample(now=102.0)
        assert store.latest("t.ob_g") == (102.0, 5.0)
        # counter rate: 20 increments over 2 seconds
        assert store.latest("t.ob_total.rate") == (102.0, 10.0)
        # histogram quantiles + observation rate
        assert store.latest("t.ob_seconds.p50")[1] == pytest.approx(
            h.quantile(0.5)
        )
        assert store.latest("t.ob_seconds.p99")[1] == pytest.approx(
            h.quantile(0.99)
        )
        assert store.latest("t.ob_seconds.rate") == (102.0, 0.5)

    def test_labeled_series_get_their_own_names(self):
        store = TimeSeriesStore()
        c = obs.counter("t.ob_lab_total", "x", labels=("op",))
        c.inc(3, op="a")
        store.sample(now=10.0)
        c.inc(3, op="a")
        c.inc(9, op="b")
        store.sample(now=11.0)
        store.sample(now=12.0)
        assert store.latest("t.ob_lab_total{op=a}.rate")[1] == 0.0
        # op=b first seen at t=11 (baseline), rate 0 by t=12
        assert store.latest("t.ob_lab_total{op=b}.rate")[1] == 0.0
        pts = store.points("t.ob_lab_total{op=a}.rate")
        assert pts[0] == (11.0, 3.0)

    def test_counter_reset_rebaselines_instead_of_negative_rate(self):
        store = TimeSeriesStore()
        c = obs.counter("t.ob_reset_total", "x")
        c.inc(100)
        store.sample(now=10.0)
        obs.registry().get("t.ob_reset_total")._reset()  # process restart
        c.inc(7)
        store.sample(now=11.0)  # cum went 100 -> 7: no point, re-baseline
        c.inc(5)
        store.sample(now=12.0)  # rate resumes from the new baseline
        pts = store.points("t.ob_reset_total.rate")
        assert all(v >= 0 for _, v in pts)
        assert pts == [(12.0, 5.0)]

    def test_histogram_quantiles_are_windowed_not_lifetime(self):
        """A latency spike must AGE OUT of the sampled p99: quantiles
        come from the bucket-count delta per tick, not the lifetime
        histogram — a cumulative p99 would pin any SLO over it breached
        for hours after a one-minute incident ended."""
        store = TimeSeriesStore()
        h = obs.histogram("t.ob_win_seconds", "x")
        h.observe(0.001)
        store.sample(now=10.0)  # baseline tick: no quantile point yet
        assert store.latest("t.ob_win_seconds.p99") is None
        h.observe(10.0)  # the spike
        store.sample(now=11.0)
        assert store.latest("t.ob_win_seconds.p99")[1] > 1.0
        h.observe(0.001)  # back to normal
        store.sample(now=12.0)
        assert store.latest("t.ob_win_seconds.p99")[1] < 1.0  # aged out
        store.sample(now=13.0)  # idle tick: no new observations
        assert store.latest("t.ob_win_seconds.p99")[0] == 12.0

    def test_kill_switch_parks_sampling(self):
        store = TimeSeriesStore()
        obs.gauge("t.ob_killed", "x").set(1.0)
        set_config(observability=False)
        try:
            assert store.sample(now=5.0) == 0
            assert store.names() == []
        finally:
            set_config(observability=True)

    def test_series_cap_drops_new_not_crashes(self):
        store = TimeSeriesStore()
        import tensorframes_tpu.obs.timeseries as ts_mod

        old = ts_mod._MAX_SERIES
        ts_mod._MAX_SERIES = 2
        try:
            store.record("a", 1.0, 1.0)
            store.record("b", 1.0, 1.0)
            store.record("c", 1.0, 1.0)  # dropped
            assert store.names() == ["a", "b"]
            store.record("a", 2.0, 2.0)  # existing still records
            assert len(store.points("a")) == 2
        finally:
            ts_mod._MAX_SERIES = old

    def test_background_sampler_refcount(self):
        set_config(obs_sample_interval_s=0.02)
        try:
            timeseries.acquire_sampler()
            timeseries.acquire_sampler()
            assert timeseries.sampler_running()
            timeseries.release_sampler()
            assert timeseries.sampler_running()  # still one holder
            obs.gauge("t.ob_bg", "x").set(3.0)
            deadline = time.monotonic() + 5.0
            while (
                timeseries.store().latest("t.ob_bg") is None
                and time.monotonic() < deadline
            ):
                time.sleep(0.02)
            assert timeseries.store().latest("t.ob_bg") is not None
        finally:
            timeseries.release_sampler()
            set_config(obs_sample_interval_s=1.0)
        assert not timeseries.sampler_running()

    def test_sampler_release_acquire_bounce_leaves_one_thread(self):
        """A quick release->acquire (server bounce) must not leak the
        old sampler thread: each thread owns its OWN stop event, so the
        new acquire cannot un-set the event the old thread exits on."""
        set_config(obs_sample_interval_s=0.02)
        try:
            timeseries.acquire_sampler()
            timeseries.release_sampler()
            timeseries.acquire_sampler()  # immediate re-acquire
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                alive = [
                    t for t in threading.enumerate()
                    if t.name == "tft-obs-sampler" and t.is_alive()
                ]
                if len(alive) == 1:
                    break
                time.sleep(0.02)
            assert len(alive) == 1, f"{len(alive)} sampler threads alive"
            assert timeseries.sampler_running()
        finally:
            timeseries.release_sampler()
            set_config(obs_sample_interval_s=1.0)
        assert not timeseries.sampler_running()


# ---------------------------------------------------------------------------
# per-program cost registry
# ---------------------------------------------------------------------------


class TestPrograms:
    def test_matmul_costs_are_exact_2mnk(self):
        import jax

        programs.reset()
        try:
            m, k, n = 32, 48, 16
            a = np.ones((m, k), np.float32)
            b = np.ones((k, n), np.float32)
            wrapped = programs.instrument(
                jax.jit(lambda a, b: {"y": a @ b}),
                key="t:mm", name="t.matmul", kind="test",
            )
            wrapped(a, b)
            (rec,) = programs.programs()
            assert rec.flops == pytest.approx(2 * m * n * k)
            assert rec.cost_source in ("xla", "jaxpr")
            assert rec.compile_s is not None and rec.compile_s > 0
        finally:
            programs.reset()

    def test_jaxpr_fallback_matches_xla_for_matmul(self):
        import jax
        import jax.numpy as jnp

        f = lambda x: {"y": jnp.tanh(x) @ x}  # noqa: E731
        x = np.ones((8, 8), np.float32)
        flops, nbytes, _ = programs.estimate_costs(jax.jit(f), x)
        closed = jax.make_jaxpr(f)(x)
        j_flops, j_bytes = programs.jaxpr_costs(closed)
        # dot dominates and both agree on it exactly (2*8*8*8); the
        # elementwise tanh counts its outputs in both models
        assert j_flops == pytest.approx(2 * 8 * 8 * 8 + 8 * 8)
        assert flops >= 2 * 8 * 8 * 8
        assert nbytes > 0 and j_bytes == 8 * 8 * 4 * 2

    def test_dispatch_accounting_and_table_order(self):
        import jax

        programs.reset()
        try:
            w = programs.instrument(
                jax.jit(lambda x: {"y": x + 1}),
                key="t:a", name="t.a", kind="test",
            )
            x = np.ones((4,), np.float32)
            for _ in range(5):
                w(x)
            rec = w.record
            assert rec.invocations == 5
            assert rec.dispatches == 4  # first call was the compile
            assert rec.dispatch_s >= 0
            row = programs.table()[0]
            for field in (
                "compile_s", "flops", "bytes", "invocations",
                "dispatch_s", "achieved_flops_per_s",
                "intensity_flops_per_byte", "roofline_utilization",
            ):
                assert field in row
        finally:
            programs.reset()

    def test_recompile_books_into_compile_not_dispatch(self):
        """A later-signature call recompiles; its (potentially
        seconds-long) wall must land in compile_s, not corrupt the
        dispatch_s the roofline divides by. Detection: the jit's
        executable-cache depth grew."""
        import jax

        programs.reset()
        try:
            w = programs.instrument(
                jax.jit(lambda x: {"y": x * 2}),
                key="t:rc", name="t.recompile", kind="test",
            )
            w(np.ones((4,), np.float32))   # compile #1
            w(np.ones((4,), np.float32))   # dispatch
            compile_after_one = w.record.compile_s
            w(np.ones((9,), np.float32))   # NEW signature: compile #2
            w(np.ones((9,), np.float32))   # dispatch
            rec = w.record
            assert rec.invocations == 4
            assert rec.dispatches == 2
            assert rec.compile_s > compile_after_one  # accumulated
        finally:
            programs.reset()

    def test_kill_switch_is_a_pure_passthrough(self):
        """Under TFT_OBS=0 the wrapper must not even REGISTER: no
        record, nothing for /statusz to list, nothing for autopersist
        to write (registration is lazy on the first enabled call)."""
        import jax

        programs.reset()
        try:
            w = programs.instrument(
                jax.jit(lambda x: {"y": x * 2}),
                key="t:off", name="t.off", kind="test",
            )
            x = np.ones((4,), np.float32)
            set_config(observability=False)
            try:
                out = w(x)
                np.testing.assert_array_equal(np.asarray(out["y"]), x * 2)
                assert w.record is None
                assert programs.programs() == []
                assert programs.autopersist() == 0  # gated, no disk
            finally:
                set_config(observability=True)
            # flipping back on registers at the next call
            w(x)
            assert w.record is not None and w.record.invocations == 1
        finally:
            programs.reset()

    def test_engine_map_rows_registers_a_program(self):
        programs.reset()
        try:
            df = tft.TensorFrame.from_columns(
                {"x": np.ones((64, 4), np.float32)}
            ).analyze()
            tft.map_rows(lambda x: {"yy_obs": x * 2.0}, df).collect()
            names = [r.name for r in programs.programs()]
            assert any("yy_obs" in n for n in names), names
            rec = next(r for r in programs.programs() if "yy_obs" in r.name)
            assert rec.kind in ("engine.row", "engine.block")
            assert rec.flops is not None and rec.invocations >= 1
        finally:
            programs.reset()

    def test_fused_plan_composite_carries_its_label(self):
        programs.reset()
        try:
            df = tft.TensorFrame.from_columns(
                {"x": np.ones((64, 4), np.float32)}
            ).analyze()
            a = tft.map_rows(lambda x: {"m1_obs": x * 2.0}, df)
            b = tft.map_rows(lambda m1_obs: {"m2_obs": m1_obs + 1.0}, a)
            b.collect()
            names = [r.name for r in programs.programs()]
            assert any(n.startswith("plan.fused:") for n in names), names
        finally:
            programs.reset()

    def test_persist_jsonl_appends_only_dirty(self, tmp_path):
        import jax

        programs.reset()
        try:
            target = str(tmp_path / "programs.jsonl")
            w = programs.instrument(
                jax.jit(lambda x: {"y": x}),
                key="t:p", name="t.persist", kind="test",
            )
            w(np.ones((2,), np.float32))
            assert programs.persist(target) == 1
            assert programs.persist(target) == 0  # nothing moved
            w(np.ones((2,), np.float32))
            assert programs.persist(target) == 1
            lines = [
                json.loads(ln)
                for ln in open(target).read().splitlines()
            ]
            assert len(lines) == 2
            assert lines[0]["name"] == "t.persist"
            assert lines[1]["invocations"] == 2
            assert {"ts", "host", "pid", "flops", "dispatch_s"} <= set(
                lines[1]
            )
        finally:
            programs.reset()

    def test_peak_override_enables_roofline(self, monkeypatch):
        import jax

        programs.reset()
        try:
            monkeypatch.setenv("TFT_PEAK_FLOPS", "1e12")
            w = programs.instrument(
                jax.jit(lambda a, b: {"y": a @ b}),
                key="t:r", name="t.roof", kind="test",
            )
            a = np.ones((64, 64), np.float32)
            w(a, a)
            w(a, a)
            row = programs.table()[0]
            assert row["roofline_utilization"] is not None
            assert 0 < row["roofline_utilization"] < 1
        finally:
            programs.reset()

    def test_serve_engine_registers_named_step_programs(self, lm):
        from tensorframes_tpu.serve.engine import GenerationEngine

        programs.reset()
        try:
            eng = GenerationEngine(
                lm, max_slots=2, page_size=4, max_seq_len=32, name="rX"
            )
            h = eng.submit([1, 2, 3], 4)
            eng.run_until_idle()
            h.result(timeout=30)
            names = {r.name for r in programs.programs()}
            assert "serve.prefill[rX]" in names
            assert "serve.decode[rX]" in names
            decode = next(
                r for r in programs.programs()
                if r.name == "serve.decode[rX]"
            )
            assert decode.invocations >= 3
            assert decode.flops is not None and decode.dispatch_s > 0
        finally:
            programs.reset()

    def test_explain_analyze_appends_programs_table(self):
        programs.reset()
        try:
            df = tft.TensorFrame.from_columns(
                {"x": np.ones((16, 4), np.float32)}
            ).analyze()
            out = tft.map_rows(lambda x: {"ex_obs": x * 3.0}, df)
            out.collect()
            txt = tft.explain(out, analyze=True)
            assert "== Programs ==" in txt
            assert "ex_obs" in txt.split("== Programs ==")[1]
            # and without the flag, no table
            assert "== Programs ==" not in tft.explain(out)
        finally:
            programs.reset()


# ---------------------------------------------------------------------------
# SLO monitors
# ---------------------------------------------------------------------------


class TestSLO:
    def _ticks(self, store, series, values, start=1000.0, dt=1.0):
        for i, v in enumerate(values):
            store.record(series, start + i * dt, v)

    def test_breach_and_recovery_transitions(self):
        store = TimeSeriesStore()
        mon = slo.SLOMonitor()
        obj = mon.add(slo.Objective(
            name="t_lat", series="t.lat.p99", bound=1.0, kind="upper",
            fast_window_s=10.0, slow_window_s=20.0, min_samples=3,
        ))
        breaches = obs.registry().get("slo.breaches_total")
        base = breaches.value(slo="t_lat")
        self._ticks(store, obj.series, [5.0, 5.0, 5.0], start=1000.0)
        mon.evaluate(store, now=1002.0)
        assert mon.degraded()
        (st,) = mon.status()
        assert st["breached"] and st["fast_burn"] == 1.0
        assert breaches.value(slo="t_lat") == base + 1
        assert (
            obs.registry().get("slo.breached").value(slo="t_lat") == 1.0
        )
        # recovery: healthy samples displace the window
        self._ticks(store, obj.series, [0.1] * 12, start=1003.0)
        mon.evaluate(store, now=1014.0)
        assert not mon.degraded()
        assert (
            obs.registry().get("slo.breached").value(slo="t_lat") == 0.0
        )
        # exactly one breach counted for the whole episode
        assert breaches.value(slo="t_lat") == base + 1

    def test_flight_events_on_transition(self):
        obs.flight.reset()
        store = TimeSeriesStore()
        mon = slo.SLOMonitor()
        obj = mon.add(slo.Objective(
            name="t_ev", series="t.ev", bound=1.0,
            fast_window_s=5.0, slow_window_s=10.0, min_samples=2,
        ))
        self._ticks(store, obj.series, [9.0, 9.0], start=100.0)
        mon.evaluate(store, now=101.0)
        self._ticks(store, obj.series, [0.0] * 8, start=102.0)
        mon.evaluate(store, now=109.0)
        kinds = [
            (e["kind"], e.get("slo"))
            for e in obs.flight.rings().get("slo", [])
        ]
        assert ("breach", "t_ev") in kinds
        assert ("recovered", "t_ev") in kinds

    def test_fast_vs_sustained_severity(self):
        store = TimeSeriesStore()
        mon = slo.SLOMonitor()
        obj = mon.add(slo.Objective(
            name="t_sev", series="t.sev", bound=1.0,
            fast_window_s=4.0, slow_window_s=40.0, min_samples=2,
        ))
        # long healthy history, then a sharp recent burn: fast-only
        self._ticks(store, obj.series, [0.0] * 30, start=1000.0)
        self._ticks(store, obj.series, [5.0] * 4, start=1030.0)
        mon.evaluate(store, now=1033.0)
        (st,) = mon.status()
        assert st["breached"] and st["severity"] == "fast"
        # keep burning until the slow window crosses too
        self._ticks(store, obj.series, [5.0] * 30, start=1034.0)
        mon.evaluate(store, now=1063.0)
        (st,) = mon.status()
        assert st["severity"] == "sustained"

    def test_lower_bound_objective(self):
        store = TimeSeriesStore()
        mon = slo.SLOMonitor()
        obj = mon.add(slo.tokens_per_s_floor(
            100.0, fast_window_s=5.0, slow_window_s=10.0, min_samples=2,
        ))
        assert obj.series == "serve.tokens_total.rate"
        self._ticks(store, obj.series, [10.0, 10.0, 10.0], start=50.0)
        mon.evaluate(store, now=52.0)
        assert mon.degraded()

    def test_idle_zero_rate_does_not_breach_a_floor(self):
        """Counter rates record an explicit 0.0 every idle tick, so a
        throughput floor must not flip a healthy idle server to
        degraded: tokens_per_s_floor excludes exact-zero samples by
        default (ignore_zero=True)."""
        store = TimeSeriesStore()
        mon = slo.SLOMonitor()
        obj = mon.add(slo.tokens_per_s_floor(
            100.0, fast_window_s=5.0, slow_window_s=10.0, min_samples=2,
        ))
        self._ticks(store, obj.series, [0.0] * 5, start=50.0)  # idle
        mon.evaluate(store, now=54.0)
        assert not mon.degraded()
        # genuinely slow (nonzero but under the floor) still breaches
        self._ticks(store, obj.series, [5.0, 5.0, 5.0], start=60.0)
        mon.evaluate(store, now=62.0)
        assert mon.degraded()
        mon.clear()
        # opting out alerts on idleness itself
        mon.add(slo.tokens_per_s_floor(
            100.0, fast_window_s=5.0, slow_window_s=10.0,
            min_samples=2, ignore_zero=False,
        ))
        mon.evaluate(store, now=54.0)
        assert mon.degraded()

    def test_min_samples_gates_cold_series(self):
        store = TimeSeriesStore()
        mon = slo.SLOMonitor()
        obj = mon.add(slo.Objective(
            name="t_cold", series="t.cold", bound=1.0, min_samples=5,
            fast_window_s=10.0, slow_window_s=10.0,
        ))
        self._ticks(store, obj.series, [9.0] * 4, start=10.0)
        mon.evaluate(store, now=13.0)
        assert not mon.degraded()  # 4 < min_samples

    def test_objective_validation(self):
        with pytest.raises(ValueError):
            slo.Objective(name="x", series="s", bound=1.0, kind="sideways")
        with pytest.raises(ValueError):
            slo.Objective(name="x", series="s", bound=1.0, burn_threshold=0)
        with pytest.raises(ValueError):
            slo.Objective(
                name="x", series="s", bound=1.0,
                fast_window_s=60, slow_window_s=30,
            )


# ---------------------------------------------------------------------------
# serving surfaces
# ---------------------------------------------------------------------------


class TestEndpoints:
    def test_varz_statusz_healthz_shapes(self, lm):
        from tensorframes_tpu.interop.serving import ScoringServer
        from tensorframes_tpu.serve.engine import GenerationEngine

        programs.reset()
        prev = get_config().obs_sample_interval_s
        set_config(obs_sample_interval_s=0.02)
        eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
        srv = ScoringServer(engine=eng)
        try:
            host, port = srv.start()
            assert timeseries.sampler_running()  # the server holds it
            h = eng.submit([1, 2, 3], 4)
            h.result(timeout=60)
            deadline = time.monotonic() + 5.0
            while (
                timeseries.store().latest("serve.queue_depth") is None
                and time.monotonic() < deadline
            ):
                time.sleep(0.02)
            status, body = _http(host, port, "/varz")
            assert status.endswith("200 OK")
            varz = json.loads(body)
            assert varz["sampler_running"]
            assert "serve.queue_depth" in varz["series"]
            assert varz["series"]["serve.queue_depth"]["points"]
            # prefix + window filtering
            status, body = _http(
                host, port, "/varz?prefix=serve.queue&window=60"
            )
            filtered = json.loads(body)["series"]
            assert set(filtered) == {"serve.queue_depth"}
            status, _ = _http(host, port, "/varz?window=bogus")
            assert status.endswith("400 Bad Request")
            # statusz: programs table + slo + timeseries summary
            status, body = _http(host, port, "/statusz")
            sz = json.loads(body)
            prog_names = {p["name"] for p in sz["programs"]}
            assert any(n.startswith("serve.prefill[") for n in prog_names)
            assert any(n.startswith("serve.decode[") for n in prog_names)
            for p in sz["programs"]:
                assert {
                    "flops", "bytes", "invocations", "dispatch_s",
                    "compile_s", "roofline_utilization",
                } <= set(p)
            assert sz["timeseries"]["sampler_running"]
            assert isinstance(sz["slo"], list)
            # healthz: ok status with no objectives declared
            status, body = _http(host, port, "/healthz")
            hz = json.loads(body)
            assert status.endswith("200 OK") and hz["status"] == "ok"
            assert hz["slo"] == []
            # 404 message names the varz endpoint
            status, body = _http(host, port, "/nope")
            assert status.endswith("404 Not Found")
            assert b"/varz" in body
        finally:
            srv.stop()
            set_config(obs_sample_interval_s=prev)
            programs.reset()
        assert not timeseries.sampler_running()  # released on stop

    def test_acceptance_soak_full_observatory_loop(self, lm):
        """The ISSUE-12 acceptance: one serving soak where (1) /varz
        returns non-empty queue-depth / pages / TTFT-p99 series, (2)
        /statusz lists every compiled step program with flops / bytes /
        invocations / cumulative time, and (3) a chaos-injected decode
        latency burns the TTFT p99 SLO until /healthz flips to the
        degraded state (still 200 — distinct from unhealthy) with a
        flight-recorder breach event."""
        from tensorframes_tpu.interop.serving import ScoringServer
        from tensorframes_tpu.serve.engine import GenerationEngine

        programs.reset()
        obs.flight.reset()
        prev = get_config().obs_sample_interval_s
        set_config(obs_sample_interval_s=0.02)
        # quantile points land only on ticks with NEW TTFT observations
        # (windowed quantiles), so this low-traffic soak sizes the fast
        # window to a couple of request waves and accepts a single
        # violating sample — the tuning guidance docs/observability.md
        # gives for sparse series
        slo.monitor().add(slo.ttft_p99(
            0.5, fast_window_s=3.0, slow_window_s=12.0, min_samples=1,
        ))
        eng = GenerationEngine(lm, max_slots=4, page_size=4, max_seq_len=32)
        srv = ScoringServer(engine=eng)
        rng = np.random.default_rng(5)
        try:
            host, port = srv.start()

            def drive(n):
                handles = [
                    eng.submit(
                        list(rng.integers(1, 60, size=4)), 6, block=True
                    )
                    for _ in range(n)
                ]
                for h in handles:
                    h.result(timeout=60)

            # warmup pays the step-program compiles, then the registry
            # resets: ttft_seconds is a LIFETIME histogram, and a
            # compile-heavy first TTFT would otherwise pin its p99 over
            # the bound before any chaos fires (programs' compile_s is
            # recorded on the cost registry, which reset() leaves alone)
            drive(2)
            obs.registry().reset()
            timeseries.store().reset()

            # healthy traffic: one wave per drive (4 requests ≤
            # max_slots, so no queue wait inflates TTFT near the bound)
            drive(4)
            time.sleep(0.3)
            status, body = _http(host, port, "/healthz")
            assert json.loads(body)["status"] == "ok"

            # (3) chaos: a 1s latency on every prefill dispatch (the
            # TTFT path) burns the p99 through the 500ms bound while
            # the engine itself stays perfectly healthy
            set_config(chaos="serve.prefill=latency:ms=1000")
            try:
                deadline = time.monotonic() + 30.0
                degraded = False
                while time.monotonic() < deadline and not degraded:
                    drive(2)
                    time.sleep(0.1)
                    status, body = _http(host, port, "/healthz")
                    hz = json.loads(body)
                    degraded = hz["status"] == "degraded"
                assert degraded, "SLO breach never degraded /healthz"
                assert status.endswith("200 OK")  # degraded != unhealthy
                assert hz["healthy"] is True
                burning = [s for s in hz["slo"] if s["breached"]]
                assert burning and burning[0]["name"] == "ttft_p99"
            finally:
                set_config(chaos="")
            breach_events = [
                e for e in obs.flight.rings().get("slo", [])
                if e["kind"] == "breach" and e.get("slo") == "ttft_p99"
            ]
            assert breach_events, "breach left no flight-recorder event"

            # (1) /varz: the three acceptance series are non-empty
            status, body = _http(host, port, "/varz")
            series = json.loads(body)["series"]
            for name in (
                "serve.queue_depth",
                "serve.pages_in_use",
                "serve.ttft_seconds.p99",
            ):
                assert series.get(name, {}).get("points"), name
            # the injected latency is visible in the stored p99
            p99_values = [
                v for _, v in series["serve.ttft_seconds.p99"]["points"]
            ]
            assert max(p99_values) > 0.25

            # (2) /statusz: every compiled step program, with costs
            status, body = _http(host, port, "/statusz")
            sz = json.loads(body)
            by_name = {p["name"]: p for p in sz["programs"]}
            prefill = by_name[f"serve.prefill[{eng.name}]"]
            decode = by_name[f"serve.decode[{eng.name}]"]
            for p in (prefill, decode):
                assert p["flops"] and p["bytes"]
                assert p["invocations"] >= 1
                assert p["dispatch_s"] >= 0 and p["compile_s"] > 0
            assert decode["invocations"] > prefill["invocations"]
            slo_rows = {s["name"]: s for s in sz["slo"]}
            assert "ttft_p99" in slo_rows
        finally:
            srv.stop()
            set_config(obs_sample_interval_s=prev, chaos="")
            slo.monitor().clear()
            programs.reset()
            obs.flight.reset()


# ---------------------------------------------------------------------------
# sampler overhead
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestSamplerOverhead:
    def test_sampler_overhead_within_budget(self):
        """The ISSUE-12 ≤1% budget, asserted on a map_rows scoring
        pass: interleaved best-of passes with the background sampler at
        a 0.25s cadence vs parked. The assert allows 5% — this shared
        CI host jitters more than the budget itself, and no chip run
        has taken the number (docs/observability.md); a wired
        per-dispatch cost (the failure this guards) shows up as tens of
        percent."""
        import time as _time

        rng = np.random.default_rng(0)
        x = rng.normal(size=(120_000, 64)).astype(np.float32)
        df = tft.TensorFrame.from_columns({"features": x}).analyze()
        w = np.asarray(
            rng.normal(size=(64, 64)).astype(np.float32)
        )

        def score(features):
            import jax.numpy as jnp

            return {"s": jnp.tanh(features @ w).sum(axis=-1)}

        def one():
            t0 = _time.perf_counter()
            tft.map_rows(score, df).collect()
            return _time.perf_counter() - t0

        one()  # compile warmup
        prev = get_config().obs_sample_interval_s
        on = off = float("inf")
        try:
            set_config(obs_sample_interval_s=0.25)
            for _ in range(6):
                timeseries.acquire_sampler()
                try:
                    on = min(on, one())
                finally:
                    timeseries.release_sampler()
                off = min(off, one())
        finally:
            set_config(obs_sample_interval_s=prev)
        overhead = (on - off) / off * 100.0
        assert overhead <= 5.0, (
            f"sampler overhead {overhead:.2f}% exceeds budget "
            f"(on={on:.4f}s off={off:.4f}s)"
        )
