"""Fleet telemetry plane (ISSUE 16): cross-process metric federation
(``obs/export.py`` + ``obs/aggregate.py``), per-request cost
attribution (``obs/requests.py`` + the engine's finish hook), and
drift detection over the observatory (``obs/drift.py``).

The acceptance bar: ``GET /varz?scope=fleet`` merges metrics from at
least two REAL OS processes with bucket-exact histogram quantiles (==
a hand-combined oracle); a kill -9'd exporter stays visible but
flagged stale; a chaos-injected decode-latency shift flips
``obs.drift_active`` within one evaluation window and clears after
recovery; and every completed request carries tokens / KV pages /
estimated FLOPs / tenant in its cost record.

Everything here is CPU-only, seeded, and deterministic; the suite is
tier-1 (``make test-obsfleet``). Scratch metrics use ``t.``-prefixed
names, which the docs<->code drift gate ignores by convention.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

from tensorframes_tpu import obs
from tensorframes_tpu.obs import (
    aggregate,
    drift,
    export,
    flight,
    requests as obs_requests,
    timeseries,
)
from tensorframes_tpu.interop.serving import ScoringServer
from tensorframes_tpu.obs.metrics import (
    DEFAULT_BUCKETS,
    MetricsRegistry,
    quantile_from_counts,
)
from tensorframes_tpu.utils import get_config, set_config

pytestmark = pytest.mark.obsfleet

VOCAB = 32


@pytest.fixture(scope="module")
def lm():
    from tensorframes_tpu.models import TransformerLM

    return TransformerLM.init(0, VOCAB, d_model=16, n_heads=4, max_len=64)


@pytest.fixture(autouse=True)
def _isolated_plane():
    """Each test sees an empty store / drift state / request ring and a
    disabled telemetry dir, and leaves them that way."""
    prev_tdir = get_config().telemetry_dir
    timeseries.store().reset()
    drift.monitor().reset()
    obs_requests.reset()
    yield
    set_config(telemetry_dir=prev_tdir)
    obs_requests.reset()
    drift.monitor().reset()
    timeseries.store().reset()


def _http_get(host, port, path):
    c = socket.create_connection((host, port), timeout=60)
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
    return head.split(b"\r\n")[0].decode(), body


# ---------------------------------------------------------------------------
# export: per-process snapshots
# ---------------------------------------------------------------------------


class TestExportSnapshot:
    def test_disabled_without_dir(self, monkeypatch):
        monkeypatch.delenv("TFT_TELEMETRY_DIR", raising=False)
        set_config(telemetry_dir="")
        assert export.telemetry_dir() == ""
        assert export.export_snapshot() is None

    def test_kill_switch_parity(self, tmp_path):
        set_config(observability=False, telemetry_dir=str(tmp_path))
        try:
            assert export.export_snapshot() is None
            assert export.autoexport() is None
            assert list(tmp_path.iterdir()) == []
        finally:
            set_config(observability=True)

    def test_snapshot_roundtrip(self, tmp_path):
        c = obs.counter("t.exp_total", "scratch", labels=("k",))
        c.inc(4, k="x")
        timeseries.store().record("t.exp_series", 100.0, 2.5)
        set_config(telemetry_dir=str(tmp_path))
        path = export.export_snapshot(now=101.0)
        assert path is not None and os.path.exists(path)
        snap = json.loads(open(path).read())
        assert snap["schema"] == export.SCHEMA_VERSION
        assert snap["proc"] == export.proc_id()
        assert snap["pid"] == os.getpid()
        assert snap["identity"]["role"] in (
            "driver", "serve-replica", "job-worker"
        )
        assert snap["metrics"]["t.exp_total"]["values"]["k=x"] == 4.0
        assert snap["series"]["t.exp_series"] == [[100.0, 2.5]]

    def test_atomic_write_leaves_no_tmp(self, tmp_path):
        set_config(telemetry_dir=str(tmp_path))
        export.export_snapshot()
        leftovers = [p for p in os.listdir(tmp_path) if ".tmp-" in p]
        assert leftovers == []

    def test_autoexport_throttles(self, tmp_path):
        set_config(
            telemetry_dir=str(tmp_path), obs_export_interval_s=3600.0
        )
        first = export.autoexport()
        second = export.autoexport()
        # whichever call was inside the throttle window returns None;
        # at most one write per interval
        assert [first, second].count(None) >= 1

    def test_exports_counter_increments(self, tmp_path):
        set_config(telemetry_dir=str(tmp_path))
        before = (
            obs.registry()
            .snapshot()["obs.telemetry_exports_total"]["values"]
            .get("", 0.0)
        )
        assert export.export_snapshot() is not None
        after = obs.registry().snapshot()[
            "obs.telemetry_exports_total"
        ]["values"][""]
        assert after == before + 1


class TestIdentity:
    def test_set_identity_round_trip(self):
        try:
            ident = export.set_identity("job-worker")
            assert ident["role"] == "job-worker"
            assert ident["pid"] == os.getpid()
            snap = obs.registry().snapshot()["build.info"]
            assert snap["labels"] == ["proc", "pid", "role", "version",
                                      "device"]
            live = {
                ls: v for ls, v in snap["values"].items() if v == 1.0
            }
            assert len(live) == 1
            assert "role=job-worker" in next(iter(live))
        finally:
            export.set_identity("driver")

    def test_role_change_zeroes_former_series(self):
        try:
            export.set_identity("job-worker")
            export.set_identity("serve-replica")
            values = obs.registry().snapshot()["build.info"]["values"]
            for ls, v in values.items():
                if "role=job-worker" in ls:
                    assert v == 0.0
                if "role=serve-replica" in ls:
                    assert v == 1.0
        finally:
            export.set_identity("driver")

    def test_proc_id_env_override(self, monkeypatch):
        monkeypatch.setenv("TFT_PROC_ID", "replica-7")
        assert export.proc_id() == "replica-7"

    def test_server_without_engine_initializes_no_backend(self):
        """A chip belongs to one process: a process that fronts engines
        elsewhere (a router, a score-only driver before its first
        partition) must start, label itself and answer every status
        endpoint without initializing a jax backend."""
        script = r"""
import socket
from jax._src import xla_bridge
from tensorframes_tpu.interop import ScoringServer

srv = ScoringServer(lambda x: {"y": x * 2.0})
host, port = srv.start()
for path in ("/metrics", "/statusz", "/healthz", "/varz"):
    with socket.create_connection((host, port), timeout=10) as c:
        c.sendall(
            f"GET {path} HTTP/1.1\r\nHost: x\r\n"
            f"Connection: close\r\n\r\n".encode()
        )
        buf = b""
        while chunk := c.recv(65536):
            buf += chunk
    assert buf.startswith(b"HTTP/1.1 200"), (path, buf[:80])
    if path == "/metrics":
        assert b'role="driver"' in buf and b'device="unknown"' in buf
srv.stop()
assert not xla_bridge.backends_are_initialized()
"""
        out = subprocess.run(
            [sys.executable, "-c", script],
            capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stderr[-2000:]


# ---------------------------------------------------------------------------
# aggregate: read-side merge semantics
# ---------------------------------------------------------------------------


def _snap(proc, mtime, metrics=None, series=None, role="driver"):
    return {
        "schema": export.SCHEMA_VERSION,
        "proc": proc,
        "pid": 1,
        "ts_unix": mtime,
        "identity": {"role": role, "version": "0", "device": "cpu",
                     "host": "h"},
        "metrics": metrics or {},
        "series": series or {},
        "_mtime": mtime,
    }


def _hist_value(values):
    """Observe ``values`` into a scratch registry histogram and return
    its snapshot value dict — the per-process payload shape."""
    reg = MetricsRegistry()
    h = reg.histogram("t.h", "oracle")
    for v in values:
        h.observe(v)
    return reg.snapshot()["t.h"]["values"][""]


class TestAggregateMerge:
    def test_counters_sum_per_label(self):
        a = _snap("a", 100.0, metrics={
            "t.c": {"type": "counter", "help": "", "labels": ["k"],
                    "values": {"k=x": 3.0, "k=y": 1.0}},
        })
        b = _snap("b", 100.0, metrics={
            "t.c": {"type": "counter", "help": "", "labels": ["k"],
                    "values": {"k=x": 5.0}},
        })
        out = aggregate.merge([a, b], now=100.0, stale_after_s=60.0)
        assert out["metrics"]["t.c"]["values"] == {"k=x": 8.0, "k=y": 1.0}

    def test_gauges_keep_per_proc_sum_max(self):
        a = _snap("a", 100.0, metrics={
            "t.g": {"type": "gauge", "help": "", "labels": [],
                    "values": {"": 2.0}},
        })
        b = _snap("b", 100.0, metrics={
            "t.g": {"type": "gauge", "help": "", "labels": [],
                    "values": {"": 5.0}},
        })
        out = aggregate.merge([a, b], now=100.0, stale_after_s=60.0)
        merged = out["metrics"]["t.g"]["values"][""]
        assert merged["sum"] == 7.0
        assert merged["max"] == 5.0
        assert merged["procs"] == {"a": 2.0, "b": 5.0}

    def test_histogram_quantiles_bucket_exact_vs_oracle(self):
        obs_a = [1e-5, 3e-4, 0.002, 0.002, 0.4]
        obs_b = [0.008, 0.03, 0.03, 2.5]
        a = _snap("a", 100.0, metrics={
            "t.h": {"type": "histogram", "help": "", "labels": [],
                    "buckets": list(DEFAULT_BUCKETS),
                    "values": {"": _hist_value(obs_a)}},
        })
        b = _snap("b", 100.0, metrics={
            "t.h": {"type": "histogram", "help": "", "labels": [],
                    "buckets": list(DEFAULT_BUCKETS),
                    "values": {"": _hist_value(obs_b)}},
        })
        out = aggregate.merge([a, b], now=100.0, stale_after_s=60.0)
        merged = out["metrics"]["t.h"]["values"][""]
        # the oracle: one histogram that observed the UNION
        oracle = _hist_value(obs_a + obs_b)
        assert merged["counts"] == oracle["counts"]
        assert merged["count"] == len(obs_a) + len(obs_b)
        assert merged["sum"] == pytest.approx(sum(obs_a) + sum(obs_b))
        for suffix, q in (("p50", 0.5), ("p99", 0.99)):
            assert merged[suffix] == quantile_from_counts(
                list(DEFAULT_BUCKETS), oracle["counts"],
                oracle["count"], q,
            )

    def test_mismatched_buckets_flagged_not_merged(self):
        a = _snap("a", 100.0, metrics={
            "t.h": {"type": "histogram", "help": "", "labels": [],
                    "buckets": [1.0, 2.0],
                    "values": {"": {"counts": [1, 0, 0], "sum": 0.5,
                                     "count": 1}}},
        })
        b = _snap("b", 100.0, metrics={
            "t.h": {"type": "histogram", "help": "", "labels": [],
                    "buckets": [1.0, 4.0],
                    "values": {"": {"counts": [0, 1, 0], "sum": 3.0,
                                     "count": 1}}},
        })
        out = aggregate.merge([a, b], now=100.0, stale_after_s=60.0)
        entry = out["metrics"]["t.h"]
        assert entry.get("mixed_buckets") is True
        assert entry["values"][""]["count"] == 1  # first proc kept

    def test_stale_flagged_never_dropped(self):
        fresh = _snap("fresh", 100.0, metrics={
            "t.c": {"type": "counter", "help": "", "labels": [],
                    "values": {"": 1.0}},
        })
        dead = _snap("dead", 10.0, metrics={
            "t.c": {"type": "counter", "help": "", "labels": [],
                    "values": {"": 41.0}},
        })
        out = aggregate.merge([fresh, dead], now=101.0,
                              stale_after_s=15.0)
        by_proc = {p["proc"]: p for p in out["procs"]}
        assert by_proc["fresh"]["stale"] is False
        assert by_proc["dead"]["stale"] is True
        assert by_proc["dead"]["age_s"] == pytest.approx(91.0)
        # the dead process's counters still count
        assert out["metrics"]["t.c"]["values"][""] == 42.0

    def test_series_align_by_tick_rate_sums_level_means(self):
        a = _snap("a", 100.0, series={
            "t.q.rate": [[100.2, 3.0], [101.1, 5.0]],
            "t.depth": [[100.4, 10.0]],
        })
        b = _snap("b", 100.0, series={
            "t.q.rate": [[100.7, 4.0]],
            "t.depth": [[100.6, 30.0]],
        })
        out = aggregate.merge([a, b], now=101.0, stale_after_s=60.0)
        rate = out["series"]["t.q.rate"]
        assert rate["merge"] == "sum"
        assert rate["points"] == [[100.0, 7.0], [101.0, 5.0]]
        depth = out["series"]["t.depth"]
        assert depth["merge"] == "mean"
        assert depth["points"] == [[100.0, 20.0]]
        assert rate["procs"] == ["a", "b"]

    def test_read_snapshots_skips_foreign_files(self, tmp_path):
        (tmp_path / "good.json").write_text(json.dumps(
            {k: v for k, v in _snap("good", 1.0).items()
             if k != "_mtime"}
        ))
        (tmp_path / "bad-schema.json").write_text(json.dumps(
            {"schema": 999, "proc": "x"}
        ))
        (tmp_path / "torn.json").write_text('{"schema": 1, "proc": ')
        (tmp_path / "notes.txt").write_text("not telemetry")
        snaps = aggregate.read_snapshots(str(tmp_path))
        assert [s["proc"] for s in snaps] == ["good"]
        assert "_mtime" in snaps[0]

    def test_fleet_status_memoizes_parse_on_dir_stamp(self, tmp_path):
        set_config(telemetry_dir=str(tmp_path))
        export.export_snapshot()
        first = aggregate.fleet_status(str(tmp_path))
        assert first["dir"] == str(tmp_path)
        assert len(first["procs"]) == 1
        # unchanged directory -> the parsed snapshots are reused (the
        # merge still recomputes, so ages advance)
        again = aggregate.fleet_status(str(tmp_path))
        assert [p["proc"] for p in again["procs"]] == [
            p["proc"] for p in first["procs"]
        ]
        # a new export changes the stamp and is picked up
        obs.counter("t.memo_total", "scratch").inc()
        export.export_snapshot()
        updated = aggregate.fleet_status(str(tmp_path))
        assert "t.memo_total" in updated["metrics"]


# ---------------------------------------------------------------------------
# multi-process federation (the acceptance test)
# ---------------------------------------------------------------------------

_EXPORTER_SCRIPT = r"""
import os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from tensorframes_tpu import obs
from tensorframes_tpu.obs import export

mode = sys.argv[1]
c = obs.counter("t.fed_total", "federated scratch counter", labels=("k",))
h = obs.histogram("t.fed_seconds", "federated scratch histogram")
incs = int(sys.argv[2])
c.inc(incs, k="x")
for v in sys.argv[3].split(","):
    h.observe(float(v))
export.set_identity("job-worker")
p = export.export_snapshot()
assert p, "export failed"
print("READY", flush=True)
if mode == "loop":
    while True:
        time.sleep(0.1)
        export.export_snapshot()
else:  # park: stop refreshing, wait to be kill -9'd
    while True:
        time.sleep(60)
"""


@pytest.mark.slow
class TestMultiProcessFederation:
    def test_varz_fleet_merges_real_processes_and_flags_killed(
        self, tmp_path
    ):
        """Two real exporter subprocesses + this process: merged
        counters equal the per-process sum, merged histogram quantiles
        equal the hand-combined oracle, and the kill -9'd exporter is
        visible but stale while the live one stays fresh."""
        tdir = str(tmp_path / "telemetry")
        a_obs = [0.001, 0.004, 0.2]
        b_obs = [0.02, 0.3, 0.0005]
        my_obs = [0.08]

        def spawn(proc_id, mode, incs, values):
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            env["TFT_TELEMETRY_DIR"] = tdir
            env["TFT_PROC_ID"] = proc_id
            return subprocess.Popen(
                [sys.executable, "-c", _EXPORTER_SCRIPT, mode,
                 str(incs), ",".join(str(v) for v in values)],
                env=env, stdout=subprocess.PIPE, text=True,
            )

        live = spawn("fed-live", "loop", 3, a_obs)
        doomed = spawn("fed-doomed", "park", 5, b_obs)
        try:
            for p in (live, doomed):
                line = p.stdout.readline()
                assert "READY" in line, f"exporter failed: {line!r}"
            # kill -9 the parked exporter: its file stops refreshing
            doomed.send_signal(signal.SIGKILL)
            doomed.wait(timeout=30)
            # this process is the third member of the fleet
            c = obs.counter(
                "t.fed_total", "federated scratch counter", labels=("k",)
            )
            h = obs.histogram(
                "t.fed_seconds", "federated scratch histogram"
            )
            c.inc(2, k="x")
            for v in my_obs:
                h.observe(v)
            set_config(telemetry_dir=tdir)
            # age the corpse past the staleness bar while the live
            # exporter keeps refreshing its snapshot
            time.sleep(1.2)
            export.export_snapshot()

            srv = ScoringServer(lambda x: {"y": x * 2.0})
            with srv as addr:
                host, port_s = addr.rsplit(":", 1)
                status, body = _http_get(
                    host, int(port_s), "/varz?scope=fleet"
                )
            assert status.startswith("HTTP/1.1 200")
            view = json.loads(body)
            assert view["scope"] == "fleet"
            assert view["enabled"] is True

            by_proc = {p["proc"]: p for p in view["procs"]}
            assert {"fed-live", "fed-doomed"} <= set(by_proc)
            assert len(by_proc) == 3
            # counters merged across all three OS processes
            assert view["metrics"]["t.fed_total"]["values"][
                "k=x"
            ] == 10.0
            # histogram quantiles: bucket-exact == hand-combined oracle
            merged = view["metrics"]["t.fed_seconds"]["values"][""]
            oracle = _hist_value(a_obs + b_obs + my_obs)
            assert merged["counts"] == oracle["counts"]
            for suffix, q in (("p50", 0.5), ("p99", 0.99)):
                assert merged[suffix] == quantile_from_counts(
                    list(DEFAULT_BUCKETS), oracle["counts"],
                    oracle["count"], q,
                )
            # the kill -9'd worker: visible, counted, flagged stale
            stale_view = aggregate.fleet_status(
                tdir, stale_after_s=1.0
            )
            sp = {p["proc"]: p for p in stale_view["procs"]}
            assert sp["fed-doomed"]["stale"] is True
            assert sp["fed-live"]["stale"] is False
            assert sp["fed-doomed"]["role"] == "job-worker"
            assert stale_view["metrics"]["t.fed_total"]["values"][
                "k=x"
            ] == 10.0
        finally:
            for p in (live, doomed):
                if p.poll() is None:
                    p.kill()
                    p.wait(timeout=30)
                p.stdout.close()

    def test_fleet_scope_without_dir_reports_disabled(self, monkeypatch):
        monkeypatch.delenv("TFT_TELEMETRY_DIR", raising=False)
        set_config(telemetry_dir="")
        srv = ScoringServer(lambda x: {"y": x})
        with srv as addr:
            host, port_s = addr.rsplit(":", 1)
            status, body = _http_get(
                host, int(port_s), "/varz?scope=fleet"
            )
        assert status.startswith("HTTP/1.1 200")
        view = json.loads(body)
        assert view["enabled"] is False
        assert "telemetry dir" in view["error"]


# ---------------------------------------------------------------------------
# drift detection
# ---------------------------------------------------------------------------


class TestDriftDetector:
    def _mon(self, **kw):
        mon = drift.DriftMonitor()
        kw.setdefault("name", "t_det")
        kw.setdefault("series", "t.lat.p99")
        kw.setdefault("tolerance", 0.3)
        kw.setdefault("min_samples", 3)
        kw.setdefault("trigger", 2)
        mon.add(drift.Detector(**kw))
        return mon

    def _drive(self, mon, store, values, start=100.0):
        for i, v in enumerate(values):
            store.record("t.lat.p99", start + i, v)
            mon.evaluate(store, now=start + i)
        return start + len(values)

    def test_stable_series_never_flags(self):
        mon, store = self._mon(), timeseries.TimeSeriesStore()
        self._drive(mon, store, [0.01] * 12)
        assert not mon.any_active()
        (row,) = mon.report()
        assert row["active"] is False
        assert row["baseline"] == pytest.approx(0.01)

    def test_shift_flags_within_trigger_and_report_names_delta(self):
        mon, store = self._mon(), timeseries.TimeSeriesStore()
        t = self._drive(mon, store, [0.01] * 6)
        # one out-of-band sample is NOT drift (trigger=2)...
        self._drive(mon, store, [0.05], start=t)
        assert not mon.any_active()
        # ...the second consecutive one is — within one more window
        self._drive(mon, store, [0.05], start=t + 1)
        (row,) = mon.report()
        assert row["active"] is True
        assert row["series"] == "t.lat.p99"
        assert row["detector"] == "t_det"
        assert row["delta"] == pytest.approx(0.04)
        assert row["since"] == t + 1

    def test_baseline_frozen_while_drifted_then_recovers(self):
        mon, store = self._mon(), timeseries.TimeSeriesStore()
        t = self._drive(mon, store, [0.01] * 6)
        t = self._drive(mon, store, [0.05] * 5, start=t)
        (row,) = mon.report()
        assert row["active"] is True
        # frozen: five shifted samples did not drag the baseline
        assert row["baseline"] == pytest.approx(0.01)
        # returning in-band for `trigger` samples clears the flag
        self._drive(mon, store, [0.01] * 2, start=t)
        (row,) = mon.report()
        assert row["active"] is False

    def test_adopting_drift_as_normal_never_reports_recovery(self):
        """The counterexample the frozen baseline exists for: if the
        shifted value simply persists, the detector stays active
        instead of quietly rebaselining."""
        mon, store = self._mon(), timeseries.TimeSeriesStore()
        t = self._drive(mon, store, [0.01] * 6)
        self._drive(mon, store, [0.05] * 30, start=t)
        assert mon.any_active()

    def test_min_band_floors_near_zero_series(self):
        mon = drift.DriftMonitor()
        mon.add(drift.Detector(
            name="p", series="t.preempt.rate", min_samples=3,
            trigger=2, min_band=0.5,
        ))
        store = timeseries.TimeSeriesStore()
        for i, v in enumerate([0.0] * 6 + [0.4, 0.3]):
            store.record("t.preempt.rate", 100.0 + i, v)
            mon.evaluate(store, now=100.0 + i)
        # without the floor a relative band around 0 flags everything
        assert not mon.any_active()

    def test_prefix_match_covers_labeled_series(self):
        mon = drift.DriftMonitor()
        mon.add(drift.Detector(
            name="acc", series="t.accept", match="prefix",
            min_samples=3, trigger=2,
        ))
        store = timeseries.TimeSeriesStore()
        for i in range(6):
            store.record("t.accept{engine=a}", 100.0 + i, 0.8)
            store.record("t.accept{engine=b}", 100.0 + i, 0.8)
            mon.evaluate(store, now=100.0 + i)
        for i in range(6, 9):
            store.record("t.accept{engine=a}", 100.0 + i, 0.2)
            store.record("t.accept{engine=b}", 100.0 + i, 0.8)
            mon.evaluate(store, now=100.0 + i)
        rows = {r["series"]: r for r in mon.report()}
        assert rows["t.accept{engine=a}"]["active"] is True
        assert rows["t.accept{engine=b}"]["active"] is False

    def test_shift_emits_gauge_counter_and_flight_event(self):
        flight.reset()
        try:
            mon, store = self._mon(), timeseries.TimeSeriesStore()
            t = self._drive(mon, store, [0.01] * 6)
            self._drive(mon, store, [0.05] * 3, start=t)
            snap = obs.registry().snapshot()
            assert snap["obs.drift_active"]["values"][
                "series=t.lat.p99"
            ] == 1.0
            assert snap["obs.drift_shifts_total"]["values"][
                "series=t.lat.p99"
            ] >= 1.0
            ring = flight.rings().get("drift", [])
            shifts = [e for e in ring if e["kind"] == "shift"]
            assert shifts and shifts[-1]["series"] == "t.lat.p99"
        finally:
            flight.reset()

    def test_detector_validation(self):
        with pytest.raises(ValueError):
            drift.Detector(name="x", series="s", match="regex")
        with pytest.raises(ValueError):
            drift.Detector(name="x", series="s", alpha=0.0)
        with pytest.raises(ValueError):
            drift.Detector(name="x", series="s", tolerance=-1.0)

    def test_canned_detectors_installed_on_default_monitor(self):
        names = {d.name for d in drift.monitor().detectors()}
        assert {"h2d_p50", "spec_acceptance", "inter_token_p99",
                "preemption_rate"} <= names


class TestDriftEndToEnd:
    def test_chaos_decode_latency_flags_and_clears(self, lm):
        """The acceptance drill: a chaos-injected decode-step latency
        shifts ``serve.inter_token_seconds.p99``; the sampler-tick
        evaluation flips ``obs.drift_active`` within one window of the
        trigger and clears it after the chaos stops."""
        from tensorframes_tpu.serve.engine import GenerationEngine

        mon = drift.monitor()
        # the canned inter-token detector uses a relative band; this
        # drill swaps in one with an absolute floor so CPU timing noise
        # in the baseline cannot flake the recovery phase
        mon.remove("inter_token_p99")
        det = drift.Detector(
            name="itl_e2e", series="serve.inter_token_seconds.p99",
            tolerance=0.5, min_band=0.03, min_samples=3, trigger=2,
        )
        mon.add(det)
        eng = GenerationEngine(
            lm, max_slots=2, page_size=4, max_seq_len=48
        )
        series = "serve.inter_token_seconds.p99"
        tick = [0]

        def one_round():
            h = eng.submit([1, 2, 3], 4)
            eng.run_until_idle()
            h.result(timeout=60)
            tick[0] += 1
            timeseries.sample_once(now=1000.0 + tick[0])

        try:
            for _ in range(5):  # warmup + baseline (sub-ms CPU steps)
                one_round()
            assert not any(
                r["active"] for r in drift.drift_report()
                if r["detector"] == "itl_e2e"
            )
            set_config(chaos="serve.decode_step=latency:ms=80")
            try:
                for _ in range(3):  # trigger=2 + one slack window
                    one_round()
            finally:
                set_config(chaos="")
            rows = [r for r in drift.drift_report()
                    if r["detector"] == "itl_e2e"]
            assert rows and rows[0]["active"] is True
            assert rows[0]["series"] == series
            assert rows[0]["delta"] > 0.03
            assert obs.registry().snapshot()["obs.drift_active"][
                "values"
            ][f"series={series}"] == 1.0
            # recovery: chaos off, in-band rounds clear the flag
            for _ in range(4):
                one_round()
            rows = [r for r in drift.drift_report()
                    if r["detector"] == "itl_e2e"]
            assert rows and rows[0]["active"] is False
            assert obs.registry().snapshot()["obs.drift_active"][
                "values"
            ][f"series={series}"] == 0.0
        finally:
            eng.stop()
            mon.remove("itl_e2e")
            mon.add(drift.inter_token_p99())


# ---------------------------------------------------------------------------
# sampler lag + /varz liveness
# ---------------------------------------------------------------------------


class TestSamplerLag:
    def test_lag_gauge_tracks_tick_gap(self):
        timeseries.sample_once(now=500.0)
        # a deliberately slow tick: 5 s after the previous one
        timeseries.sample_once(now=505.0)
        assert obs.registry().snapshot()[
            "obs.ts_sampler_lag_seconds"
        ]["values"][""] == 5.0
        assert timeseries.last_tick_ts() == 505.0
        # a healthy cadence shrinks the gauge back
        timeseries.sample_once(now=506.0)
        assert obs.registry().snapshot()[
            "obs.ts_sampler_lag_seconds"
        ]["values"][""] == 1.0

    def test_varz_reports_last_tick_and_lag(self):
        srv = ScoringServer(lambda x: {"y": x})
        with srv as addr:
            host, port_s = addr.rsplit(":", 1)
            timeseries.sample_once()
            status, body = _http_get(host, int(port_s), "/varz")
        assert status.startswith("HTTP/1.1 200")
        view = json.loads(body)
        assert view["last_tick_ts"] is not None
        assert view["sampler_lag_s"] is not None
        assert view["sampler_lag_s"] < 120.0


# ---------------------------------------------------------------------------
# per-request cost attribution
# ---------------------------------------------------------------------------


class TestCostAttribution:
    def test_completed_request_carries_costs(self, lm, tmp_path,
                                             monkeypatch):
        from tensorframes_tpu.serve.engine import GenerationEngine

        ledger = tmp_path / "requests.jsonl"
        monkeypatch.setenv("TFT_REQUESTS_FILE", str(ledger))
        eng = GenerationEngine(
            lm, max_slots=2, page_size=4, max_seq_len=48
        )
        try:
            h = eng.submit([1, 2, 3, 4], 6, tenant="acme")
            eng.run_until_idle()
            toks = h.result(timeout=60)
        finally:
            eng.stop()
        assert len(toks) >= 1
        t = h.timings
        assert t["tokens"] == len(toks)
        assert t["kv_pages"] >= 1
        assert t["tenant"] == "acme"
        assert t.get("est_flops", 0.0) > 0.0
        rows = [r for r in obs_requests.recent()
                if r.get("request_id") == h.request_id]
        assert len(rows) == 1
        row = rows[0]
        assert row["status"] == "completed"
        assert row["tenant"] == "acme"
        assert row["tokens"] == t["tokens"]
        assert row["kv_pages"] == t["kv_pages"]
        assert row["est_flops"] == pytest.approx(t["est_flops"])
        assert row["prefix_cached_tokens"] >= 0
        # the durable feed has the same record
        lines = [json.loads(ln) for ln in
                 ledger.read_text().splitlines()]
        match = [ln for ln in lines
                 if ln.get("request_id") == h.request_id]
        assert match and match[0]["tenant"] == "acme"

    def test_every_completed_request_gets_a_record(self, lm):
        from tensorframes_tpu.serve.engine import GenerationEngine

        obs_requests.reset()
        eng = GenerationEngine(
            lm, max_slots=4, page_size=4, max_seq_len=48
        )
        try:
            handles = [
                eng.submit([1 + i, 2, 3], 4, tenant=f"team-{i % 2}")
                for i in range(4)
            ]
            eng.run_until_idle()
            for h in handles:
                h.result(timeout=60)
        finally:
            eng.stop()
        recorded = {r["request_id"] for r in obs_requests.recent()}
        assert {h.request_id for h in handles} <= recorded
        tenants = {r["tenant"] for r in obs_requests.recent()
                   if r["request_id"] in
                   {h.request_id for h in handles}}
        assert tenants == {"team-0", "team-1"}

    def test_top_by_cost_orders_by_flops_then_tokens(self):
        obs_requests.reset()
        obs_requests.record_request(request_id=1, est_flops=10.0,
                                    tokens=5)
        obs_requests.record_request(request_id=2, est_flops=99.0,
                                    tokens=1)
        obs_requests.record_request(request_id=3, est_flops=0.0,
                                    tokens=50)
        obs_requests.record_request(request_id=4, est_flops=0.0,
                                    tokens=2)
        top = obs_requests.top_by_cost(3)
        assert [r["request_id"] for r in top] == [2, 1, 3]

    def test_statusz_lists_top_costs_and_identity(self, lm):
        from tensorframes_tpu.serve.engine import GenerationEngine

        obs_requests.reset()
        eng = GenerationEngine(
            lm, max_slots=2, page_size=4, max_seq_len=48
        )
        srv = ScoringServer(engine=eng)
        with srv as addr:
            host, port_s = addr.rsplit(":", 1)
            h = eng.submit([1, 2, 3], 4, tenant="acme")
            h.result(timeout=60)
            status, body = _http_get(host, int(port_s), "/statusz")
        assert status.startswith("HTTP/1.1 200")
        page = json.loads(body)
        assert page["identity"]["role"] == "serve-replica"
        assert page["identity"]["proc"] == export.proc_id()
        costs = page["request_costs"]
        assert any(r.get("tenant") == "acme" for r in costs)

    def test_generate_endpoint_parses_tenant(self, lm):
        from tensorframes_tpu.serve.engine import GenerationEngine

        obs_requests.reset()
        eng = GenerationEngine(
            lm, max_slots=2, page_size=4, max_seq_len=48
        )
        srv = ScoringServer(engine=eng)
        with srv as addr:
            host, port_s = addr.rsplit(":", 1)
            spec = json.dumps({
                "prompt": [1, 2, 3], "max_new_tokens": 4,
                "tenant": "bill-me",
            }).encode()
            c = socket.create_connection((host, int(port_s)),
                                         timeout=60)
            try:
                c.sendall(
                    b"POST /generate HTTP/1.1\r\nHost: x\r\n"
                    + f"Content-Length: {len(spec)}\r\n\r\n".encode()
                    + spec
                )
                buf = b""
                while True:
                    chunk = c.recv(65536)
                    if not chunk:
                        break
                    buf += chunk
            finally:
                c.close()
            head, _, body = buf.partition(b"\r\n\r\n")
            assert head.split(b"\r\n")[0].endswith(b"200 OK")
            payload = json.loads(body)
        assert payload["timing"]["tenant"] == "bill-me"
        assert payload["timing"]["tokens"] >= 1
        assert payload["timing"]["est_flops"] >= 0.0
        rows = [r for r in obs_requests.recent()
                if r.get("tenant") == "bill-me"]
        assert rows and rows[-1]["status"] == "completed"


# ---------------------------------------------------------------------------
# debug bundles capture the triggering subsystem's series window
# ---------------------------------------------------------------------------


class TestBundleTimeseries:
    def test_dump_bundle_includes_prefixed_series_window(self, tmp_path):
        flight.reset()
        prev = get_config().debug_bundle_dir
        set_config(debug_bundle_dir=str(tmp_path / "bundles"))
        try:
            now = time.time()
            timeseries.store().record("serve.queue_depth", now, 7.0)
            timeseries.store().record("jobs.other", now, 1.0)
            path = flight.dump_bundle(
                "t_fatal", series_prefix="serve.",
                extra={"probe": True},
            )
            assert path is not None
            bundle = json.loads(open(path).read())
            ts = bundle["timeseries"]
            assert ts["prefix"] == "serve."
            assert "serve.queue_depth" in ts["series"]
            assert "jobs.other" not in ts["series"]
        finally:
            set_config(debug_bundle_dir=prev)
            flight.reset()

    def test_dump_bundle_without_prefix_has_no_series_block(
        self, tmp_path
    ):
        flight.reset()
        prev = get_config().debug_bundle_dir
        set_config(debug_bundle_dir=str(tmp_path / "bundles"))
        try:
            path = flight.dump_bundle("t_plain")
            assert path is not None
            bundle = json.loads(open(path).read())
            assert "timeseries" not in bundle
        finally:
            set_config(debug_bundle_dir=prev)
            flight.reset()
