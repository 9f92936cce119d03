"""Self-tuning performance layer (``tensorframes_tpu/tune``, ISSUE 13).

Covers the three pieces and their wiring:

- **store** (``tune/store.py``): atomic-rename durability — concurrent
  two-process winner writes, ``kill -9`` mid-write → clean re-read,
  schema-version mismatch → ignore-and-retune, corrupt-line tolerance,
  cross-process mtime re-read;
- **model** (``tune/model.py``): the ridge fit recovers synthetic
  weights, thin data falls back to the analytic prior, ranking orders
  by predicted cost;
- **search** (``tune/search.py``): online tuning installs + persists a
  median-wall winner, the learned ranker prunes trials to ≤ half the
  grid, budgets degrade to the default, trials retry under chaos and
  skip on fatal faults, the ``tune.trial`` chaos site is a first-class
  dispatch site;
- **byte-identity** (the acceptance contract): for every tuned surface
  — flash tiles, transfer chunking, map-rows block rows, serve page
  size + prefill chunk — results with autotune on (pinned or online,
  incl. under chaos and a mid-trial process kill) are byte-identical
  to ``TFT_TUNE=0``;
- **persistence round-trip**: a winner tuned by a REAL subprocess is
  served in this process with zero trials (asserted on the tuner's own
  counters);
- satellites: the ``paged_page_size_hint`` serving default + /healthz
  report, /statusz + /varz export, ``explain(analyze=True)``.
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

import tensorframes_tpu as tft
from tensorframes_tpu import tune
from tensorframes_tpu.models import TransformerLM
from tensorframes_tpu.obs import metrics as obs_metrics
from tensorframes_tpu.tune.model import CostModel
from tensorframes_tpu.tune.store import SCHEMA_VERSION, TuneStore
from tensorframes_tpu.utils import get_config, set_config

pytestmark = pytest.mark.tune

VOCAB = 32


@pytest.fixture(scope="module")
def lm():
    return TransformerLM.init(0, VOCAB, d_model=16, n_heads=4, max_len=48)


_TUNE_FIELDS = (
    "autotune", "tune_mode", "tune_budget_s", "tune_trials",
    "tune_top_k", "tune_file", "max_rows_per_device_call",
    "max_retries", "retry_backoff_s", "chaos",
)


@pytest.fixture
def tune_env(tmp_path, monkeypatch):
    """A per-test tuning world: private store file, fresh tuner, config
    restored afterwards. Yields the store path."""
    store = str(tmp_path / "tune.jsonl")
    monkeypatch.setenv("TFT_TUNE_FILE", store)
    monkeypatch.delenv("TFT_TUNE", raising=False)
    prev = {f: getattr(get_config(), f) for f in _TUNE_FIELDS}
    tune.reset()
    yield store
    set_config(**prev)
    tune.reset()


def _totals(name):
    snap = obs_metrics.snapshot().get(name, {})
    return float(sum((snap.get("values") or {}).values()))


def _err_hist_count():
    s = obs_metrics.registry().get("tune.predicted_error_ratio").series()
    return 0 if s is None else s["count"]


# ---------------------------------------------------------------------------
# store units
# ---------------------------------------------------------------------------


class TestStore:
    def test_put_get_roundtrip_atomic(self, tune_env):
        s = TuneStore(tune_env)
        rec = s.put(
            "surf|sig=1|dev", {"rows": 7}, wall_s=0.5, meta={"trials": 2}
        )
        assert rec["v"] == SCHEMA_VERSION
        got = s.get("surf|sig=1|dev")
        assert got["config"] == {"rows": 7}
        assert got["surface"] == "surf" and got["device"] == "dev"
        # atomic rename: the target parses, and no temp litter remains
        with open(tune_env) as f:
            for line in f:
                json.loads(line)
        litter = [
            n for n in os.listdir(os.path.dirname(tune_env))
            if n.endswith(".tmp")
        ]
        assert litter == []

    def test_last_write_wins_per_key(self, tune_env):
        s = TuneStore(tune_env)
        s.put("a|b|c", {"n": 1})
        s.put("a|b|c", {"n": 2})
        assert s.get("a|b|c")["config"] == {"n": 2}
        assert len(s.entries()) == 1

    def test_corrupt_lines_are_tolerated(self, tune_env):
        s = TuneStore(tune_env)
        s.put("good|sig|dev", {"n": 1})
        with open(tune_env, "a") as f:
            f.write("{torn json!!\n")
            f.write('"not a dict"\n')
        s2 = TuneStore(tune_env)
        assert s2.get("good|sig|dev")["config"] == {"n": 1}
        assert len(s2.entries()) == 1

    def test_schema_version_mismatch_is_ignored(self, tune_env):
        s = TuneStore(tune_env)
        with open(tune_env, "w") as f:
            f.write(
                json.dumps(
                    {
                        "v": SCHEMA_VERSION + 1,
                        "key": "old|sig|dev",
                        "config": {"n": 99},
                    }
                )
                + "\n"
            )
        # ignore-and-retune: the record is invisible, not an error
        assert s.get("old|sig|dev") is None
        # a put keeps the file valid JSONL AND carries the
        # foreign-version line through verbatim — a mixed-version fleet
        # sharing one store must never erase each other's winners
        s.put("new|sig|dev", {"n": 1})
        assert s.get("new|sig|dev")["config"] == {"n": 1}
        with open(tune_env) as f:
            recs = [json.loads(ln) for ln in f if ln.strip()]
        assert any(r.get("v") == SCHEMA_VERSION + 1 for r in recs)
        assert any(r.get("v") == SCHEMA_VERSION for r in recs)

    def test_cross_process_staleness_mtime_reread(self, tune_env):
        writer = TuneStore(tune_env)
        reader = TuneStore(tune_env)
        assert reader.get("k|s|d") is None
        writer.put("k|s|d", {"n": 1})
        # distinct instance, no shared state: the mtime re-read makes
        # process A's winner visible at B's next lookup
        assert reader.get("k|s|d")["config"] == {"n": 1}
        time.sleep(0.01)  # ensure the mtime moves even on coarse clocks
        writer.put("k|s|d", {"n": 2})
        assert reader.get("k|s|d")["config"] == {"n": 2}

    def test_clear_by_surface(self, tune_env):
        s = TuneStore(tune_env)
        s.put("a|s1|d", {"n": 1})
        s.put("b|s2|d", {"n": 2})
        assert s.clear("a") == 1
        assert s.get("a|s1|d") is None
        assert s.get("b|s2|d")["config"] == {"n": 2}
        assert s.clear() == 1
        assert s.entries() == {}


# ---------------------------------------------------------------------------
# store subprocess drills (patterns from tests/test_dist_jobs.py)
# ---------------------------------------------------------------------------

_WRITER_SCRIPT = r"""
import sys, time
from tensorframes_tpu.tune.store import TuneStore

path, tag = sys.argv[1:3]
s = TuneStore(path)
end = time.time() + 0.8
i = 0
while time.time() < end:
    for j in range(5):
        s.put(f"surf{tag}|k{j}|dev", {"writer": tag, "iter": i, "j": j})
    i += 1
print("W_DONE", tag, i, flush=True)
"""

_KILL_WRITER_SCRIPT = r"""
import sys
from tensorframes_tpu.tune.store import TuneStore

s = TuneStore(sys.argv[1])
print("WRITING", flush=True)
i = 0
while True:
    s.put("kill|sig|dev", {"n": i})
    i += 1
"""


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("TFT_CHAOS", None)
    env.update(extra)
    return env


class TestStoreProcesses:
    def test_concurrent_two_process_writes_no_torn_jsonl(self, tune_env):
        """Two real processes hammer the same store concurrently: the
        file must ALWAYS parse (atomic rename — no torn line can ever
        land), every surviving record must be something a writer
        actually wrote (last-complete-wins, never a splice), and
        neither writer may crash."""
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", _WRITER_SCRIPT, tune_env, tag],
                env=_env(), stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True,
            )
            for tag in ("1", "2")
        ]
        for p in procs:
            out, err = p.communicate(timeout=120)
            assert p.returncode == 0, err
            assert "W_DONE" in out
        with open(tune_env) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        assert lines, "both writers ran and nothing survived"
        entries = {}
        for ln in lines:
            rec = json.loads(ln)  # no torn JSONL, ever
            assert rec["v"] == SCHEMA_VERSION
            assert rec["surface"] in ("surf1", "surf2")
            cfg = rec["config"]
            assert cfg["writer"] in ("1", "2")
            assert rec["key"] == (
                f"surf{cfg['writer']}|k{cfg['j']}|dev"
            )
            entries[rec["key"]] = rec
        # the store reads it back cleanly too
        s = TuneStore(tune_env)
        assert set(s.entries()) == set(entries)

    def test_kill9_mid_write_clean_reread(self, tune_env):
        """A writer SIGKILLed while rewriting the store must leave a
        readable file: the rename either happened (previous complete
        state) or it did not (the one before) — never a torn tail."""
        p = subprocess.Popen(
            [sys.executable, "-c", _KILL_WRITER_SCRIPT, tune_env],
            env=_env(), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        )
        try:
            assert "WRITING" in p.stdout.readline()
            time.sleep(0.15)  # let some writes land, then murder it
            p.send_signal(signal.SIGKILL)
            assert p.wait(timeout=30) == -signal.SIGKILL
        finally:
            if p.poll() is None:
                p.kill()
        s = TuneStore(tune_env)
        entries = s.entries()  # parses — or the contract is broken
        rec = s.get("kill|sig|dev")
        if rec is not None:  # the kill may have landed before write 0
            assert isinstance(rec["config"]["n"], int)
        for r in entries.values():
            assert r["v"] == SCHEMA_VERSION


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------


class TestCostModel:
    def test_ridge_fit_recovers_synthetic_weights(self):
        rng = np.random.default_rng(0)
        w_f, w_b, w_0 = 2e-11, 5e-10, 1e-4
        records = []
        for _ in range(64):
            flops = float(rng.uniform(1e6, 1e9))
            nbytes = float(rng.uniform(1e5, 1e8))
            wall = w_f * flops + w_b * nbytes + w_0
            records.append(
                {
                    "flops": flops, "bytes": nbytes,
                    "dispatches": 10, "dispatch_s": wall * 10,
                }
            )
        m = CostModel.fit(records)
        assert m.source == "ridge"
        for flops, nbytes in ((5e8, 1e7), (1e7, 5e7)):
            truth = w_f * flops + w_b * nbytes + w_0
            assert abs(m.predict(flops, nbytes) - truth) / truth < 0.05

    def test_thin_data_falls_back_to_analytic_prior(self):
        m = CostModel.fit([{"flops": 1.0, "bytes": 1.0,
                            "dispatches": 1, "dispatch_s": 1.0}])
        assert m.source == "analytic"
        assert m.w_flops > 0 and m.w_bytes > 0 and m.w_overhead > 0

    def test_rank_orders_by_predicted_cost(self):
        m = CostModel(1e-12, 1e-10, 1e-4)
        cands = [{"n": n} for n in (1, 4, 2)]

        def feats(c):
            return 0.0, 0.0, float(c["n"])  # cost = overhead * n

        ranked = m.rank(cands, feats)
        assert [c["n"] for c, _ in ranked] == [1, 2, 4]
        # a candidate whose features raise ranks last, not fatally
        def bad_feats(c):
            if c["n"] == 1:
                raise RuntimeError("boom")
            return 0.0, 0.0, float(c["n"])

        ranked = m.rank(cands, bad_feats)
        assert ranked[-1][0]["n"] == 1


# ---------------------------------------------------------------------------
# search semantics
# ---------------------------------------------------------------------------


def _sleep_trial(ms_by_n):
    def trial(cand):
        time.sleep(ms_by_n[cand["n"]] / 1000.0)

    return trial


class TestSearch:
    def test_off_mode_and_kill_switch_return_default(
        self, tune_env, monkeypatch
    ):
        set_config(autotune=False, tune_mode="online")
        calls = []
        out = tune.lookup(
            "t.s", "sig", {"n": 1}, grid=[{"n": 2}],
            trial=lambda c: calls.append(c),
        )
        assert out == {"n": 1} and calls == []
        set_config(autotune=True)
        monkeypatch.setenv("TFT_TUNE", "0")
        out = tune.lookup(
            "t.s", "sig", {"n": 1}, grid=[{"n": 2}],
            trial=lambda c: calls.append(c),
        )
        assert out == {"n": 1} and calls == []
        assert tune.mode() == "off"

    def test_unknown_mode_warns_off(self, tune_env):
        set_config(autotune=True, tune_mode="turbo")
        assert tune.mode() == "off"

    def test_cached_miss_returns_default_without_trials(self, tune_env):
        set_config(autotune=True, tune_mode="cached")
        t0 = _totals("tune.trials_total")
        out = tune.lookup(
            "t.c", "sig", {"n": 1}, grid=[{"n": 2}],
            trial=lambda c: None,
        )
        assert out == {"n": 1}
        assert _totals("tune.trials_total") == t0
        assert not os.path.exists(tune_env) or TuneStore(
            tune_env
        ).entries() == {}

    def test_online_tunes_installs_persists_and_memoizes(self, tune_env):
        set_config(
            autotune=True, tune_mode="online", tune_trials=2,
            tune_budget_s=30.0,
        )
        t0 = _totals("tune.trials_total")
        h0 = _totals("tune.cache_hits_total")
        w0 = _totals("tune.winners_total")
        trial = _sleep_trial({1: 8, 2: 1, 3: 20})
        out = tune.lookup(
            "t.o", "sig", {"n": 1}, grid=[{"n": 2}, {"n": 3}],
            trial=trial,
        )
        assert out == {"n": 2}  # fastest by median wall
        assert _totals("tune.winners_total") == w0 + 1
        trials_used = _totals("tune.trials_total") - t0
        assert 1 <= trials_used <= 3
        # persisted, device-keyed
        rec = TuneStore(tune_env).get(
            f"t.o|sig|{tune.device_kind()}"
        )
        assert rec["config"] == {"n": 2}
        assert rec["meta"]["trials"] == trials_used
        # second lookup: memo hit, zero new trials
        out2 = tune.lookup(
            "t.o", "sig", {"n": 1}, grid=[{"n": 2}, {"n": 3}],
            trial=trial,
        )
        assert out2 == {"n": 2}
        assert _totals("tune.trials_total") - t0 == trials_used
        assert _totals("tune.cache_hits_total") > h0

    def test_learned_ranker_prunes_to_half_grid(self, tune_env):
        """The acceptance criterion: with the predictor, trials per
        signature ≤ half the full grid — and the predicted-vs-measured
        error histogram is populated."""
        set_config(
            autotune=True, tune_mode="online", tune_trials=1,
            tune_top_k=8, tune_budget_s=60.0,
        )
        grid = [{"n": n} for n in range(2, 9)]  # +default = 8 full

        def feats(c):
            return 0.0, 0.0, float(c["n"])

        t0 = _totals("tune.trials_total")
        e0 = _err_hist_count()
        out = tune.lookup(
            "t.rank", "sig", {"n": 1}, grid=grid, feats=feats,
            trial=lambda c: time.sleep(0.001 * c["n"]),
        )
        trials_used = _totals("tune.trials_total") - t0
        assert trials_used <= (len(grid) + 1) // 2
        assert trials_used >= 1
        assert out["n"] in (1, 2, 3, 4)  # a top-ranked candidate won
        assert _err_hist_count() > e0  # model honesty is a series

    def test_budget_exhaustion_degrades_to_default(self, tune_env):
        set_config(
            autotune=True, tune_mode="online", tune_trials=1,
            tune_budget_s=0.0,
        )
        measured = []
        out = tune.lookup(
            "t.budget", "sig", {"n": 1},
            grid=[{"n": 2}, {"n": 3}],
            trial=lambda c: measured.append(c["n"]),
        )
        # only the default fit the (zero) budget; it still wins and is
        # persisted so the next process skips straight to cached
        assert out == {"n": 1}
        assert set(measured) == {1}
        rec = TuneStore(tune_env).get(
            f"t.budget|sig|{tune.device_kind()}"
        )
        assert rec["config"] == {"n": 1}

    def test_failing_candidate_is_skipped(self, tune_env):
        set_config(
            autotune=True, tune_mode="online", tune_trials=1,
            tune_budget_s=30.0, max_retries=0,
        )

        def trial(cand):
            if cand["n"] == 2:
                raise RuntimeError("candidate crashes")
            time.sleep(0.001)

        out = tune.lookup(
            "t.fail", "sig", {"n": 1}, grid=[{"n": 2}, {"n": 3}],
            trial=trial,
        )
        assert out["n"] in (1, 3)

    def test_failed_default_trial_never_installs_blind_winner(
        self, tune_env
    ):
        """If the DEFAULT's own trial fails, a candidate that was never
        compared against it must not win — 'degrades to keep the
        default, never a blind winner'."""
        set_config(
            autotune=True, tune_mode="online", tune_trials=1,
            tune_budget_s=30.0, max_retries=0,
        )

        def trial(cand):
            if cand["n"] == 1:  # the default
                raise RuntimeError("default trial dies")

        out = tune.lookup(
            "t.blind", "sig", {"n": 1}, grid=[{"n": 2}], trial=trial
        )
        assert out == {"n": 1}
        assert TuneStore(tune_env).get(
            f"t.blind|sig|{tune.device_kind()}"
        ) is None

    def test_all_candidates_failing_keeps_default_stores_nothing(
        self, tune_env
    ):
        set_config(
            autotune=True, tune_mode="online", tune_trials=1,
            tune_budget_s=30.0, max_retries=0,
        )

        def trial(cand):
            raise RuntimeError("device on fire")

        out = tune.lookup("t.dead", "sig", {"n": 1}, trial=trial)
        assert out == {"n": 1}
        assert TuneStore(tune_env).get(
            f"t.dead|sig|{tune.device_kind()}"
        ) is None

    def test_trials_retry_under_chaos_transients(self, tune_env):
        """The ``tune.trial`` site is a real dispatch site: transient
        chaos faults inside a trial retry inside the trial's own
        ``run_with_retries`` window and tuning still converges."""
        from tensorframes_tpu.utils import chaos

        set_config(
            autotune=True, tune_mode="online", tune_trials=2,
            tune_budget_s=30.0, max_retries=4, retry_backoff_s=0.001,
            chaos="seed=3;tune.trial=transient:p=0.4",
        )
        try:
            inj0 = _totals("chaos.injections_total")
            out = tune.lookup(
                "t.chaos", "sig", {"n": 1}, grid=[{"n": 2}],
                trial=_sleep_trial({1: 6, 2: 1}),
            )
            assert out == {"n": 2}
            assert _totals("chaos.injections_total") > inj0
        finally:
            set_config(chaos="")
        assert TuneStore(tune_env).get(
            f"t.chaos|sig|{tune.device_kind()}"
        )["config"] == {"n": 2}

    def test_lookup_inside_trial_is_read_only(self, tune_env):
        """A lookup made while a trial runs must never START a nested
        search — but it must still SEE installed winners, so trials
        measure the configuration steady state will run with."""
        set_config(
            autotune=True, tune_mode="online", tune_trials=1,
            tune_budget_s=30.0,
        )
        tune.pin("t.installed", "sig", {"n": 42})
        inner, installed = [], []

        def trial(cand):
            inner.append(
                tune.lookup("t.inner", "sig", {"n": 99},
                            grid=[{"n": 100}], trial=lambda c: None)
            )
            installed.append(
                tune.lookup("t.installed", "sig", {"n": 1})
            )

        tune.lookup("t.outer", "sig", {"n": 1}, grid=[{"n": 2}],
                    trial=trial)
        assert inner and all(v == {"n": 99} for v in inner)
        assert installed and all(v == {"n": 42} for v in installed)
        # and the inner surface was never tuned/persisted
        assert TuneStore(tune_env).get(
            f"t.inner|sig|{tune.device_kind()}"
        ) is None

    def test_empty_grid_skips_measurement_and_store(self, tune_env):
        set_config(autotune=True, tune_mode="online", tune_trials=3)
        calls = []
        out = tune.lookup(
            "t.lone", "sig", {"n": 1}, grid=[{"n": 1}],
            trial=lambda c: calls.append(c),
        )
        assert out == {"n": 1}
        assert calls == []  # nothing to choose between: no trials
        assert TuneStore(tune_env).get(
            f"t.lone|sig|{tune.device_kind()}"
        ) is None

    def test_pin_clear_snapshot_cookbook(self, tune_env):
        set_config(autotune=True, tune_mode="cached")
        tune.pin("t.pin", "sig", {"n": 5})
        out = tune.lookup("t.pin", "sig", {"n": 1})
        assert out == {"n": 5}
        snap = tune.snapshot()
        mine = [s for s in snap if s["surface"] == "t.pin"]
        assert mine and mine[0]["source"] == "pinned"
        assert "t.pin[sig]" in tune.render_table()
        assert tune.clear("t.pin") == 1
        assert tune.lookup("t.pin", "sig", {"n": 1}) == {"n": 1}


# ---------------------------------------------------------------------------
# byte-identity: every tuned surface vs TFT_TUNE=0
# ---------------------------------------------------------------------------


def _map_fn(x):
    return {"y": x * 2.0 + 1.0}


def _run_map(rows=100, width=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, width)).astype(np.float32)
    df = tft.TensorFrame.from_columns({"x": x}).analyze()
    return tft.map_rows(_map_fn, df).cache().column_data("y").host()


class TestByteIdentity:
    def test_transfer_chunking(self, tune_env, monkeypatch):
        from tensorframes_tpu.frame import transfer

        rng = np.random.default_rng(1)
        arrs = [
            rng.normal(size=(999, 7)).astype(np.float32),
            rng.integers(0, 1000, size=(257, 3)).astype(np.int32),
        ]
        monkeypatch.setenv("TFT_TUNE", "0")
        baseline = [transfer.d2h(transfer.h2d(a)) for a in arrs]
        monkeypatch.delenv("TFT_TUNE")
        set_config(autotune=True, tune_mode="cached")
        tune.pin(
            "transfer.link", "link", {"chunk_bytes": 4096, "streams": 2}
        )
        cb, st = transfer._link_knobs()
        assert (cb, st) == (4096, 2)  # the tuned knobs actually apply
        for i, (a, base) in enumerate(zip(arrs, baseline)):
            up = transfer.StreamingUpload(a)
            if i == 0:  # the f32 column exceeds the tuned 4 KiB chunk
                assert up.num_chunks > 1  # genuinely chunked differently
            got = transfer.d2h(up.assembled())
            np.testing.assert_array_equal(got, base)

    def test_flash_tiles(self, tune_env, monkeypatch):
        from tensorframes_tpu.ops.attention import flash_attention

        rng = np.random.default_rng(2)
        L, D = 256, 64
        q, k, v = (
            rng.normal(size=(1, 1, L, D)).astype(np.float32)
            for _ in range(3)
        )
        monkeypatch.setenv("TFT_TUNE", "0")
        base = np.asarray(flash_attention(q, k, v, causal=True))
        monkeypatch.delenv("TFT_TUNE")
        set_config(autotune=True, tune_mode="cached")
        # a winner differing in block_q ONLY — the shipped grids vary
        # nothing else, exactly because that preserves bit-identity
        tune.pin(
            "flash.tiles", f"lowp=0|d=64|L={L}",
            {"block_q": 128, "block_k": 1024},
        )
        from tensorframes_tpu.ops import attention as attn_mod

        assert attn_mod._best_blocks(np.float32, D, L) == (128, 1024)
        tuned = np.asarray(flash_attention(q, k, v, causal=True))
        np.testing.assert_array_equal(tuned, base)

    def test_map_rows_block_rows(self, tune_env, monkeypatch):
        monkeypatch.setenv("TFT_TUNE", "0")
        base = _run_map()
        monkeypatch.delenv("TFT_TUNE")
        set_config(autotune=True, tune_mode="cached")
        # width 4 f32 -> 16 bytes/row, 100 rows -> n bucket 128: the
        # signature the consumer computes; an odd 7-row budget
        # exercises ragged tails
        tune.pin(
            "map_rows.block_rows", "row_bytes=16|cols=1|n=128",
            {"rows": 7},
        )
        tuned = _run_map()
        np.testing.assert_array_equal(tuned, base)

    def test_map_rows_online_tuning_under_chaos(self, tune_env,
                                                monkeypatch):
        """Online trials — real row programs, chaos-injected at
        ``tune.trial`` — must leave results byte-identical to the kill
        switch."""
        monkeypatch.setenv("TFT_TUNE", "0")
        base = _run_map(rows=128)
        monkeypatch.delenv("TFT_TUNE")
        set_config(
            autotune=True, tune_mode="online", tune_trials=1,
            tune_budget_s=30.0, max_rows_per_device_call=32,
            max_retries=4, retry_backoff_s=0.001,
            chaos="seed=5;tune.trial=transient:p=0.3",
        )
        try:
            t0 = _totals("tune.trials_total")
            tuned = _run_map(rows=128)
            assert _totals("tune.trials_total") > t0  # it DID tune
        finally:
            set_config(chaos="")
        np.testing.assert_array_equal(tuned, base)
        # and the winner is a real persisted record
        assert any(
            r["surface"] == "map_rows.block_rows"
            for r in TuneStore(tune_env).entries().values()
        )

    def test_serve_page_size_and_prefill_chunk(self, tune_env, lm,
                                               monkeypatch):
        from tensorframes_tpu.serve import GenerationEngine

        prompt = list(np.random.default_rng(3).integers(1, VOCAB, size=12))
        monkeypatch.setenv("TFT_TUNE", "0")
        eng = GenerationEngine(lm, max_slots=2, max_seq_len=48)
        assert eng.page_size == 48  # hint clamped to max_seq_len
        base_greedy = eng.generate([prompt], 8)[0]
        base_sampled = eng.generate(
            [prompt], 8, temperature=0.8, seed=7
        )[0]
        monkeypatch.delenv("TFT_TUNE")
        set_config(autotune=True, tune_mode="cached")
        sig = tune.serve_signature(np.float32, 4, 48)
        tune.pin("serve.page_size", sig, {"page_size": 8})
        tune.pin("serve.prefill_chunk", sig, {"tokens": 8})
        eng2 = GenerationEngine(lm, max_slots=2, max_seq_len=48)
        assert eng2.page_size == 8
        assert eng2.prefill_chunk_tokens == 8
        np.testing.assert_array_equal(
            eng2.generate([prompt], 8)[0], base_greedy
        )
        np.testing.assert_array_equal(
            eng2.generate([prompt], 8, temperature=0.8, seed=7)[0],
            base_sampled,
        )

    def test_serve_page_slots_geometry(self, tune_env, lm, monkeypatch):
        """The ISSUE 14 pool-geometry surface: a stored winner steers
        the DEFAULT max_slots and num_pages (clamped to feasibility),
        explicit arguments always win, and the streams stay
        byte-identical — geometry moves scheduling, never bytes."""
        from tensorframes_tpu.serve import GenerationEngine

        prompt = list(np.random.default_rng(5).integers(1, VOCAB, size=10))
        monkeypatch.setenv("TFT_TUNE", "0")
        base_eng = GenerationEngine(lm, max_seq_len=48, page_size=8)
        assert base_eng.max_slots == 8  # the untuned default
        base = base_eng.generate([prompt], 8)[0]
        monkeypatch.delenv("TFT_TUNE")
        set_config(autotune=True, tune_mode="cached")
        sig = tune.serve_signature(np.float32, 4, 48)
        tune.pin(
            "serve.page_slots", sig, {"slots": 3, "pages_per_slot": 2}
        )
        eng = GenerationEngine(lm, max_seq_len=48, page_size=8)
        assert eng.max_slots == 3
        # pool = max(one full-length request, slots × pages_per_slot)
        assert eng.pool.num_pages == max(eng._max_pages, 3 * 2)
        np.testing.assert_array_equal(eng.generate([prompt], 8)[0], base)
        # explicit arguments beat the winner
        eng2 = GenerationEngine(
            lm, max_seq_len=48, page_size=8, max_slots=5, num_pages=40
        )
        assert eng2.max_slots == 5 and eng2.pool.num_pages == 40
        np.testing.assert_array_equal(eng2.generate([prompt], 8)[0], base)

    def test_jobs_lease_ttl_surface(self, tune_env, tmp_path,
                                    monkeypatch):
        """The ISSUE 14 lease-TTL surface: cache/pin-only resolution on
        the drain path, explicit ttl untouched, and a real one-worker
        drain under the tuned TTL produces byte-identical block results
        (TTL moves reclamation timing, never results)."""
        from tensorframes_tpu.engine.dist_jobs import (
            _tuned_lease_ttl,
            run_worker,
            wait_job,
        )

        rng = np.random.default_rng(0)
        x = rng.normal(size=(48, 4)).astype(np.float32)
        df = tft.TensorFrame.from_columns({"x": x}).analyze().repartition(2)

        def fn(x):
            return {"y": x * 3.0 + 1.0}

        monkeypatch.setenv("TFT_TUNE", "0")
        assert _tuned_lease_ttl(6.0) == 6.0
        ref = np.asarray(tft.map_rows(fn, df).column_data("y").host())
        monkeypatch.delenv("TFT_TUNE")
        set_config(autotune=True, tune_mode="cached")
        tune.pin("jobs.lease_ttl", tune.jobs_signature(), {"ttl_s": 2.0})
        assert _tuned_lease_ttl(6.0) == 2.0
        # garbage in the store degrades to the default, never a crash
        tune.pin("jobs.lease_ttl", tune.jobs_signature(), {"ttl_s": -1})
        assert _tuned_lease_ttl(6.0) == 6.0
        tune.pin("jobs.lease_ttl", tune.jobs_signature(), {"ttl_s": 2.0})
        path = str(tmp_path / "drain")
        report = run_worker(
            "map_rows", fn, df, path=path, worker_id="w0", poll_s=0.05
        )
        assert report.complete
        out = wait_job(path, fn, df)
        np.testing.assert_array_equal(
            np.asarray(out.completed.column_data("y").host()), ref
        )

    def test_rank_tp_layouts_ranks_and_persists(self, tune_env, lm):
        """The ISSUE 14 sharding-ranker surface: cost-model ranking over
        TP degrees (programs.jsonl-fitted when records exist, analytic
        prior otherwise), non-dividing degrees rank last with an
        infinite prediction, winner persisted under serve.tp_layout."""
        set_config(autotune=True, tune_mode="cached")
        ranked = tune.rank_tp_layouts(
            lm, max_seq_len=48, degrees=(1, 2, 4, 3)
        )
        assert [r["tp"] for r in ranked[:3]] != []
        finite = [r for r in ranked if np.isfinite(r["predicted_step_s"])]
        assert {r["tp"] for r in finite} == {1, 2, 4}
        # n_heads=4 does not divide by 3 — ranked last, prediction inf
        assert ranked[-1]["tp"] == 3
        assert not np.isfinite(ranked[-1]["predicted_step_s"])
        # predictions are monotone with the ranking order
        preds = [r["predicted_step_s"] for r in ranked]
        assert preds == sorted(preds)
        stored = {
            r["surface"]: r["config"] for r in tune.snapshot()
        }
        assert stored.get("serve.tp_layout", {}).get("tp") == finite[0]["tp"]
        # higher degrees shrink the per-chip attention-read bytes the
        # model sees (the 1/N KV sharding is IN the features)
        by_tp = {r["tp"]: r for r in finite}
        assert by_tp[4]["bytes"] < by_tp[2]["bytes"] < by_tp[1]["bytes"]


# ---------------------------------------------------------------------------
# persistence round-trip + mid-trial kill (real subprocesses)
# ---------------------------------------------------------------------------

_TUNER_SCRIPT = r"""
import sys
import numpy as np
import tensorframes_tpu as tft
from tensorframes_tpu.obs import metrics as obs_metrics
from tensorframes_tpu.utils import set_config

set_config(autotune=True, tune_mode="online", tune_budget_s=30.0,
           tune_trials=1, max_rows_per_device_call=32)
rng = np.random.default_rng(0)
x = rng.normal(size=(128, 4)).astype(np.float32)
df = tft.TensorFrame.from_columns({"x": x}).analyze()
out = tft.map_rows(
    lambda x: {"y": x * 2.0 + 1.0}, df
).cache().column_data("y").host()
snap = obs_metrics.snapshot().get("tune.trials_total", {})
trials = sum((snap.get("values") or {}).values())
np.save(sys.argv[1], out)
print("A_TRIALS", trials, flush=True)
print("A_DONE", flush=True)
"""

_KILL_TUNER_SCRIPT = r"""
import numpy as np
import tensorframes_tpu as tft
from tensorframes_tpu.utils import set_config

# latency chaos on every trial + many repeats = a tuning pass long
# enough for the parent to SIGKILL us mid-trial, deterministically
set_config(autotune=True, tune_mode="online", tune_budget_s=600.0,
           tune_trials=50, max_rows_per_device_call=32,
           chaos="tune.trial=latency:ms=100")
rng = np.random.default_rng(0)
x = rng.normal(size=(128, 4)).astype(np.float32)
df = tft.TensorFrame.from_columns({"x": x}).analyze()
print("TUNING", flush=True)
tft.map_rows(lambda x: {"y": x * 2.0 + 1.0}, df).cache()
print("NEVER_REACHED", flush=True)
"""


class TestPersistenceRoundTrip:
    def test_winner_tuned_in_process_a_serves_b_with_zero_trials(
        self, tune_env, monkeypatch
    ):
        """The acceptance criterion end-to-end: process A (a REAL
        subprocess) tunes online and persists; this process (B) resolves
        the same signature from the store with ZERO trials — asserted
        via ``tune.trials_total`` / ``tune.cache_hits_total`` — and
        produces byte-identical results."""
        out_npy = tune_env + ".a.npy"
        p = subprocess.run(
            [sys.executable, "-c", _TUNER_SCRIPT, out_npy],
            env=_env(TFT_TUNE_FILE=tune_env), capture_output=True,
            text=True, timeout=300,
        )
        assert p.returncode == 0, p.stderr
        assert "A_DONE" in p.stdout
        a_trials = float(p.stdout.split("A_TRIALS")[1].split()[0])
        assert a_trials > 0, "process A never actually tuned"
        winners = {
            r["surface"]: r
            for r in TuneStore(tune_env).entries().values()
        }
        assert "map_rows.block_rows" in winners

        # process B: same signature, online mode — but the store wins
        set_config(
            autotune=True, tune_mode="online", tune_budget_s=30.0,
            tune_trials=1, max_rows_per_device_call=32,
        )
        t0 = _totals("tune.trials_total")
        h0 = _totals("tune.cache_hits_total")
        b_out = _run_map(rows=128)
        assert _totals("tune.trials_total") == t0, (
            "process B ran trials for a signature the store already has"
        )
        assert _totals("tune.cache_hits_total") > h0
        np.testing.assert_array_equal(b_out, np.load(out_npy))

    def test_mid_trial_kill9_store_clean_and_identity_holds(
        self, tune_env, monkeypatch
    ):
        """kill -9 in the middle of a tuning pass: the store re-reads
        cleanly (possibly empty, never torn) and results afterwards —
        cached mode vs kill switch — stay byte-identical."""
        p = subprocess.Popen(
            [sys.executable, "-c", _KILL_TUNER_SCRIPT],
            env=_env(TFT_TUNE_FILE=tune_env), stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
        )
        try:
            line = ""
            deadline = time.monotonic() + 240
            while "TUNING" not in line:
                assert time.monotonic() < deadline
                line = p.stdout.readline()
                assert line, p.stderr.read()
            time.sleep(0.25)  # mid-trial (each trial sleeps 100ms)
            p.send_signal(signal.SIGKILL)
            assert p.wait(timeout=30) == -signal.SIGKILL
        finally:
            if p.poll() is None:
                p.kill()
        for rec in TuneStore(tune_env).entries().values():
            assert rec["v"] == SCHEMA_VERSION  # clean re-read
        monkeypatch.setenv("TFT_TUNE", "0")
        base = _run_map(rows=128)
        monkeypatch.delenv("TFT_TUNE")
        set_config(
            autotune=True, tune_mode="cached",
            max_rows_per_device_call=32,
        )
        tune.reset()
        np.testing.assert_array_equal(_run_map(rows=128), base)


# ---------------------------------------------------------------------------
# serving satellites + the measured serve-knob search
# ---------------------------------------------------------------------------


class TestServeSatellites:
    def test_page_size_hint_is_the_default_and_healthz_reports(
        self, tune_env, lm
    ):
        from tensorframes_tpu.ops.attention import paged_page_size_hint
        from tensorframes_tpu.serve import GenerationEngine

        hint = paged_page_size_hint(np.float32, 4)
        eng = GenerationEngine(lm, max_slots=2, max_seq_len=48)
        assert eng.page_size == min(hint, 48)
        h = eng.health()
        assert h["page_size"] == eng.page_size
        assert h["prefill_chunk_tokens"] == 0
        # the explicit argument still wins
        eng16 = GenerationEngine(
            lm, max_slots=2, max_seq_len=48, page_size=16
        )
        assert eng16.page_size == 16
        assert eng16.health()["page_size"] == 16

    def test_tune_serve_knobs_persists_and_engines_inherit(
        self, tune_env, lm
    ):
        set_config(
            autotune=True, tune_mode="online", tune_trials=1,
            tune_budget_s=120.0,
        )
        winners = tune.tune_serve_knobs(
            lm, max_seq_len=48, prompt_len=12, max_new_tokens=4,
            max_slots=2, page_sizes=[8], prefill_chunks=[0, 8],
            repeats=1,
        )
        assert set(winners) == {
            "serve.page_size", "serve.prefill_chunk", "serve.page_slots",
        }
        stored = {
            r["surface"] for r in TuneStore(tune_env).entries().values()
        }
        assert {"serve.page_size", "serve.prefill_chunk"} <= stored
        # a later engine resolves the persisted winner (fresh memo =
        # fresh process)
        tune.reset()
        set_config(tune_mode="cached")
        from tensorframes_tpu.serve import GenerationEngine

        eng = GenerationEngine(lm, max_slots=2, max_seq_len=48)
        assert eng.page_size == winners["serve.page_size"]["page_size"]

    def test_tune_serve_knobs_reuses_engines_across_shared_grids(
        self, tune_env, lm, monkeypatch
    ):
        """ISSUE 15 satellite fix: the measured serve search memoizes
        throwaway engines per distinct engine-level config — candidates
        sharing a config (and repeat trials of one candidate) must not
        rebuild, or construction wall eats ``tune_budget_s`` on the
        larger spec-enabled grid."""
        from tensorframes_tpu import serve as serve_pkg

        set_config(
            autotune=True, tune_mode="online", tune_trials=2,
            tune_budget_s=120.0,
        )
        real = serve_pkg.GenerationEngine
        builds = []

        class Counting(real):
            def __init__(self, *a, **kw):
                builds.append(1)
                super().__init__(*a, **kw)

        monkeypatch.setattr(serve_pkg, "GenerationEngine", Counting)
        winners = tune.tune_serve_knobs(
            lm, max_seq_len=48, prompt_len=12, max_new_tokens=4,
            max_slots=2, page_sizes=[8, 16], prefill_chunks=[0, 8],
            draft_params=lm.params, draft_lens=(2, 3),
            repeats=2,
        )
        assert "serve.draft_len" in winners
        assert winners["serve.draft_len"]["k"] in (2, 3, 4)
        # distinct engine configs per grid (the memo is scoped to one
        # surface so only one grid's device pools stay resident): <= 3
        # page sizes (hint default + 2 candidates) + <= 2 chunk configs
        # + <= 3 geometries + <= 3 draft lengths = <= 11 builds. Every
        # measured candidate runs warmup + 2 repeats (~3x that in
        # run_engine calls), so an un-memoized search would build ~30
        # engines — the bound is what separates reuse from
        # rebuild-per-trial.
        trials = _totals("tune.trials_total")
        assert trials >= 8
        assert len(builds) <= 11, (
            f"{len(builds)} engine builds for {trials} measured trials "
            f"— the per-config memo is not reusing engines"
        )
        stored = {
            r["surface"] for r in TuneStore(tune_env).entries().values()
        }
        assert "serve.draft_len" in stored

    def test_draft_len_candidates_stream_byte_identical(
        self, tune_env, lm
    ):
        """The serve-suite invariant extended to the new surface: every
        draft-length candidate (and k=0, speculation off) emits the
        same bytes — draft length changes scheduling, never streams."""
        from tensorframes_tpu.serve import GenerationEngine

        prompts = [[1, 5, 9, 2, 7], [3, 3, 8]]
        outs = []
        for k in (0, 2, 4):
            kw = (
                {}
                if k == 0
                else dict(draft_params=lm.params, draft_len=k)
            )
            eng = GenerationEngine(
                lm, max_slots=2, page_size=8, max_seq_len=48, **kw
            )
            outs.append(
                eng.generate(prompts, 8, temperature=0.7, seed=13)
            )
        for other in outs[1:]:
            for a, b in zip(outs[0], other):
                np.testing.assert_array_equal(a, b)


class TestPerChipRecords:
    """ISSUE 15 satellite: multi-device ``programs.jsonl`` records
    (per-replica TP-named programs, ``meta.tp_degree``) feed the
    layout ranker's cost-model fit, normalized to per-chip features."""

    @staticmethod
    def _mixed_records(w_f=2e-11, w_b=1e-10, w_0=5e-5, n_per=8):
        """Synthetic mixed-degree history obeying a PER-CHIP linear
        law: a degree-N record carries GLOBAL features (N x the
        per-chip work) while its wall is the per-chip wall."""
        rng = np.random.default_rng(0)
        recs = []
        for tp in (1, 2, 4):
            for _ in range(n_per):
                f_chip = float(rng.uniform(1e8, 5e9))
                b_chip = float(rng.uniform(1e6, 5e8))
                wall = w_f * f_chip + w_b * b_chip + w_0
                recs.append(
                    {
                        "flops": f_chip * tp,
                        "bytes": b_chip * tp,
                        "dispatches": 10,
                        "dispatch_s": wall * 10,
                        "meta": {"tp_degree": tp},
                    }
                )
        return recs

    def test_normalization_and_passthrough(self):
        recs = [
            {"flops": 8.0, "bytes": 4.0, "meta": {"tp_degree": 4}},
            {"flops": 8.0, "bytes": 4.0, "meta": {}},
            {"flops": None, "bytes": 4.0, "meta": {"tp_degree": 2}},
        ]
        out = tune.per_chip_records(recs)
        assert out[0]["flops"] == 2.0 and out[0]["bytes"] == 1.0
        assert out[1]["flops"] == 8.0  # single-device: unchanged
        assert out[2]["flops"] is None and out[2]["bytes"] == 2.0
        # the input rows are never mutated
        assert recs[0]["flops"] == 8.0

    def test_mixed_degree_fit_recovers_the_per_chip_law(self):
        recs = self._mixed_records()
        fit_norm = CostModel.fit(tune.per_chip_records(recs))
        fit_raw = CostModel.fit(recs)
        # probe on per-chip features (what rank_tp_layouts predicts
        # with): the normalized fit tracks the generating law; the raw
        # fit is skewed by the global-feature rows
        probe_f, probe_b = 2e9, 2e8
        truth = 2e-11 * probe_f + 1e-10 * probe_b + 5e-5
        err_norm = abs(fit_norm.predict(probe_f, probe_b, 1) - truth)
        err_raw = abs(fit_raw.predict(probe_f, probe_b, 1) - truth)
        assert err_norm < truth * 0.05
        assert err_norm < err_raw

    def test_rank_tp_layouts_fits_over_multi_device_records(
        self, tune_env, lm, tmp_path, monkeypatch
    ):
        """End-to-end: a programs.jsonl holding ONLY multi-device rows
        still yields a usable ranking (finite predictions, monotone
        order, winner pinned) — the fit no longer depends on
        single-device-only records."""
        import json as _json

        costs = tmp_path / "programs.jsonl"
        with open(costs, "w") as f:
            for rec in self._mixed_records():
                if rec["meta"]["tp_degree"] == 1:
                    continue
                f.write(_json.dumps(rec) + "\n")
        monkeypatch.setenv("TFT_PROGRAM_COSTS_FILE", str(costs))
        set_config(autotune=True, tune_mode="cached")
        model = tune.default_model(per_chip=True)
        assert model.source.startswith("ridge")
        ranked = tune.rank_tp_layouts(
            lm, max_seq_len=48, degrees=(1, 2, 4)
        )
        preds = [r["predicted_step_s"] for r in ranked]
        assert all(np.isfinite(p) for p in preds)
        assert preds == sorted(preds)
        stored = {
            r["surface"]: r["config"] for r in tune.snapshot()
        }
        assert stored.get("serve.tp_layout", {}).get("tp") == (
            ranked[0]["tp"]
        )


# ---------------------------------------------------------------------------
# export + gate satellites
# ---------------------------------------------------------------------------


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
    return head.split(b"\r\n")[0].decode(), body


class TestExportSurfaces:
    def test_explain_analyze_appends_tuned_table(self, tune_env):
        set_config(autotune=True, tune_mode="cached")
        tune.pin("t.explain", "sig", {"n": 3})
        x = np.arange(8, dtype=np.float32).reshape(4, 2)
        df = tft.TensorFrame.from_columns({"x": x}).analyze()
        txt = tft.explain(df, analyze=True)
        assert "== Tuned configs ==" in txt
        assert "t.explain[sig]" in txt

    def test_statusz_and_varz_export(self, tune_env, lm):
        """/statusz carries the tuned-winner view; the
        predicted-vs-measured error histogram is sampled onto /varz."""
        from tensorframes_tpu.interop.serving import ScoringServer
        from tensorframes_tpu.obs import timeseries
        from tensorframes_tpu.serve import GenerationEngine

        set_config(
            autotune=True, tune_mode="online", tune_trials=1,
            tune_budget_s=30.0,
        )
        timeseries.sample_once()  # baseline tick
        tune.lookup(
            "t.varz", "sig", {"n": 1},
            grid=[{"n": 2}, {"n": 3}, {"n": 4}],
            feats=lambda c: (0.0, 0.0, float(c["n"])),
            trial=lambda c: time.sleep(0.001),
        )
        timeseries.sample_once()
        names = timeseries.store().names()
        assert any(
            n.startswith("tune.predicted_error_ratio.") for n in names
        ), names
        srv = ScoringServer(
            engine=GenerationEngine(
                lm, max_slots=2, page_size=4, max_seq_len=32
            )
        )
        try:
            host, port = srv.start()
            status, body = _http(host, port, "/statusz")
            assert status.endswith("200 OK")
            tz = json.loads(body)["tune"]
            assert tz["mode"] == "online"
            assert any(
                w["surface"] == "t.varz" for w in tz["winners"]
            )
            status, body = _http(
                host, port, "/varz?prefix=tune.predicted_error_ratio"
            )
            assert status.endswith("200 OK")
            series = json.loads(body)["series"]
            assert any(
                k.startswith("tune.predicted_error_ratio.")
                and v.get("points")
                for k, v in series.items()
            ), series
        finally:
            srv.stop()
