"""The benchmark's own tests: the harness at toy sizes on the CPU.

They drive ``chipbench.run.main`` with ``--rehearsal`` (the only way the
command runs off the chip), the traffic generator, the functions that
count needed FLOPs and bytes, the trace reduction on a trace recorded on
the chip, the table of peaks, and the agreement of ``BENCHMARK.json`` with
the files it names. Two kinds of test keep ``correct`` honest: the
controls (the reference one precision down must read over the limit) and
the faults (a run whose timed path is broken underneath must come out not
correct). Nothing here describes a TPU topology, sleeps or asserts on a
wall-clock threshold; the program is reached only through
``tensorframes_tpu``'s public entry points and the server's wire format.
"""

import glob
import json
import os
import re
import socket
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import peaks, run as bench, trace_reduce, traffic  # noqa: E402
from chipbench.models import gpt2, softmax_regression  # noqa: E402

BENCH = os.path.join(ROOT, "chipbench")
MANIFEST = bench.load_json(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def rehearse(capsys, cell, *extra):
    """One rehearsal run in this process; its last line, parsed."""
    capsys.readouterr()
    rc = bench.main(
        ["--workload", cell, "--seed", "3000000019", "--seconds", "2",
         "--rehearsal", *extra]
    )
    out, err = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), err


def config_of(cell, rehearsal=True):
    spec = bench.load_json(BENCH, "workloads", cell + ".json")
    cfg = bench.load_json(BENCH, "configs", spec["config"] + ".json")
    if rehearsal:
        bench.merge(cfg, cfg.get("rehearsal", {}))
    return cfg


# ------------------------------------------------------------ the command


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_ends_in_one_result_line(capsys, cell):
    line, err = rehearse(capsys, cell, "--trace", "0")
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["device"]["rehearsal"]
    wanted = {
        m["name"] for m in MANIFEST["end_to_end"]
        if cell in bench.metric_cells(m, MANIFEST)
    }
    assert set(line["metrics"]) == wanted and "setup_s" in wanted
    for name, m in line["metrics"].items():
        assert m["value"] > 0 and isinstance(m["unit"], str), name
    for name, c in line["checks"].items():
        assert c["value"] <= c["limit"], name
        assert f"check {name}:" in err  # each number beside its limit
    assert line["checks"]["window_compiles"]["value"] == 0


def test_traced_rehearsal_reports_layer_metrics(capsys):
    line, _ = rehearse(capsys, "mnist-lr.resident-score", "--trace", "1")
    assert line["correct"] is True
    # no device plane on the CPU: the device readers return nothing, and
    # no share of a roofline or of a peak is ever printed as 0
    assert set(line["metrics"]) == {"frame.host_ms_per_pass"}
    assert line["metrics"]["frame.host_ms_per_pass"]["value"] > 0


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "--rehearsal" in proc.stderr


def test_the_load_generator_never_imports_jax():
    code = (
        "import sys, chipbench.loadgen, chipbench.traffic;"
        "assert 'jax' not in sys.modules and 'tensorframes_tpu' not in sys.modules"
    )
    subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True, timeout=60)


# ----------------------------------------------------------------- faults


def test_an_altered_answer_makes_the_frame_cell_incorrect(capsys, monkeypatch):
    import tensorframes_tpu as tft

    real = tft.map_blocks

    def broken(fn, frame, *args, **kwargs):
        def altered(features):
            out = dict(fn(features))
            pred = out["prediction"]
            out["prediction"] = pred.at[0].set((pred[0] + 1) % 10)
            return out

        return real(altered, frame, *args, **kwargs)

    monkeypatch.setattr(tft, "map_blocks", broken)
    line, err = rehearse(capsys, "mnist-lr.resident-score", "--trace", "0")
    assert line["correct"] is False
    gap = line["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
    assert "FAILED" in err


def test_an_altered_token_makes_a_serving_cell_incorrect(capsys, monkeypatch):
    real = socket.socket.sendall
    seen = [0]

    def broken(self, data, *args):
        # the server's wire: one ``{"t": <token>}`` line per emission
        if data.startswith(b'{"t": '):
            seen[0] += 1
            if seen[0] % 7 == 0:
                tok = json.loads(data)["t"]
                data = (json.dumps({"t": (tok + 1) % 512}) + "\n").encode()
        return real(self, data, *args)

    monkeypatch.setattr(socket.socket, "sendall", broken)
    line, _ = rehearse(capsys, "gpt2-xl.chat-rate", "--trace", "0")
    assert seen[0] >= 7
    assert line["correct"] is False
    gap = line["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


# --------------------------------------------------------------- controls


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_bfloat16_control_fails_the_gpt2_limit(seed):
    import jax.numpy as jnp

    cfg = config_of("gpt2-xl.chat-rate")
    params = gpt2.init_params(seed, cfg)
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg["vocab_size"], (4, cfg["n_positions"])).astype(np.int32)
    rows = np.repeat(np.arange(4), 100).astype(np.int32)
    cols = np.tile(np.arange(20, 120), 4).astype(np.int32)
    ref = gpt2.reference_logits(params, cfg, tokens, rows, cols)
    low = gpt2.reference_logits(params, cfg, tokens, rows, cols, "bfloat16")

    def gaps(picks):
        got = jnp.take_along_axis(ref, picks[:, None], axis=-1)[:, 0]
        below = jnp.max(ref, axis=-1) - got
        return float(jnp.max(below)), float(jnp.mean(below))

    limits = cfg["limits"]
    assert gaps(jnp.argmax(ref, axis=-1)) == (0.0, 0.0)
    widest, mean = gaps(jnp.argmax(low, axis=-1))
    assert widest > 3 * limits["logit_gap"]
    assert mean > 2 * limits["logit_gap_mean"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_three_pass_control_fails_the_scoring_limit(seed):
    import jax

    cfg = config_of("mnist-lr.resident-score")
    x = softmax_regression.make_features(seed, 65536, cfg)
    w, b = softmax_regression.init_weights(seed, cfg)
    out = jax.jit(softmax_regression.score_fn(w, b, "highest"))(x)
    gap, flips, err = softmax_regression.reference_gap(
        x, w, b, out["prediction"], out["score"]
    )
    limits = cfg["limits"]
    assert gap <= limits["logit_gap"] and err <= limits["score_error"]
    low = softmax_regression.control_predictions(x, w, b)
    _, _, low_err = softmax_regression.reference_gap(x, w, b, *low)
    assert low_err > limits["score_error"] and low_err > 3 * err


# ---------------------------------------------------------------- traffic


@pytest.mark.parametrize("cell", [c for c in CELLS if c.startswith("gpt2")])
def test_traffic_repeats_for_a_seed_and_permutes_for_another(cell):
    spec = bench.load_json(BENCH, "workloads", cell + ".json")["traffic"]
    a = traffic.generate(spec, 7, 20, 50257)
    b = traffic.generate(spec, 7, 20, 50257)
    c = traffic.generate(spec, 2**31 + 5, 20, 50257)
    assert a == b and a != c
    sizes = lambda p: sorted(
        (len(r["body"]["prompt"]), r["body"]["max_new_tokens"])[i]
        for r in p["requests"] for i in (0, 1)
    )
    assert sizes(a) == sizes(c)  # the same work, in another order
    lens = [len(r["body"]["prompt"]) for r in a["requests"]]
    outs = [r["body"]["max_new_tokens"] for r in a["requests"]]
    assert spec["prompt_tokens"]["min"] <= min(lens) <= max(lens) <= spec["prompt_tokens"]["max"]
    assert max(l + o for l, o in zip(lens, outs)) <= 1024
    if spec["loop"] == "open":
        due_a = [r["due_s"] for r in a["requests"]]
        due_c = [r["due_s"] for r in c["requests"]]
        assert due_a == sorted(due_a) and due_a != due_c
        assert len(due_a) == round(spec["rate_per_s"] * (spec["ramp_s"] + 20))
        assert abs(due_a[-1] - (spec["ramp_s"] + 20)) < 2.0
        # the first request is due half its gap in; the rest follow theirs
        gaps = lambda d: sorted([2 * d[0]] + list(np.diff(d)))
        assert np.allclose(gaps(due_a), gaps(due_c))


# ------------------------------------------------------- needed work, peaks


def test_gpt2_xl_counts_match_hand_worked_values():
    cfg = config_of("gpt2-xl.chat-rate", rehearsal=False)
    # the checkpoint's 1,557,611,200 less the 48 * 14,400 biases the block lacks
    assert gpt2.param_count(cfg) == 1_557_611_200 - 691_200 == 1_556_920_000
    assert gpt2.kv_bytes_per_token(cfg) == 614_400
    assert gpt2.weight_bytes(cfg) == 6_227_680_000
    # one block: 2 * (1600*4800 + 1600*1600 + 2*1600*6400) per token
    dense = 2 * 30_720_000
    assert gpt2.token_flops(cfg, 1) == 48 * (dense + 4 * 1600)
    assert gpt2.sequence_flops(cfg, 0, 1, 1) == (
        gpt2.token_flops(cfg, 1) + 2 * 1600 * 50257
    )
    # a prefill is the sum of its tokens, with one evaluation of the head
    assert gpt2.sequence_flops(cfg, 0, 5, 1) == sum(
        gpt2.token_flops(cfg, c) for c in range(1, 6)
    ) + gpt2.head_flops(cfg)
    assert gpt2.decode_step_bytes(cfg, [10, 20]) == 6_227_680_000 + 30 * 614_400
    assert gpt2.prefill_bytes(cfg, 100) == 6_227_680_000 + 100 * 614_400


def test_mnist_lr_counts_match_hand_worked_values():
    cfg = config_of("mnist-lr.resident-score", rehearsal=False)
    assert softmax_regression.row_flops(cfg) == 15_680
    assert softmax_regression.pass_bytes(cfg, 1_500_000) == 1_500_000 * 3_144
    frame = bench.load_json(BENCH, "workloads", "mnist-lr.resident-score.json")["frame"]
    assert frame["rows"] * 784 * 4 >= 0.25 * 16e9  # the driver's memory floor


def test_an_unknown_device_kind_is_an_error():
    assert peaks.lookup("TPU v5 lite")["flops_per_s"] == 197e12
    assert peaks.lookup("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.lookup("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.lookup("cpu")


# ----------------------------------------------------------- trace reduce


def test_trace_reduce_on_hand_made_intervals():
    trace = {
        "devices": {
            "/device:TPU:0": {
                "ops": [["a", 1.0, 1.0], ["b", 1.5, 1.0], ["a", 4.0, 1.0]],
                "modules": [["jit_f(1)", 1.0, 1.5], ["jit_f(1)", 4.0, 1.0]],
            }
        },
        "host": [["outer", 0.0, 10.0], ["inner", 2.4, 1.8]],
    }
    assert trace_reduce.union([(1, 2), (1.5, 2.5), (4, 5)]) == [[1, 2.5], [4, 5]]
    out = trace_reduce.reduce(trace, (0.0, 10.0))
    assert out["busy_s"] == pytest.approx(2.5)
    assert out["window_s"] == 10.0 and out["idle_share"] == pytest.approx(0.75)
    assert out["programs"]["jit_f"] == {
        "calls": 2, "total_s": pytest.approx(2.5), "mean_s": pytest.approx(1.25),
    }
    assert out["device_ops"][0] == ["a", pytest.approx(2.0)]
    gaps = dict(out["idle_gaps"])
    assert gaps["inner"] == pytest.approx(1.5)  # 2.5..4.0, the innermost span
    assert gaps["outer"] == pytest.approx(6.0)  # 0..1 and 5..10
    assert trace_reduce.reduce({"devices": {}, "host": []}) is None


def test_trace_reduce_on_the_trace_recorded_on_the_chip():
    """``testdata/score_passes.xplane.pb``: seven scoring passes over a
    200,000-row frame on a TPU v5e, recorded by the jax profiler."""
    path = os.path.join(BENCH, "testdata", "score_passes.xplane.pb")
    expect = bench.load_json(BENCH, "testdata", "score_passes.expect.json")
    trace = trace_reduce.load(path, ["chipbench.window"])
    dev = trace["devices"]["/device:TPU:0"]
    assert len(dev["modules"]) == expect["modules"] == len(dev["ops"])
    out = trace_reduce.reduce(trace)
    (name, rec), = out["programs"].items()
    assert name == expect["program"] and rec["calls"] == expect["modules"]
    assert rec["total_s"] == pytest.approx(expect["program_total_s"], rel=1e-6)
    assert out["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-6)
    assert 0.0 < out["busy_s"] <= out["window_s"]
    assert out["idle_share"] == pytest.approx(1 - out["busy_s"] / out["window_s"])
    marks = [e for e in trace["host"] if e[0] == "chipbench.window"]
    assert len(marks) == 1
    lo, hi = marks[0][1], marks[0][1] + marks[0][2]
    inside = trace_reduce.reduce(trace, (lo, hi))
    assert inside["window_s"] == pytest.approx(hi - lo)
    assert inside["busy_s"] <= out["busy_s"] * (1 + 1e-9)


# ------------------------------------------------- the manifest and files


def test_manifest_names_units_and_limits_of_the_contract():
    assert set(MANIFEST) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= MANIFEST["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(names) == len(set(names))
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock"
        )
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    four = sum(1 for w in MANIFEST["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(MANIFEST["workloads"]) // 4)
    for w in MANIFEST["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    assert len(json.dumps(MANIFEST)) < 64 * 1024


def test_manifest_and_files_agree():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for name, c in configs.items():
        assert c["file"] == f"chipbench/configs/{name}.json"
        on_disk = bench.load_json(ROOT, c["file"])
        assert on_disk["name"] == name and on_disk["reduced"] == c["reduced"]
        assert os.path.exists(
            os.path.join(BENCH, "models", on_disk["family"] + ".py")
        )
        assert any(w["config"] == name for w in cells.values())
    for name, w in cells.items():
        spec = bench.load_json(BENCH, "workloads", name + ".json")
        assert spec["name"] == name == f"{w['config']}.{w['traffic']}"
        assert spec["config"] == w["config"] in configs
        assert spec["chips"] == w["chips"] and spec["why"] == w["why"]
        assert os.path.exists(os.path.join(BENCH, "drivers", spec["driver"] + ".py"))
        mine = [m for m in e2e.values() if name in bench.metric_cells(m, MANIFEST)]
        assert len(mine) >= 2  # setup_s and one more
    listed = {os.path.basename(p)[:-5] for p in glob.glob(os.path.join(BENCH, "metrics", "*.json"))}
    assert listed == {m["name"] for m in MANIFEST["per_layer"]}
    layers = set()
    for m in MANIFEST["per_layer"]:
        spec = bench.load_json(BENCH, "metrics", m["name"] + ".json")
        for key in ("name", "layer", "unit", "better", "source", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert os.path.exists(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
        layers.add(m["layer"])
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in bench.metric_cells(moved, MANIFEST), (m["name"], cell)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
            beside = [
                o for o in MANIFEST["per_layer"]
                if "mfu" in o["name"].split(".") and o["moves"] == m["moves"]
                and set(m["workloads"]) <= set(o["workloads"])
            ]
            assert beside, f"no whole-step mfu beside {m['name']}"
    for cell in cells:
        assert any(cell in m["workloads"] for m in MANIFEST["per_layer"])
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"
