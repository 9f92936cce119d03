"""The cell ``mellum2-12b.long-mix``: the needed-work counts of
``chipbench/models/mellum.py`` against hand-worked values, the control one
precision down against the configuration's limits, a fault underneath the
timed path, the traffic, and the files the manifest names for the cell."""

import json
import os
import socket
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run as bench, traffic  # noqa: E402
from chipbench.models import mellum  # noqa: E402
from chipbench.readers import chunk_roofline, counter_ratio  # noqa: E402

BENCH = os.path.join(ROOT, "chipbench")
CELL = "mellum2-12b.long-mix"
MANIFEST = bench.load_json(ROOT, "BENCHMARK.json")


def config(rehearsal=False):
    cfg = bench.load_json(BENCH, "configs", "mellum2-12b.json")
    if rehearsal:
        bench.merge(cfg, cfg["rehearsal"])
    return cfg


# ------------------------------------------------------------------ counts


def test_counts_match_hand_worked_values():
    cfg = config()
    # q 2304 -> 32 x 128, k and v 2304 -> 4 x 128, o 4096 -> 2304
    assert mellum.attention_params(cfg) == 21_233_664
    assert 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304 == 21_233_664
    assert mellum.expert_params(cfg) == 3 * 2304 * 896 == 6_193_152
    # projections + router + 64 experts + two gains
    assert mellum.layer_params(cfg) == 417_747_456
    assert 21_233_664 + 2304 * 64 + 64 * 6_193_152 + 2 * 2304 == 417_747_456
    assert mellum.param_count(cfg) == 12 * 417_747_456 + 2 * 98_304 * 2304 + 2304
    whole = dict(cfg, num_hidden_layers=28)
    assert mellum.param_count(whole) == 12_149_915_904  # 12.15 B
    assert cfg["published"]["parameters"] == 12_149_915_904
    # bfloat16: the weights held here, and a token's K and V in one layer
    assert round(2 * mellum.param_count(cfg) / 1e9, 2) == 10.93
    assert mellum.kv_bytes_per_token(cfg) == 12 * 2 * 4 * 128 * 2
    # 8 experts a token, not 64
    assert mellum.token_weight_flops(cfg) == 2 * (
        21_233_664 + 147_456 + 8 * 6_193_152
    )
    assert mellum.layer_types(cfg) == (
        ["sliding_attention"] * 3 + ["full_attention"]
    ) * 3
    assert len(cfg["layer_types"]) == 28  # kept whole, as published


@pytest.mark.parametrize("span", [(0, 1), (0, 700), (1000, 1100), (5000, 5003)])
def test_sequence_flops_counts_the_keys_each_layer_sees(span):
    cfg = config()
    start, stop = span
    full = sum(p + 1 for p in range(start, stop))
    window = sum(min(p + 1, 1024) for p in range(start, stop))
    want = (
        12 * mellum.token_weight_flops(cfg) * (stop - start)
        + 4 * 4096 * (3 * full + 9 * window)
        + 2 * 2304 * 98_304
    )
    assert mellum.sequence_flops(cfg, start, stop, 1) == want


def test_least_bytes_cannot_overstate():
    cfg = config()
    least = mellum.decode_step_bytes(cfg, [40_000.0])
    # every projection and router, 8 experts a layer, the head, the gains
    weights = 2 * (
        12 * (21_233_664 + 147_456 + 8 * 6_193_152 + 2 * 2304)
        + 2304 * 98_304 + 2304
    )
    # full layers read every live position, sliding layers one window
    kv = 2 * 512 * 2 * (3 * 40_000 + 9 * 1024)
    assert least == weights + kv
    assert least < 2 * mellum.param_count(cfg) / 2  # far under all weights
    assert mellum.prefill_bytes(cfg, 3000) == weights + 3000 * 24_576
    assert mellum.chunk_bytes(cfg, 2048, 1024) == (
        weights + 1024 * 24_576 + 2 * 512 * 2 * (3 * 2048 + 9 * 1023)
    )


# ---------------------------------------------------------------- controls


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float8_control_fails_the_mellum_limits(seed):
    import jax.numpy as jnp

    cfg = config(rehearsal=True)
    params = mellum.init_params(seed, cfg, cfg["precision"]["parameters"])
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg["vocab_size"], (3, cfg["n_positions"])).astype(np.int32)
    rows = np.repeat(np.arange(3), 100).astype(np.int32)
    cols = np.tile(np.arange(100, 200), 3).astype(np.int32)  # past the window
    refs = {
        p: mellum.reference_logits(params, cfg, tokens, rows, cols, p)
        for p in ("float32", "default")
    }
    low = mellum.reference_logits(params, cfg, tokens, rows, cols, "bfloat16")

    def gaps(picks):  # the smaller of the two readings, as the driver takes it
        out = []
        for ref in refs.values():
            got = jnp.take_along_axis(ref, picks[:, None], axis=-1)[:, 0]
            below = jnp.max(ref, axis=-1) - got
            out.append((float(jnp.max(below)), float(jnp.mean(below))))
        return min(o[0] for o in out), min(o[1] for o in out)

    limits = cfg["limits"]
    assert gaps(jnp.argmax(refs["default"], axis=-1)) == (0.0, 0.0)
    widest, mean = gaps(jnp.argmax(low, axis=-1))
    assert widest > limits["logit_gap"] or mean > limits["logit_gap_mean"]
    assert mean > 2 * limits["logit_gap_mean"]


def test_an_altered_token_makes_the_cell_incorrect(capsys, monkeypatch):
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
    capsys.readouterr()
    rc = bench.main(
        ["--workload", CELL, "--seed", "3000000019", "--seconds", "2",
         "--rehearsal", "--trace", "0"]
    )
    out, _ = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and seen[0] >= 7
    assert line["correct"] is False
    gap = line["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


# ----------------------------------------------------------------- traffic


def test_traffic_repeats_for_a_seed_and_permutes_for_another():
    cell = bench.load_json(BENCH, "workloads", CELL + ".json")
    spec = cell["traffic"]
    assert spec == {
        "loop": "closed", "clients": 48, "population": 256, "ramp_s": 8,
        "drain_s": 60,
        "prompt_tokens": {
            "dist": "lognormal", "median": 2048, "sigma": 1.0, "min": 256,
            "max": 16384,
        },
        "output_tokens": {
            "dist": "lognormal", "median": 128, "sigma": 0.5, "min": 32,
            "max": 384,
        },
        "temperature": 0.0,
    }
    assert cell["check"]["requests"] >= 16 and cell["trace_s"] == 6
    a = traffic.generate(spec, 7, 51, 98_304)
    b = traffic.generate(spec, 7, 51, 98_304)
    c = traffic.generate(spec, 2**31 + 5, 51, 98_304)
    assert a == b and a != c
    sizes = lambda p: sorted(
        (len(r["body"]["prompt"]), r["body"]["max_new_tokens"])[i]
        for r in p["requests"] for i in (0, 1)
    )
    assert sizes(a) == sizes(c)  # the same work, in another order
    lens = [len(r["body"]["prompt"]) for r in a["requests"]]
    outs = [r["body"]["max_new_tokens"] for r in a["requests"]]
    assert 256 <= min(lens) and max(lens) == 16_384  # the tail is in
    assert 32 <= min(outs) <= max(outs) <= 384
    assert max(l + o for l, o in zip(lens, outs)) <= config()["n_positions"]
    assert a["clients"] == 48 > config()["engine"]["max_slots"] == 32


# ------------------------------------------------------- files and readers


def test_the_cell_and_its_metrics_are_in_the_manifest():
    cfg = config()
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "mellum2-12b")
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"] and "JetBrains/Mellum2" in cfg["source"]
    assert cfg["num_hidden_layers"] == 12 and cfg["hidden_size"] == 2304
    assert (cfg["num_experts"], cfg["num_experts_per_tok"]) == (64, 8)
    assert cfg["deployment"]["chips_sharing_a_layer"] == 1
    assert cfg["precision"]["parameters"] == "bfloat16"
    work = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert work["chips"] == 1 and len(work["why"]) <= 200
    tps = next(m for m in MANIFEST["end_to_end"] if m["name"] == "tokens_per_s")
    assert CELL in tps["workloads"] and tps["bound"] == 0.1
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    # by name and membership: where an entry sits in the list, and what
    # later PRs add beside these, is not this test's business
    for name in (
        "step.mfu.long", "step.decode_ms.long", "step.prefill_chunk_ms.long",
        "kernel.long_decode_roofline", "kernel.long_prefill_roofline",
        "sched.occupancy.long", "sched.wait_on_pages_share.long",
        "sched.recompute_share.long", "cache.window_release_share.long",
        "moe.expert_load_max_over_mean.long", "moe.experts_hit.long",
        "kernel.kv_read_amplification.long", "step.prefill_pad_share.long",
        "device.idle_share.long",
        "sched.host_gap_ms.long", "sched.emit_ms.long",
    ):
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "tokens_per_s", name
    for name in ("setup.program_load_s.long", "setup.program_trace_s.long"):
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "setup_s"


def test_traced_rehearsal_reports_the_cells_span_metrics(capsys):
    capsys.readouterr()
    rc = bench.main(
        ["--workload", CELL, "--seed", "3000000029", "--seconds", "2",
         "--rehearsal", "--trace", "1"]
    )
    out, _ = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    # no device plane and no table of peaks on the CPU: the roofline, mfu
    # and idle readers return nothing; what the spans and counters say is there
    for name in (
        "step.decode_ms.long", "step.prefill_chunk_ms.long",
        "sched.occupancy.long", "cache.window_release_share.long",
        "moe.expert_load_max_over_mean.long", "moe.experts_hit.long",
        "kernel.kv_read_amplification.long", "step.prefill_pad_share.long",
        "sched.host_gap_ms.long", "sched.emit_ms.long",
        "setup.program_load_s.long", "setup.program_trace_s.long",
    ):
        assert metrics[name]["value"] > 0, name
    assert not any("roofline" in n or "mfu" in n for n in metrics)
    assert metrics["moe.experts_hit.long"]["value"] <= 8  # rehearsal: 8 experts
    assert line["checks"]["window_compiles"]["value"] == 0
    assert line["notes"]["pages_left_in_use"] == 0


def test_chunk_roofline_divides_like_by_like():
    cfg = config()
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    chunks = [(0, 1024), (1024, 1024), (2048, 300)]
    facts = {
        "peaks": peaks,
        "trace_window": (100.0, 106.0),
        "spans": [
            {"name": "serve.prefill_chunk", "t_mono": 101.0 + i, "dur_s": 0.1,
             "attrs": {"start": s, "tokens": n}}
            for i, (s, n) in enumerate(chunks)
        ] + [
            {"name": "serve.decode_step", "attrs": {"occupancy": 3}},
            # a chunk that ran while the profiler was still starting
            {"name": "serve.prefill_chunk", "t_mono": 98.0, "dur_s": 0.1,
             "attrs": {"start": 0, "tokens": 1024}},
        ],
        "trace": {"programs": {
            "jit_chunk_step": {"calls": 3, "total_s": 0.3},
            "jit_decode": {"calls": 9, "total_s": 0.9},
        }},
    }
    least = sum(
        max(
            mellum.sequence_flops(cfg, s, s + n, 1) / 197e12,
            mellum.chunk_bytes(cfg, s, n) / 819e9,
        )
        for s, n in chunks
    )
    args = {"program": "jit_chunk_step", "span": "serve.prefill_chunk", "config": "mellum2-12b"}
    assert chunk_roofline.read(facts, **args) == pytest.approx(100 * least / 0.3)
    assert 0 < chunk_roofline.read(facts, **args) < 100
    # a program without the span's attributes, or an untraced run: nothing
    assert chunk_roofline.read(dict(facts, spans=[]), **args) is None
    assert chunk_roofline.read(dict(facts, trace=None), **args) is None
    old = [{"name": "serve.prefill_chunk", "attrs": {"request": 1}}]
    assert chunk_roofline.read(dict(facts, spans=old), **args) is None


def test_counter_ratio_reads_nothing_where_nothing_was_counted():
    assert counter_ratio.read({}, "no.such_total", "no.such_other_total") is None


def test_grouped_products_count_the_kernels_least_work():
    """The grouped expert kernel's own FLOPs and bytes. No metric reads
    them yet: the trace reduction keeps the ten longest operations by
    opcode and shape, and the kernel's are not always among them (PERF.md
    section 7)."""
    cfg = config()
    step = mellum.grouped_products(cfg, 240, 61.5)
    assert step[0] == (2 * 240 * 2304 * 896, 62 * 2304 * 896 * 2 + 240 * 2304 * 2 + 240 * 896 * 4)
    assert step[1] == step[0] and len(step) == 3
    assert step[2] == (step[0][0], 62 * 896 * 2304 * 2 + 240 * 896 * 2 + 240 * 2304 * 4)
    # a chunk touches every expert, and never more experts than it has pairs
    chunk = mellum.grouped_products(cfg, 8000, 64)
    assert chunk[0][1] == 64 * 2304 * 896 * 2 + 8000 * 2304 * 2 + 8000 * 896 * 4
    assert mellum.grouped_products(cfg, 8, 64)[0][1] == 8 * 2304 * 896 * 2 + 8 * 2304 * 2 + 8 * 896 * 4
    # on the v5e both are bound by the experts' matrices they read, a
    # 1,000-token chunk less far from its FLOPs than a decode step
    share = [
        (flops / 197e12) / (moved / 819e9)
        for flops, moved in (step[0], chunk[0])
    ]
    assert share[0] < 0.05 < share[1] < 1.0
