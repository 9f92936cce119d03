"""The four per-layer metrics that split the host's time around a step
program: the host gap's off-CPU part (``sched.host_offcpu_ms.long`` /
``.rate``) and the decode program's issue and wait
(``step.decode_issue_ms.long``, ``step.decode_wait_ms.long``), all read
by the accepted ``span_mean`` from attributes of ``serve.decode_step``.
They are read from a recorded span list, read nothing where the program
stamps no such attribute, and are reported by a traced rehearsal of
their cells."""

import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run as bench  # noqa: E402
from chipbench.readers import span_mean  # noqa: E402

MANIFEST = bench.load_json(ROOT, "BENCHMARK.json")
#: name -> (cell, the span attribute it reads, the end-to-end metric it moves)
SPLIT = {
    "sched.host_offcpu_ms.long": (
        "mellum2-12b.long-mix", "host_gap_offcpu_s", "tokens_per_s"),
    "sched.host_offcpu_ms.rate": (
        "gpt2-xl.chat-rate", "host_gap_offcpu_s", "itl_p99_ms"),
    "step.decode_issue_ms.long": (
        "mellum2-12b.long-mix", "issue_s", "tokens_per_s"),
    "step.decode_wait_ms.long": (
        "mellum2-12b.long-mix", "wait_s", "tokens_per_s"),
}
#: the last per-layer entry the benchmark held before these
BEFORE = "sched.deliver_overlap_share.long"

RECORDED = {"spans": [
    {"name": "serve.decode_step", "dur_s": 0.031, "cpu_s": 0.004, "attrs": {
        "occupancy": 31, "host_gap_s": 0.0031, "host_gap_offcpu_s": 0.0021,
        "issue_s": 0.0008, "deliver_s": 0.003, "wait_s": 0.0272,
    }},
    {"name": "serve.decode_step", "dur_s": 0.029, "cpu_s": 0.003, "attrs": {
        "occupancy": 32, "host_gap_s": 0.0029, "host_gap_offcpu_s": 0.0011,
        "issue_s": 0.0012, "deliver_s": 0.002, "wait_s": 0.0248,
    }},
    # the first step after a program whose span was not live: no off-CPU
    {"name": "serve.decode_step", "dur_s": 0.03, "cpu_s": 0.003, "attrs": {
        "occupancy": 30, "host_gap_s": 0.004,
        "issue_s": 0.001, "deliver_s": 0.0, "wait_s": 0.029,
    }},
    {"name": "serve.grow", "dur_s": 0.001, "cpu_s": 0.001, "attrs": {
        "issue_s": 9.0, "wait_s": 9.0, "host_gap_offcpu_s": 9.0,
    }},
]}


def _spec(name):
    return bench.load_json(ROOT, "chipbench", "metrics", name + ".json")


def test_the_manifest_appends_the_four_for_their_cells():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name, (cell, attr, moves) in SPLIT.items():
        entry = by_name[name]
        assert names.index(name) > names.index(BEFORE), name
        assert entry["workloads"] == [cell], name
        assert entry["moves"] == moves and entry["unit"] == "ms", name
        assert entry["better"] == "lower" and entry["source"] == "program_span"
        spec = _spec(name)
        assert spec["reader"] == "span_mean"
        assert spec["args"] == {
            "span": "serve.decode_step", "attr": attr, "scale": 1000.0,
        }
        for key in ("name", "layer", "unit", "better", "source", "moves"):
            assert spec[key] == entry[key], (name, key)
    assert by_name["sched.host_offcpu_ms.long"]["layer"] == (
        by_name["sched.host_gap_ms.long"]["layer"]
    )
    assert by_name["step.decode_issue_ms.long"]["layer"] == (
        by_name["step.decode_ms.long"]["layer"]
    )


@pytest.mark.parametrize("name,expected", [
    ("sched.host_offcpu_ms.long", (2.1 + 1.1) / 2),
    ("sched.host_offcpu_ms.rate", (2.1 + 1.1) / 2),
    ("step.decode_issue_ms.long", (0.8 + 1.2 + 1.0) / 3),
    ("step.decode_wait_ms.long", (27.2 + 24.8 + 29.0) / 3),
])
def test_the_four_read_a_recorded_span_list(name, expected):
    assert span_mean.read(RECORDED, **_spec(name)["args"]) == (
        pytest.approx(expected)
    )


@pytest.mark.parametrize("name", sorted(SPLIT))
def test_the_four_read_nothing_where_the_attribute_is_absent(name):
    """A program without this split: its ``serve.decode_step`` spans
    carry the accepted attributes and none of these."""
    older = {"spans": [
        {"name": "serve.decode_step", "dur_s": 0.03, "attrs": {
            "occupancy": 31, "host_gap_s": 0.003, "deliver_s": 0.002,
        }},
        {"name": "serve.emit", "dur_s": 0.0004, "attrs": {"tokens": 31}},
    ]}
    args = _spec(name)["args"]
    assert span_mean.read(older, **args) is None
    assert span_mean.read({}, **args) is None
    assert span_mean.read({"spans": []}, **args) is None


@pytest.mark.parametrize(
    "cell", sorted({cell for cell, _, _ in SPLIT.values()})
)
def test_a_traced_rehearsal_reports_the_split(capsys, cell):
    capsys.readouterr()
    rc = bench.main(
        ["--workload", cell, "--seed", "3000000041", "--seconds", "2",
         "--rehearsal", "--trace", "1"]
    )
    out, _ = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    for name, (where, _, _) in SPLIT.items():
        if where != cell:
            continue
        value = metrics[name]["value"]
        assert math.isfinite(value) and value >= 0, (name, value)
    gap = next(n for n in metrics if n.startswith("sched.host_gap_ms."))
    offcpu = gap.replace("host_gap_ms", "host_offcpu_ms")
    assert metrics[offcpu]["value"] <= metrics[gap]["value"]
    if cell == "mellum2-12b.long-mix":  # a decode program waits on the device
        assert metrics["step.decode_wait_ms.long"]["value"] > 0
