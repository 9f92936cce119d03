"""The per-layer metrics of ISSUE 25 and their two readers: what the
serving step says of itself (phase spans, why a request waited, what a
preemption recomputed, what loading a step program cost) as the benchmark
reads it. A traced rehearsal of each serving cell has to report every one
of them; the readers are also tried on hand-made facts, and on facts as a
program without these spans, keys and fields leaves them (nothing to read:
``None``, never 0 and never an error)."""

import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run as bench  # noqa: E402
from chipbench.readers import registry_sum, span_mean, timing_ratio  # noqa: E402

MANIFEST = bench.load_json(ROOT, "BENCHMARK.json")
NEW = {
    "gpt2-xl.chat-rate": {
        "sched.host_gap_ms.rate", "sched.emit_ms.rate",
        "sched.wait_on_pages_share.rate", "sched.recompute_share.rate",
        "kernel.kv_read_amplification.rate", "setup.program_load_s",
        "setup.program_trace_s",
    },
    "gpt2-xl.docs-batch": {
        "sched.host_gap_ms.batch", "sched.wait_on_pages_share.batch",
        "sched.recompute_share.batch", "kernel.kv_read_amplification.batch",
        "step.prefill_pad_share.batch", "setup.program_load_s",
        "setup.program_trace_s",
    },
}
#: the records say these cannot be nought (ISSUE 25, acceptance)
NON_ZERO = ("host_gap", "kv_read_amplification", "prefill_pad_share", "program_")


def test_the_manifest_lists_the_new_metrics_for_their_cells():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for cell, names in NEW.items():
        for name in names:
            assert cell in by_name[name]["workloads"], (name, cell)
    listed = {n for names in NEW.values() for n in names}
    assert len(listed) == 12
    # appended, after everything the benchmark already had
    assert {m["name"] for m in MANIFEST["per_layer"][-12:]} == listed


@pytest.mark.parametrize("cell", sorted(NEW))
def test_traced_serving_rehearsal_reports_the_new_metrics(capsys, cell):
    capsys.readouterr()
    rc = bench.main(
        ["--workload", cell, "--seed", "3000000029", "--seconds", "2",
         "--rehearsal", "--trace", "1"]
    )
    out, _ = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    assert NEW[cell] <= set(metrics), NEW[cell] - set(metrics)
    for name in NEW[cell]:
        value = metrics[name]["value"]
        assert math.isfinite(value) and value >= 0, (name, value)
        if any(part in name for part in NON_ZERO):
            assert value > 0, name
    # what the benchmark already read from the same spans is still there
    assert "sched.queue_wait_p90_ms" in metrics or "sched.occupancy.batch" in metrics
    assert line["checks"]["window_compiles"]["value"] == 0


def test_timing_ratio_on_hand_made_facts():
    pages = {"num": ["wait_pages_s"], "den": ["wait_pages_s", "wait_slots_s"]}
    facts = {"measured": [
        {"timing": {"wait_slots_s": 1.0, "wait_pages_s": 3.0}},
        {"timing": {"wait_slots_s": 4.0}},  # never waited on pages
        {"timing": {}},  # failed before admission
        {"timing": None},
    ]}
    assert timing_ratio.read(facts, **pages) == pytest.approx(100 * 3 / 8)
    recompute = {"num": ["recomputed_tokens"], "den": ["prefill_tokens"]}
    none_preempted = {"measured": [{"timing": {"prefill_tokens": 640}}] * 3}
    assert timing_ratio.read(none_preempted, **recompute) == 0.0  # a reading
    one = {"measured": [
        {"timing": {"prefill_tokens": 1000, "recomputed_tokens": 250}},
        {"timing": {"prefill_tokens": 250}},
    ]}
    assert timing_ratio.read(one, **recompute) == pytest.approx(20.0)


def test_timing_ratio_finds_nothing_on_empty_or_older_facts():
    pages = {"num": ["wait_pages_s"], "den": ["wait_pages_s", "wait_slots_s"]}
    assert timing_ratio.read({}, **pages) is None
    assert timing_ratio.read({"measured": []}, **pages) is None
    # a program that stamps none of the keys: nothing to read, not 0 %
    older = {"measured": [{"timing": {"queue_wait_s": 0.2, "total_s": 1.0}}]}
    assert timing_ratio.read(older, **pages) is None
    # every wait nought: no share of nothing
    zero = {"measured": [{"timing": {"wait_slots_s": 0.0}}]}
    assert timing_ratio.read(zero, **pages) is None


def test_registry_sum_on_hand_made_facts():
    rows = {
        "serve.prefill[eng1]": {"compile_s": 5.0, "trace_s": 1.0, "lower_s": 0.5},
        "serve.decode[eng1]": {"compile_s": 7.0, "trace_s": 2.0, "lower_s": None},
        "engine:prediction": {"compile_s": 100.0, "trace_s": 50.0, "lower_s": 50.0},
    }
    facts = {"registry": {"before": {}, "after": rows}}
    assert registry_sum.read(facts, "serve.", ["compile_s"]) == 12.0
    assert registry_sum.read(facts, "serve.", ["trace_s", "lower_s"]) == 3.5
    assert registry_sum.read(facts, "serve.", ["compile_s"], scale=1e3) == 12000.0


def test_registry_sum_finds_nothing_on_empty_or_older_facts():
    assert registry_sum.read({}, "serve.", ["compile_s"]) is None
    empty = {"registry": {"before": {}, "after": {}}}
    assert registry_sum.read(empty, "serve.", ["compile_s"]) is None
    # rows of a registry that keeps no load split
    older = {"registry": {"before": {}, "after": {
        "serve.decode[eng1]": {"compile_s": 7.0},
    }}}
    assert registry_sum.read(older, "serve.", ["trace_s", "lower_s"]) is None
    assert registry_sum.read(older, "frame.", ["compile_s"]) is None


def test_span_metrics_find_nothing_where_the_program_has_no_such_attr():
    """The accepted ``span_mean`` on the spans of a program without this
    PR: ``serve.decode_step`` carries ``occupancy`` only, there is no
    ``serve.emit``."""
    older = {"spans": [
        {"name": "serve.decode_step", "dur_s": 0.1, "attrs": {"occupancy": 5}},
        {"name": "serve.prefill", "dur_s": 0.08, "attrs": {"prompt_len": 600}},
    ]}
    for name in NEW["gpt2-xl.chat-rate"] | NEW["gpt2-xl.docs-batch"]:
        spec = bench.load_json(ROOT, "chipbench", "metrics", name + ".json")
        if spec["reader"] == "span_mean":
            assert span_mean.read(older, **spec["args"]) is None, name
