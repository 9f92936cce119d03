"""A span carries the stepping thread's CPU time beside its wall: the
sink event's ``cpu_s``, over the same interval as ``dur_s`` (a chained
phase's backdated start included), and the profiler annotation's
``span_id`` / ``cpu_s`` stats. With no consumer attached none of it
runs: neither a span nor an instrumented program reads the CPU clock."""

import glob
import json
import os
import sys
import time

import pytest

from tensorframes_tpu import obs
from tensorframes_tpu.obs import programs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Lines:
    def __init__(self):
        self.lines = []

    def write(self, line):
        self.lines.append(line)
        return len(line)

    def flush(self):
        pass

    def events(self):
        return [json.loads(l) for l in self.lines if l.strip()]


@pytest.fixture
def sink():
    s = _Lines()
    obs.set_trace_sink(s)
    yield s
    obs.set_trace_sink(None)


def _spin(seconds):
    t = time.perf_counter()
    while time.perf_counter() - t < seconds:
        pass


def test_a_sleeping_span_is_off_the_cpu(sink):
    with obs.span("t.sleep"):
        time.sleep(0.02)
    (e,) = sink.events()
    assert e["dur_s"] >= 0.02
    assert 0.0 <= e["cpu_s"] <= e["dur_s"]
    assert e["dur_s"] - e["cpu_s"] >= 0.015


def test_a_spinning_span_is_on_the_cpu(sink):
    with obs.span("t.spin"):
        _spin(0.03)
    (e,) = sink.events()
    assert e["cpu_s"] >= 0.8 * e["dur_s"]


def test_a_chained_phase_counts_cpu_from_its_backdated_start(sink):
    chain = obs.SpanChain()
    with obs.span("t.a", chain=chain):
        pass
    _spin(0.03)  # no span open: the next phase begins where t.a ended
    with obs.span("t.b", chain=chain):
        pass
    a, b = sink.events()
    assert b["dur_s"] >= 0.03
    assert b["cpu_s"] >= 0.8 * b["dur_s"]


def test_a_chain_is_not_followed_across_threads(sink):
    import threading

    chain = obs.SpanChain()
    with obs.span("t.a", chain=chain):
        pass
    time.sleep(0.02)

    def other():
        with obs.span("t.b", chain=chain):
            pass

    t = threading.Thread(target=other)
    t.start()
    t.join()
    a, b = sink.events()
    # another thread's CPU clock cannot measure this one's stretch
    assert b["dur_s"] < 0.02


def test_no_consumer_reads_no_cpu_clock(monkeypatch):
    """No sink, no capture, no flight capture: a span, an instrumented
    program's call and a whole engine run never call ``thread_time``."""
    import jax
    import numpy as np

    from tensorframes_tpu.models import TransformerLM
    from tensorframes_tpu.serve import GenerationEngine

    assert obs.trace_sink() is None

    def forbidden():
        raise AssertionError("thread_time read with no consumer attached")

    lm = TransformerLM.init(0, 32, d_model=16, n_heads=4, max_len=48)
    eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
    hosted = []
    prog = programs.instrument(
        jax.jit(lambda x: x + 1), key="t.cpu:add", name="t.add",
        kind="test", sync=True, after_issue=lambda: hosted.append(1),
    )
    prog(np.float32(1.0))  # the compile-paying call
    monkeypatch.setattr(time, "thread_time", forbidden)
    with obs.span("serve.decode_step", occupancy=1) as sp:
        assert sp is None
    assert float(prog(np.float32(2.0))) == 3.0
    assert hosted == [1, 1]
    assert prog.issue_s >= 0.0 and prog.wait_s >= 0.0
    (out,) = eng.generate([[1, 2, 3]], max_new_tokens=3)
    assert len(out) == 3


def test_the_program_wall_splits_at_its_two_seams(monkeypatch):
    """``issue_s`` + what ``after_issue`` reports + ``wait_s`` is the
    registry's dispatch wall of the call."""
    import jax
    import numpy as np

    walls = []
    record = programs.ProgramRecord.add_dispatch

    def add_dispatch(self, seconds):
        walls.append(seconds)
        record(self, seconds)

    def after():
        time.sleep(0.01)
        return 0.01

    prog = programs.instrument(
        jax.jit(lambda x: x * 2), key="t.cpu:mul", name="t.mul",
        kind="test", sync=True, after_issue=after,
    )
    prog(np.float32(1.0))
    monkeypatch.setattr(programs.ProgramRecord, "add_dispatch", add_dispatch)
    prog(np.float32(2.0))
    (wall,) = walls
    assert prog.issue_s > 0.0 and prog.wait_s >= 0.0
    assert prog.issue_s + 0.01 + prog.wait_s == pytest.approx(wall, abs=1e-9)


def test_a_capture_carries_span_id_and_cpu_time(tmp_path):
    """The profiler's host events keep the spans' plain names, so the
    benchmark's ``trace_reduce.load`` still keeps them, and carry each
    span's ``span_id``, ``cpu_s`` and ``dur_s`` as event stats."""
    from jax.profiler import ProfileData

    from tensorframes_tpu.utils import profiling

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from chipbench import trace_reduce

    lines = _Lines()
    obs.set_trace_sink(lines)
    try:
        with profiling.trace(str(tmp_path)):
            with obs.span("t.sleep"):
                time.sleep(0.02)
            with obs.span("t.spin"):
                _spin(0.02)
    finally:
        obs.set_trace_sink(None)
    (path,) = glob.glob(
        os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")
    )
    kept = trace_reduce.load(path, {"t.sleep", "t.spin"})["host"]
    assert sorted(e[0] for e in kept) == ["t.sleep", "t.spin"]
    stats = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in ("t.sleep", "t.spin"):
                        stats[ev.name] = dict(ev.stats)
    events = {e["name"]: e for e in lines.events()}
    for name in ("t.sleep", "t.spin"):
        assert stats[name]["span_id"] == events[name]["span_id"]
        assert stats[name]["cpu_s"] == events[name]["cpu_s"]
        assert stats[name]["dur_s"] == events[name]["dur_s"]
    assert stats["t.sleep"]["cpu_s"] < 0.01
