"""The serving step accounts for itself (ISSUE 25): leaf phase spans over
the engine's host loop on the monotonic clock, why and how long each
request waited, what a preemption recomputed, what loading a step
program costs, the layer-part scopes inside the step programs, and the
jitted function names the benchmark's trace readers match.

Every test drives the engine through its public surface (``submit``,
``step``, ``start``/``stop``, ``POST /generate``) and reads what an
operator reads: the JSONL sink, the handle's ``timings``, the HTTP
``timing`` object, the program registry, a profiler capture.
"""

import glob
import json
import os
import socket
import threading
import time

import numpy as np
import pytest

from tensorframes_tpu import obs
from tensorframes_tpu.models import TransformerLM
from tensorframes_tpu.obs import metrics as obs_metrics, programs
from tensorframes_tpu.serve import (
    GenerationEngine,
    GenerationHandle,
    GenRequest,
    PagePool,
    Scheduler,
)
from tensorframes_tpu.utils import chaos, set_config

pytestmark = pytest.mark.serve

VOCAB = 32

#: table A of ISSUE 25 — one decode-only step, in order
STEP_PHASES = [
    "serve.admit", "serve.grow", "serve.decode_args", "serve.decode_step",
    "serve.readback", "serve.emit", "serve.bookkeeping",
]
#: what a newcomer's single-shot prefill puts between admit and grow
PREFILL_PHASES = ["serve.prefill", "serve.readback", "serve.emit"]
#: what the steps owe is delivered under the next program (ISSUE 29), so
#: a step has no phase for it; ``serve.deliver`` is the phase of a
#: delivery no program follows: before the loop's idle wait, and when
#: ``step()`` / ``run_until_idle()`` hand control back
IDLE_PHASES = ["serve.deliver", "serve.idle_wait"]
ALL_SPANS = set(STEP_PHASES) | {"serve.prefill"} | set(IDLE_PHASES)


@pytest.fixture(scope="module")
def lm():
    return TransformerLM.init(0, VOCAB, d_model=16, n_heads=4, max_len=48)


class _Lines:
    """An in-memory JSONL sink for ``obs.set_trace_sink``."""

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


def _counter_value(name, **labels):
    try:
        return obs_metrics.registry().get(name).value(**labels)
    except KeyError:
        return 0.0


def _starved_engine(lm):
    """Two slots on four pages of four tokens: two prompts of 4 that
    generate 8 need 3 pages each, so the older one's growth at length 9
    evicts the younger, which then waits on pages until the older ends:
    exactly one preemption."""
    return GenerationEngine(
        lm, max_slots=2, page_size=4, max_seq_len=16, num_pages=4
    )


# ------------------------------------------------------------ phase spans


def test_step_loop_emits_leaf_phase_spans_in_order(lm, sink):
    eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
    handles = [eng.submit([1, 2, 3, 4, 5], 6), eng.submit([7, 8, 9], 6)]
    eng.run_until_idle()
    assert all(len(h.result(timeout=5)) == 6 for h in handles)
    events = [e for e in sink.events() if e["name"].startswith("serve.")]
    names = [e["name"] for e in events]
    assert set(names) == ALL_SPANS - {"serve.idle_wait"}
    # the first step: both newcomers prefill between admit and grow
    first = len(STEP_PHASES) + 2 * len(PREFILL_PHASES)
    assert names[:first] == (
        STEP_PHASES[:1] + 2 * PREFILL_PHASES + STEP_PHASES[1:]
    )
    # every later step with a batch is the seven phases, in order
    rest = names[first:]
    while len(rest) >= len(STEP_PHASES) and "serve.decode_step" in rest[:7]:
        assert rest[:7] == STEP_PHASES
        rest = rest[7:]
    # and the run ends on the one delivery no program hosted
    assert rest == IDLE_PHASES[:1] and names.count("serve.deliver") == 1
    # leaves: none nested on the thread's stack, none overlapping in time,
    # and back to back: each phase begins where the last one ended
    assert all(e["depth"] == 0 and e["parent_id"] is None for e in events)
    for a, b in zip(events, events[1:]):
        assert abs(b["t_mono"] - (a["t_mono"] + a["dur_s"])) < 5e-5, (a, b)


def test_phase_span_attrs(lm, sink):
    eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
    h = eng.submit([1, 2, 3, 4, 5], 4)
    eng.run_until_idle()
    by = {}
    for e in sink.events():
        by.setdefault(e["name"], []).append(e)
    admit = by["serve.admit"][0]["attrs"]
    assert admit == {"admitted": 1, "queued": 0, "blocked_on": "none"}
    assert by["serve.grow"][0]["attrs"] == {"ready": 1, "preempted": 0}
    pre = by["serve.prefill"][0]["attrs"]
    assert pre["prompt_len"] == 5 and pre["padded_len"] == 32
    assert pre["pad_share"] == pytest.approx(1 - 5 / 32)
    assert pre["recompute"] == 0
    step = by["serve.decode_step"][0]["attrs"]
    assert step["occupancy"] == 1 and step["requests"] == [h.request_id]
    # gather reads every slot's whole page table: 2 slots x 8 pages x 4
    assert step["kv_tokens_read"] == 2 * 8 * 4
    assert step["kv_tokens_live"] == 6  # the prompt and its first token
    assert step["kv_read_amplification"] == pytest.approx(64 / 6)
    emits = by["serve.emit"]
    assert [e["attrs"]["tokens"] for e in emits] == [1, 1, 1, 1]
    assert [e["attrs"]["finished"] for e in emits] == [0, 0, 0, 1]
    # each program hosts the delivery of the step before's token: the
    # prefill none, the three decode steps one each, and the last token
    # with the end mark goes out when run_until_idle returns
    assert pre["delivered_tokens"] == 0 and pre["deliver_s"] == 0.0
    hosted = [e["attrs"] for e in by["serve.decode_step"]]
    assert [a["delivered_tokens"] for a in hosted] == [1, 1, 1]
    assert all(0 < a["deliver_s"] < e["dur_s"] for a, e in zip(
        hosted, by["serve.decode_step"]
    ))
    (last,) = by["serve.deliver"]
    assert last["attrs"] == {"tokens": 1, "finished": 1}
    assert list(h.result(timeout=5)) == list(h)


def test_host_gap_lies_inside_the_wall_between_two_decode_steps(lm, sink):
    eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
    eng.submit([1, 2, 3], 8)
    eng.run_until_idle()
    steps = [e for e in sink.events() if e["name"] == "serve.decode_step"]
    assert len(steps) == 7
    # the first follows the prefill's wait; each later one the step before
    assert all(e["attrs"]["host_gap_s"] > 0 for e in steps)
    for a, b in zip(steps, steps[1:]):
        wall = b["t_mono"] - (a["t_mono"] + a["dur_s"])
        assert b["attrs"]["host_gap_s"] <= wall + 1e-4
    # and the phases between two dispatches fill it: no host time between
    # steps is left without a span (what is left is the dispatch span's
    # own opening, before the engine reads the clock)
    events = [e for e in sink.events() if e["name"].startswith("serve.")]
    between = gaps = 0.0
    for a, b in zip(steps, steps[1:]):
        phases = events[events.index(a) + 1 : events.index(b)]
        assert [e["name"] for e in phases] == (
            STEP_PHASES[4:] + STEP_PHASES[:3]
        )
        between += sum(e["dur_s"] for e in phases)
        gaps += b["attrs"]["host_gap_s"]
    assert between <= gaps + 1e-4
    assert gaps - between < max(0.15 * gaps, 3e-4)


def test_decode_wall_splits_into_issue_delivery_and_wait(
    lm, sink, monkeypatch
):
    """Every ``serve.decode_step`` says how its program's wall split:
    ``issue_s`` + ``deliver_s`` + ``wait_s`` is the registry's dispatch
    wall of that call."""
    walls = []
    record = programs.ProgramRecord.add_dispatch

    def add_dispatch(self, seconds):
        if self.name.startswith("serve.decode["):
            walls.append(seconds)
        record(self, seconds)

    monkeypatch.setattr(programs.ProgramRecord, "add_dispatch", add_dispatch)
    eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
    eng.generate([[1, 2, 3]], max_new_tokens=2)  # compile outside the count
    walls.clear()
    sink.lines.clear()
    eng.generate([[1, 2, 3], [4, 5]], max_new_tokens=6)
    steps = [
        e["attrs"] for e in sink.events() if e["name"] == "serve.decode_step"
    ]
    assert len(steps) == len(walls) == 5
    for a, wall in zip(steps, walls):
        assert a["issue_s"] > 0 and a["wait_s"] > 0
        split = a["issue_s"] + a["deliver_s"] + a["wait_s"]
        assert abs(split - wall) < 1e-3, (a, wall)
    for e in sink.events():
        if e["name"] == "serve.prefill":
            assert {"issue_s", "deliver_s", "wait_s"} <= set(e["attrs"])


def test_host_gap_splits_into_work_and_waiting(lm, sink):
    eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
    eng.submit([1, 2, 3], 8)
    eng.run_until_idle()
    steps = [
        e["attrs"] for e in sink.events() if e["name"] == "serve.decode_step"
    ]
    assert len(steps) == 7
    for a in steps:
        assert 0.0 <= a["host_gap_offcpu_s"] <= a["host_gap_s"], a
    # every span carries the thread's CPU time beside its wall
    for e in sink.events():
        assert 0.0 <= e["cpu_s"] <= e["dur_s"] + 1e-4, e


def test_a_callers_pause_between_steps_is_no_phase(lm, sink):
    eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
    eng.submit([1, 2, 3], 4)
    assert eng.step()
    time.sleep(0.05)
    eng.run_until_idle()
    assert all(
        e["dur_s"] < 0.04
        for e in sink.events()
        if e["name"] in ("serve.admit", "serve.grow", "serve.bookkeeping")
    )


def test_span_chain_links_only_under_one_consumer():
    chain = obs.SpanChain()
    lines = _Lines()
    obs.set_trace_sink(lines)
    try:
        with obs.span("t.a", chain=chain):
            pass
        time.sleep(0.01)
        with obs.span("t.b", chain=chain):
            pass
        a, b = lines.events()
        assert b["dur_s"] >= 0.01  # began where t.a ended
        assert abs(b["t_mono"] - (a["t_mono"] + a["dur_s"])) < 5e-5
        assert abs(b["ts"] - (a["ts"] + a["dur_s"])) < 5e-3
        # the sink went away and came back: spans ran nowhere meanwhile,
        # and the stale link must not stretch the next span over that
        obs.set_trace_sink(None)
        with obs.span("t.off", chain=chain) as sp:
            assert sp is None
        time.sleep(0.01)
        obs.set_trace_sink(lines)
        with obs.span("t.c", chain=chain):
            pass
        assert lines.events()[-1]["dur_s"] < 0.01
        chain.reset()
        time.sleep(0.01)
        with obs.span("t.d", chain=chain):
            pass
        assert lines.events()[-1]["dur_s"] < 0.01
    finally:
        obs.set_trace_sink(None)


def test_idle_wait_span_in_the_serving_loop(lm, sink):
    eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
    with eng:
        eng.submit([1, 2, 3], 2).result(timeout=60)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not any(
            e["name"] == "serve.idle_wait" for e in sink.events()
        ):
            time.sleep(0.01)
    waits = [e for e in sink.events() if e["name"] == "serve.idle_wait"]
    assert waits and all(e["depth"] == 0 for e in waits)


def test_spans_cost_one_predicate_with_no_consumer(lm):
    """No sink, no capture, no flight capture: ``span()`` hands back the
    shared null span and the attrs are never computed."""
    assert obs.trace_sink() is None
    with obs.span("serve.decode_step", occupancy=1) as sp:
        assert sp is None
    eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
    called = []
    eng._decode_step_attrs = lambda *a: called.append(a)
    eng.generate([[1, 2, 3]], max_new_tokens=3)
    assert called == []


def test_capture_host_plane_carries_every_phase_span(lm, tmp_path):
    """During a profiler capture every span is forwarded as a
    ``TraceAnnotation``: the host plane of the trace must hold every phase
    name, because that is what names the device's idle gaps
    (``chipbench/trace_reduce.py::_covering``)."""
    from jax.profiler import ProfileData

    from tensorframes_tpu.utils import profiling

    eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
    eng.generate([[1, 2, 3]], max_new_tokens=2)  # compile outside the capture
    with profiling.trace(str(tmp_path)):
        with eng:
            eng.submit([1, 2, 3, 4], 4).result(timeout=60)
            time.sleep(0.1)  # the loop goes idle inside the capture
    (path,) = glob.glob(
        os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")
    )
    seen = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                seen.update(ev.name for ev in line.events)
    assert ALL_SPANS <= seen, ALL_SPANS - seen


# ------------------------------------- why a request waited; preemption


def _mk(rid, plen, max_new=2):
    return GenRequest(
        request_id=rid, prompt=np.arange(1, plen + 1, dtype=np.int32),
        max_new_tokens=max_new, handle=GenerationHandle(rid),
    )


def test_blocked_head_is_charged_slots_when_the_slots_are_full():
    sched = Scheduler(PagePool(1, 1, 4, 16, 4), 1, 8, max_seq_len=64)
    first, second = _mk(1, 4), _mk(2, 4)
    sched.submit(first)
    sched.submit(second)
    assert len(sched.admit()) == 1 and sched.blocked_on == "slots"
    time.sleep(0.01)
    assert sched.admit() == [] and sched.blocked_on == "slots"
    t = second.handle.timings
    assert t["wait_slots_s"] >= 0.01 and "wait_pages_s" not in t
    assert "requeue_wait_s" not in t
    sched.finish(0)
    assert len(sched.admit()) == 1 and sched.blocked_on is None


def test_blocked_head_is_charged_pages_when_the_pool_is_short():
    # two free slots, but the pool holds the first prompt only
    sched = Scheduler(PagePool(1, 1, 4, 2, 4), 3, 8, max_seq_len=8)
    first, head, behind = _mk(1, 8, 0), _mk(2, 4), _mk(3, 4)
    for r in (first, head, behind):
        sched.submit(r)
    assert len(sched.admit()) == 1 and sched.blocked_on == "pages"
    time.sleep(0.01)
    assert sched.admit() == [] and sched.blocked_on == "pages"
    for r in (head, behind):  # behind a blocked head: the head's reason
        t = r.handle.timings
        assert t["wait_pages_s"] >= 0.01
        # only the moments before the first admit() saw them are not pages'
        assert t.get("wait_slots_s", 0.0) < t["wait_pages_s"]
    sched.finish(0)
    assert len(sched.admit()) == 2 and sched.blocked_on is None


def test_one_preemption_is_counted_timed_and_costed(lm):
    eng = _starved_engine(lm)
    before = _counter_value("serve.recomputed_tokens_total")
    handles = [eng.submit([1, 2, 3, 4], 8), eng.submit([5, 6, 7, 8], 8)]
    requeued_len = None
    while eng.step():
        if requeued_len is None and eng.scheduler.preemptions:
            (req,) = eng.scheduler._waiting
            requeued_len = len(req.prompt)
            assert req.computed == requeued_len
    assert eng.scheduler.preemptions == 1 and requeued_len > 4
    kept, victim = sorted(
        (h.timings for h in handles), key=lambda t: t.get("preemptions", 0)
    )
    assert "preemptions" not in kept and "recomputed_tokens" not in kept
    assert kept["prefill_tokens"] == 4
    assert victim["preemptions"] == 1
    # prompt + generated-so-far went through a prefill a second time
    assert victim["recomputed_tokens"] == requeued_len
    assert victim["prefill_tokens"] == 4 + requeued_len
    assert victim["requeue_wait_s"] > 0
    # it waited for the older stream's pages, not for a slot
    assert victim["wait_pages_s"] >= victim["requeue_wait_s"] > 0
    for t in (kept, victim):
        waited = t.get("wait_slots_s", 0.0) + t.get("wait_pages_s", 0.0)
        assert waited >= t["queue_wait_s"] - 1e-9
    assert (
        _counter_value("serve.recomputed_tokens_total") - before
        == requeued_len
    )
    # the cost ledger row carries the same numbers
    row = next(
        r for r in reversed(obs.requests.recent())
        if r["engine"] == eng.name and r["preemptions"] == 1
    )
    assert row["recomputed_tokens"] == requeued_len
    assert row["requeue_wait_s"] == victim["requeue_wait_s"]


def _post_generate(addr, spec):
    host, port = addr.rsplit(":", 1)
    body = json.dumps(spec).encode()
    head = f"POST /generate HTTP/1.1\r\nContent-Length: {len(body)}\r\n\r\n"
    with socket.create_connection((host, int(port)), timeout=120) as s:
        s.sendall(head.encode() + body)
        chunks = []
        while True:
            data = s.recv(65536)
            if not data:
                break
            chunks.append(data)
    raw = b"".join(chunks)
    return int(raw.split(b" ", 2)[1]), json.loads(raw.split(b"\r\n\r\n", 1)[1])


def test_http_timing_echoes_waits_and_preemption_cost(lm):
    from tensorframes_tpu.interop.serving import ScoringServer

    eng = _starved_engine(lm)
    replies = {}

    def call(addr, key, prompt):
        replies[key] = _post_generate(
            addr, {"prompt": prompt, "max_new_tokens": 8}
        )

    # slow steps keep both streams in flight together
    with chaos.scoped("serve.decode_step=latency:ms=20"):
        with ScoringServer(engine=eng) as addr:
            threads = [
                threading.Thread(target=call, args=(addr, k, p))
                for k, p in (("a", [1, 2, 3, 4]), ("b", [5, 6, 7, 8]))
            ]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=120)
                assert not th.is_alive()
    timings = []
    for status, body in replies.values():
        assert status == 200
        timings.append(body["timing"])
    hit = [t for t in timings if t.get("preemptions")]
    assert len(hit) == 1 and eng.scheduler.preemptions == 1
    (t,) = hit
    assert t["preemptions"] == 1
    assert t["recomputed_tokens"] == t["prefill_tokens"] - 4 > 4
    assert t["requeue_wait_s"] > 0
    for t in timings:
        assert isinstance(t["prefill_tokens"], int)
        waited = t.get("wait_slots_s", 0.0) + t.get("wait_pages_s", 0.0)
        assert waited >= t["queue_wait_s"] - 2e-6  # each rounded to 1 us


# ------------------------------------------- what loading a program costs


def test_registry_row_splits_the_first_call(tmp_path):
    import jax
    import jax.numpy as jnp

    def toy(x):
        # softmax and where are jitted callees: jax reports a trace for
        # each, inside the program's own, and only the program's counts
        for _ in range(8):
            x = jax.nn.softmax(jnp.where(x > 0, x @ x, 0.0), axis=-1)
        return x

    programs.reset()
    try:
        w = programs.instrument(
            jax.jit(toy), key="t:load", name="t.load", kind="test", sync=True
        )
        x = np.ones((16, 16), np.float32)
        w(x)
        w(x)
        (row,) = [r for r in programs.table() if r["name"] == "t.load"]
        assert row["trace_s"] > 0 and row["lower_s"] > 0
        backend = [row["backend_compile_s"], row["cache_load_s"]]
        assert sum(v is not None for v in backend) == 1
        assert row["cache_hit"] is (row["cache_load_s"] is not None)
        parts = row["trace_s"] + row["lower_s"] + sum(v or 0.0 for v in backend)
        assert 0 < parts <= row["compile_s"]
        # the second call compiled nothing: the split did not move
        assert row["dispatches"] == 1
        text = programs.render_table()
        assert "trace=" in text and "lower=" in text
        assert ("cache_load=" in text) or ("backend=" in text)
    finally:
        programs.reset()


def test_another_threads_compile_is_not_booked_to_the_running_program():
    import jax

    programs.reset()
    try:
        rec = programs.program("t:other", "t.other", "test")
        # no instrumented call is running on this thread
        programs._on_jax_duration(
            "/jax/core/compile/backend_compile_duration", 1.0
        )
        jax.jit(lambda x: x + 1)(np.ones(3, np.float32))
        assert rec.as_dict()["backend_compile_s"] is None
        assert rec.as_dict()["trace_s"] is None
    finally:
        programs.reset()


def test_kill_switch_leaves_registry_and_sink_untouched(lm, sink):
    """``TFT_OBS=0``: no span reaches the sink, no program registers (so
    the load split has nowhere to land), the recompute counter stands
    still — and the request's ``timings`` are what they are with
    observability on: the waits and the preemption's cost are the
    scheduler's own arithmetic, always on like ``queue_wait_s``."""
    programs.reset()
    before = _counter_value("serve.recomputed_tokens_total")
    set_config(observability=False)
    try:
        eng = _starved_engine(lm)
        handles = [eng.submit([1, 2, 3, 4], 8), eng.submit([5, 6, 7, 8], 8)]
        eng.run_until_idle()
        assert programs.programs() == []
        assert eng._decode_jit.record is None
    finally:
        set_config(observability=True)
    assert sink.events() == []
    assert _counter_value("serve.recomputed_tokens_total") == before
    victim = max(
        (h.timings for h in handles), key=lambda t: t.get("preemptions", 0)
    )
    assert victim["preemptions"] == 1 and victim["recomputed_tokens"] > 4
    assert victim["requeue_wait_s"] > 0 and "queue_wait_s" in victim
    assert "est_flops" not in victim  # the registry's share stays off


# ------------------------------------ inside and around the step programs


def _lowered_decode(eng):
    s = eng.max_slots
    args = (
        np.zeros(s, np.int32), np.zeros(s, np.int32),
        np.zeros((s, eng._max_pages), np.int32), np.zeros(s, np.float32),
        np.zeros(s, np.int32), np.ones(s, np.float32),
    )
    return eng._decode_jit.lower(eng._params_dev, eng.pool.k, eng.pool.v, *args)


def test_decode_program_names_its_layer_parts(lm):
    eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
    text = _lowered_decode(eng).as_text(debug_info=True)
    for scope in ("attn", "kv_write", "kv_read", "mlp", "head", "sample"):
        assert f"/{scope}/" in text or f"/{scope}\"" in text, scope
    # the paged write and read sit inside their layer's attention
    assert "attn/kv_write" in text and "attn/kv_read" in text


def test_jitted_functions_keep_the_names_the_benchmark_greps(lm):
    """The profiler names a device program after its jitted Python
    function. ``chipbench/metrics/kernel.decode_roofline.json``,
    ``kernel.prefill_roofline.json`` and ``kernel.score_roofline.json``
    match ``jit_decode``, ``jit_prefill`` and ``jit_wrapped`` by prefix
    (``chipbench/readers/roofline.py``), and
    ``chipbench/drivers/serve.py::_least_times`` counts ``jit_decode``
    calls: a rename silences the three roofline shares."""
    from tensorframes_tpu.capture.graph import CapturedGraph
    from tensorframes_tpu.engine.ops import _jitted
    from tensorframes_tpu.schema import FLOAT32, Shape, Unknown

    eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
    assert _lowered_decode(eng).as_text().lstrip().startswith(
        "module @jit_decode"
    )
    row = np.zeros((1, eng.max_seq_len), np.int32)
    prefill = eng._prefill_jit.lower(
        eng._params_dev, eng.pool.k, eng.pool.v, row, np.int32(3),
        np.zeros(eng._max_pages, np.int32), np.float32(0), np.int32(0),
        np.float32(1),
    )
    assert prefill.as_text().lstrip().startswith("module @jit_prefill")
    graph = CapturedGraph.from_callable(
        lambda x: {"y": x * 2.0}, {"x": (FLOAT32, Shape(Unknown, 4))}
    )
    frame_program = _jitted(graph).lower({"x": np.ones((2, 4), np.float32)})
    assert frame_program.as_text().lstrip().startswith("module @jit_wrapped")
