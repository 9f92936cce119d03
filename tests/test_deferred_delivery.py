"""Deferred delivery (ISSUE 29): what a step owes its streams — tokens,
end marks, counters, cost records — is handed over after the NEXT step
program is issued, or at once when no program follows.

The invariants, each driven through the public surface (``submit``,
``step``, ``run_until_idle``, ``start`` / ``stop``, ``POST /generate``
streamed): a handle sees its tokens in order and its end mark after the
last of them; an error's end mark comes after every token delivered
before it, and the delivered prefix is what a replay folds in; nothing
is delivered twice; nothing stays queued while the stepping thread
sleeps or after ``step()`` returns; the two counters add up to the
tokens streamed. Streams stay token for token what solo decode gives.
"""

import json
import socket
import threading
import time

import numpy as np
import pytest

from tensorframes_tpu import obs
from tensorframes_tpu.models import TransformerLM
from tensorframes_tpu.obs import metrics as obs_metrics, programs
from tensorframes_tpu.serve import GenerationEngine
from tensorframes_tpu.utils import chaos, set_config
from tensorframes_tpu.utils.failures import DeadlineExceededError

pytestmark = pytest.mark.serve

VOCAB = 32
DELIVERED = "serve.tokens_delivered_total"
UNDER_PROGRAM = "serve.tokens_delivered_under_program_total"

#: which programs a path's tokens come from: every path prefills, then
#: decodes (plainly or speculatively)
PATHS = {
    "decode": dict(),
    "prefill": dict(),  # max_new_tokens=1: the one-pass prefill's token only
    "chunk": dict(prefill_chunk_tokens=4),
    "spec": dict(draft_len=3),
}


@pytest.fixture(scope="module")
def lm():
    return TransformerLM.init(0, VOCAB, d_model=16, n_heads=4, max_len=48)


def _engine(lm, path, **kw):
    opts = dict(max_slots=2, page_size=4, max_seq_len=32)
    opts.update(PATHS[path])
    if path == "spec":
        opts["draft_params"] = lm.params
    opts.update(kw)
    return GenerationEngine(lm, **opts)


def _solo(lm, prompt, n):
    return lm.generate(np.asarray([prompt], np.int32), n)[0, len(prompt):]


def _prompts():
    rng = np.random.default_rng(29)
    return [
        [int(t) for t in rng.integers(1, VOCAB, n)] for n in (9, 6, 11)
    ]


def _budget(path):
    return 1 if path == "prefill" else 7


def _value(name):
    try:
        return obs_metrics.registry().get(name).value()
    except KeyError:
        return 0.0


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


def _read(handle, timeout=60):
    """What a client of ``handle`` sees, in order: its tokens, then its
    end mark (``None`` or the error). Reads in a thread so that a hung
    stream fails the test instead of hanging it."""
    seen = {"tokens": [], "end": "never"}

    def consume():
        try:
            for t in handle:
                seen["tokens"].append(t)
            seen["end"] = None
        except BaseException as e:
            seen["end"] = e

    th = threading.Thread(target=consume, daemon=True)
    th.start()
    th.join(timeout)
    assert not th.is_alive(), "the stream never ended"
    # a token handed over after the end mark would sit on the handle
    # (what a replay folds in) without the client ever having seen it
    assert seen["tokens"] == handle._tokens
    return seen["tokens"], seen["end"]


# ----------------------------------------- the hook a program hosts it on


class _Out:
    def __init__(self, log):
        self.log = log

    def block_until_ready(self):
        self.log.append("wait")
        return self


class _StubProgram:
    """A jitted program's surface, as far as the wrapper uses it: the
    call returns at once with something to wait on, and ``lower`` has a
    cost analysis (so that no cost estimate traces the stub again)."""

    def __init__(self, log):
        self.log = log

    def __call__(self, x):
        self.log.append("issue")
        return _Out(self.log)

    def lower(self, *args, **kwargs):
        return self

    def cost_analysis(self):
        return {"flops": 1.0, "bytes accessed": 1.0}


@pytest.mark.parametrize("observability", [True, False])
def test_after_issue_runs_between_issue_and_wait(observability):
    log = []
    stub = _StubProgram(log)
    programs.reset()
    set_config(observability=observability)
    try:
        prog = programs.instrument(
            stub, key="t:hosted", name="t.hosted", kind="test", sync=True,
            after_issue=lambda: log.append("after_issue"),
        )
        out = prog(1)
        assert isinstance(out, _Out)
        # under the kill switch the wrapper is a pass-through that never
        # waits (its caller does), and the callable still runs
        assert log == ["issue", "after_issue"] + ["wait"] * observability
        del log[:]
        plain = programs.instrument(
            stub, key="t:plain", name="t.plain", kind="test", sync=True
        )
        plain(1)
        assert log == ["issue"] + ["wait"] * observability
    finally:
        set_config(observability=True)
        programs.reset()


def test_what_after_issue_raises_fails_the_call():
    def boom():
        raise RuntimeError("from after_issue")

    programs.reset()
    try:
        prog = programs.instrument(
            lambda x: x, key="t:boom", name="t.boom", kind="test",
            sync=True, after_issue=boom,
        )
        with pytest.raises(RuntimeError, match="from after_issue"):
            prog(np.ones(2))
    finally:
        programs.reset()


# ------------------------------------------------ order, once, and counted


@pytest.mark.parametrize("path", sorted(PATHS))
def test_streams_are_solo_decode_in_order_once_and_counted(lm, sink, path):
    eng = _engine(lm, path)
    n = _budget(path)
    before = _value(DELIVERED), _value(UNDER_PROGRAM)
    handles = [eng.submit(p, n) for p in _prompts()]
    eng.run_until_idle()
    streamed = 0
    for p, h in zip(_prompts(), handles):
        tokens, end = _read(h, timeout=5)
        assert end is None
        np.testing.assert_array_equal(tokens, _solo(lm, p, n))
        np.testing.assert_array_equal(h.result(timeout=1), tokens)
        streamed += len(tokens)
        assert h.timings["tokens"] == n and h.timings["kv_pages"] >= 1
    assert not eng._owed and eng.pool.pages_in_use == 0
    delivered = _value(DELIVERED) - before[0]
    under = _value(UNDER_PROGRAM) - before[1]
    assert delivered == streamed
    events = [e for e in sink.events() if e["name"].startswith("serve.")]
    alone = [e["attrs"] for e in events if e["name"] == "serve.deliver"]
    hosted = sum(e["attrs"].get("delivered_tokens", 0) for e in events)
    # every token went out under a program or in a span of its own
    assert under == hosted == streamed - sum(a["tokens"] for a in alone)
    assert sum(a["finished"] for a in alone) <= len(handles)
    # run_until_idle keeps the loop's order: only its last delivery has
    # no program to hide behind (two slots for three requests: the third
    # is admitted while the first two still stream)
    assert len(alone) == 1 and events[-1]["name"] == "serve.deliver"
    assert under > 0 or path == "prefill"


@pytest.mark.parametrize("path", sorted(PATHS))
def test_step_returns_with_its_tokens_on_the_handle(lm, path):
    eng = _engine(lm, path)
    n = _budget(path)
    (prompt,) = _prompts()[:1]
    h = eng.submit(prompt, n)
    want = list(_solo(lm, prompt, n))
    while True:
        more = eng.step()
        assert not eng._owed
        produced = [
            len(a.generated) for a in eng.scheduler.slots if a is not None
        ]
        # the slot's own count while it lives, the whole answer after
        assert len(h._tokens) == (produced[0] if produced else n)
        assert h._tokens == want[: len(h._tokens)]
        if not more:
            break
    assert h.done and list(h.result(timeout=1)) == want


def test_a_finished_slot_is_filled_in_the_very_next_step(lm):
    eng = _engine(lm, "decode", max_slots=1)
    first, second = eng.submit([1, 2, 3], 2), eng.submit([4, 5, 6], 2)
    eng.step()  # prefill and one decode step: the first request is done
    assert first.done and eng.scheduler.slots == [None]
    eng.step()
    assert second.done and eng.scheduler.queue_depth == 0


# ----------------------------------------------------- the serving loop


def test_nothing_waits_out_the_idle_wait(lm, sink):
    eng = _engine(lm, "decode")
    with eng:
        h = eng.submit([1, 2, 3, 4], 5)
        tokens, end = _read(h, timeout=60)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not any(
            e["name"] == "serve.idle_wait" for e in sink.events()
        ):
            time.sleep(0.01)
    assert end is None and len(tokens) == 5
    names = [e["name"] for e in sink.events() if e["name"].startswith("serve.")]
    first_wait = names.index("serve.idle_wait", names.index("serve.decode_step"))
    # the stream's end went out before the loop's first sleep after it
    assert names[first_wait - 1] == "serve.deliver"
    last = [e for e in sink.events() if e["name"] == "serve.deliver"][-1]
    assert last["attrs"] == {"tokens": 1, "finished": 1}
    assert last["depth"] == 0 and last["parent_id"] is None


def _post_stream(addr, spec):
    host, port = addr.rsplit(":", 1)
    body = json.dumps(dict(spec, stream=True)).encode()
    head = f"POST /generate HTTP/1.1\r\nContent-Length: {len(body)}\r\n\r\n"
    with socket.create_connection((host, int(port)), timeout=120) as s:
        s.sendall(head.encode() + body)
        raw = b""
        while True:
            data = s.recv(65536)
            if not data:
                break
            raw += data
    head, _, payload = raw.partition(b"\r\n\r\n")
    assert head.split(b" ", 2)[1] == b"200", head
    return [json.loads(l) for l in payload.splitlines() if l.strip()]


def test_streamed_generate_is_solo_decode_and_the_counters_add_up(lm):
    from tensorframes_tpu.interop.serving import ScoringServer

    eng = _engine(lm, "decode", max_slots=4)
    prompts = [[1, 2, 3, 4], [5, 6, 7], [8, 9, 10, 11, 12], [13, 14]]
    before = _value(DELIVERED), _value(UNDER_PROGRAM)
    lines = {}

    def call(addr, i):
        lines[i] = _post_stream(
            addr, {"prompt": prompts[i], "max_new_tokens": 8}
        )

    with ScoringServer(engine=eng) as addr:
        threads = [
            threading.Thread(target=call, args=(addr, i))
            for i in range(len(prompts))
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
            assert not th.is_alive()
    streamed = 0
    for i, p in enumerate(prompts):
        *tokens, done = lines[i]
        assert done["done"] is True and done["tokens_total"] == 8
        # the terminal line is the last, and its cost record is whole
        assert done["timing"]["tokens"] == 8
        assert done["timing"]["decode_s"] > 0
        np.testing.assert_array_equal(
            [l["t"] for l in tokens], _solo(lm, p, 8)
        )
        streamed += len(tokens)
    delivered = _value(DELIVERED) - before[0]
    under = _value(UNDER_PROGRAM) - before[1]
    assert delivered == streamed
    # each request's last token has no program after it when it is the
    # last one streaming; all the others ride a decode step
    assert streamed - len(prompts) <= under <= streamed


# ------------------------------- leaving a slot with tokens still owed


def _slow_steps():
    # keeps a stream in flight while the test acts on it; the sleep sits
    # before the program's issue, with the step before's tokens owed
    return chaos.scoped("serve.decode_step=latency:ms=10")


def _loop_steps(eng, n):
    """``n`` steps as the engine's own loops take them (``_step_once``:
    what ``run_until_idle`` and the serving thread call), which leave the
    delivery to the next program — so the last step's tokens are owed
    when the test acts. A thread of its own cannot be steered there: the
    stepping thread re-takes the step lock at once and a caller may wait
    many steps for it."""
    for _ in range(n):
        assert eng._step_once()
    assert eng._owed


def _wait_for_tokens(handle, n, timeout=60):
    deadline = time.monotonic() + timeout
    while len(handle._tokens) < n:
        assert time.monotonic() < deadline, "the stream never got going"
        time.sleep(0.002)


@pytest.mark.parametrize("how", ["stop", "fault"])
def test_an_errors_end_mark_follows_every_delivered_token(lm, how):
    eng = _engine(lm, "decode")
    prompt = [3, 1, 4, 1, 5]
    with _slow_steps():
        eng.start()
        try:
            h = eng.submit(prompt, 24)
            _wait_for_tokens(h, 3)
            if how == "stop":
                eng.stop()
            else:
                eng.inject_fault(RuntimeError("injected"))
            tokens, end = _read(h, timeout=60)
        finally:
            eng.stop()
    assert isinstance(end, RuntimeError)
    assert 3 <= len(tokens) < 24
    # delivered once and in order: a prefix of solo decode, which is what
    # a replay would fold into the prompt and continue from
    np.testing.assert_array_equal(tokens, _solo(lm, prompt, 24)[: len(tokens)])
    assert not eng._owed


def test_restart_mid_stream_delivers_each_token_once(lm):
    eng = _engine(lm, "decode")
    prompt = [2, 7, 1, 8]
    h = eng.submit(prompt, 16)
    _loop_steps(eng, 3)
    assert len(h._tokens) == 3  # the prefill's and two steps'; one owed
    eng.restart()
    assert not eng._owed and len(h._tokens) == 4
    (requeued,) = eng.scheduler._waiting
    assert requeued.emitted == 4
    eng.run_until_idle()
    tokens, end = _read(h, timeout=5)
    assert end is None
    np.testing.assert_array_equal(tokens, _solo(lm, prompt, 16))


def test_a_preemption_keeps_the_victims_stream_whole(lm, sink):
    # two slots on four pages: the older stream's growth evicts the
    # younger one mid-run, with the step before's tokens still owed
    eng = _engine(lm, "decode", page_size=4, max_seq_len=16, num_pages=4)
    prompts = [[1, 2, 3, 4], [5, 6, 7, 8]]
    handles = [eng.submit(p, 8) for p in prompts]
    eng.run_until_idle()
    assert eng.scheduler.preemptions == 1
    for p, h in zip(prompts, handles):
        tokens, end = _read(h, timeout=5)
        assert end is None
        np.testing.assert_array_equal(tokens, _solo(lm, p, 8))
    # the eviction delivered first: a span of its own inside serve.grow
    nested = [
        e for e in sink.events()
        if e["name"] == "serve.deliver" and e["depth"] == 1
    ]
    assert len(nested) == 1 and nested[0]["attrs"]["tokens"] == 2


def test_a_deadline_mid_generation_closes_after_the_tokens(lm):
    eng = _engine(lm, "decode")
    prompt = [9, 8, 7]
    with chaos.scoped("serve.decode_step=latency:ms=20"):
        h = eng.submit(prompt, 24, deadline=0.15)
        eng.run_until_idle()
    tokens, end = _read(h, timeout=5)
    assert isinstance(end, DeadlineExceededError)
    assert 1 <= len(tokens) < 24
    np.testing.assert_array_equal(tokens, _solo(lm, prompt, 24)[: len(tokens)])
    # the error names how far generation had come: all of it was delivered
    assert f"({len(tokens)} of 24 tokens emitted)" in str(end)


def test_detach_slot_hands_over_a_handle_that_holds_the_snapshot(lm):
    src = _engine(lm, "decode")
    dst = _engine(lm, "decode")
    prompt = [6, 2, 8, 3, 1]
    h = src.submit(prompt, 16)
    _loop_steps(src, 3)
    assert len(h._tokens) == 3
    snap = src.detach_slot(h.request_id)
    assert len(snap.generated) == 4
    assert h._tokens == snap.generated and not h.done
    assert not src._owed
    h2 = dst.attach_slot(snap)
    dst.run_until_idle()
    rest, end = _read(h2, timeout=5)
    assert end is None
    np.testing.assert_array_equal(
        snap.generated + rest, _solo(lm, prompt, 16)
    )


def test_a_handle_closed_past_the_step_lock_takes_nothing_more(lm):
    """The fleet fences a wedged engine by failing its handles through
    the scheduler while the stuck step still holds the step lock: what
    that step owed is never delivered, so the delivered prefix a replay
    folds in stays what the client saw."""
    eng = _engine(lm, "decode")
    h = eng.submit([1, 2, 3], 8)
    held = threading.Event()
    release = threading.Event()

    def wedged_step():
        with eng._step_lock:
            _loop_steps(eng, 2)
            held.set()
            release.wait(30)
            eng._deliver_now()  # the step comes back after the fence

    th = threading.Thread(target=wedged_step, daemon=True)
    th.start()
    assert held.wait(30)
    seen = list(h._tokens)
    eng.scheduler.fail_all(RuntimeError("fenced"))
    release.set()
    th.join(30)
    assert not th.is_alive() and not eng._owed
    tokens, end = _read(h, timeout=5)
    assert tokens == seen and isinstance(end, RuntimeError)


def test_a_delivery_that_raises_cannot_fail_the_step(lm, caplog):
    eng = _engine(lm, "decode")
    h = eng.submit([1, 2, 3, 4], 4)

    def broken(*_a, **_k):
        raise RuntimeError("cost ledger down")

    eng._account_request = broken
    with caplog.at_level("WARNING"):
        eng.run_until_idle()
    tokens, end = _read(h, timeout=5)
    assert end is None and len(tokens) == 4
    assert any("cost record failed" in r.message for r in caplog.records)
