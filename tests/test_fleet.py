"""Serving fleet: replicated engines, health-gated router, request replay.

The correctness bar is inherited from test_serve.py and raised one tier:
a stream decoded through the FLEET — placed on some replica, possibly
killed mid-stream and replayed on another — must stay BYTE-IDENTICAL to
the same request decoded alone through ``transformer_generate``, greedy
and seeded sampling alike, and failover must add zero compiled programs
(every replica stays at <= 2 for its lifetime).
"""

import json
import socket
import threading
import time

import numpy as np
import pytest

from tensorframes_tpu.models import TransformerLM
from tensorframes_tpu.obs import metrics as obs_metrics
from tensorframes_tpu.serve import (
    EngineUnhealthyError,
    Fleet,
    GenerationEngine,
    QueueFullError,
)
from tensorframes_tpu.utils import chaos, get_config, set_config
from tensorframes_tpu.utils.chaos import ChaosFault
from tensorframes_tpu.utils.failures import DeadlineExceededError

pytestmark = pytest.mark.fleet

VOCAB = 32


@pytest.fixture(scope="module")
def lm():
    return TransformerLM.init(0, VOCAB, d_model=16, n_heads=4, max_len=64)


@pytest.fixture
def fast_retries():
    old = (get_config().max_retries, get_config().retry_backoff_s)
    set_config(max_retries=3, retry_backoff_s=0.001)
    yield
    set_config(max_retries=old[0], retry_backoff_s=old[1])


def _counter_value(name, **labels):
    try:
        return obs_metrics.registry().get(name).value(**labels)
    except KeyError:
        return 0.0


def _prompts(rng, lens):
    return [
        rng.integers(1, VOCAB, size=n).astype(np.int32).tolist() for n in lens
    ]


def _solo(lm, prompt, n, **kw):
    return lm.generate(np.asarray([prompt], np.int32), n, **kw)[
        0, len(prompt):
    ]


def _fleet(lm, n=2, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_seq_len", 48)
    kw.setdefault("watchdog_interval_s", 0.02)
    return Fleet(lm, replicas=n, **kw)


def _wait_for(pred, timeout=15.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.01)
    pytest.fail(f"timed out waiting for {what}")


# ---------------------------------------------------------------------------


class TestPlacement:
    def test_least_loaded_prefers_free_pages_then_queue(self, lm):
        fleet = _fleet(lm, 2)
        r0, r1 = fleet._replicas
        # equal load: deterministic name tiebreak
        assert fleet._candidates()[0] is r0
        # r0 loses pages -> r1 leads
        held = r0.engine.pool.alloc(3)
        assert fleet._candidates()[0] is r1
        r0.engine.pool.free(held)
        # pages equal again, but r0's queue is deeper -> r1 leads
        r0.engine.submit([1, 2], 2)
        assert fleet._candidates()[0] is r1

    def test_session_affinity_sticks_until_fenced(self, lm):
        fleet = _fleet(lm, 2, auto_restart=False)
        h = fleet.submit([1, 2, 3], 2, session="chat-1")
        first = fleet._inflight[h.request_id].replica
        # the affine replica now carries MORE load, yet the session
        # sticks to it (KV locality beats balance while it is healthy)
        h2 = fleet.submit([1, 2, 3], 2, session="chat-1")
        assert fleet._inflight[h2.request_id].replica is first
        # a session-free request balances away from the loaded replica
        h3 = fleet.submit([1, 2, 3], 2)
        assert fleet._inflight[h3.request_id].replica is not first
        # fencing the affine replica remaps the session
        fleet._fence(first, ChaosFault("drill"))
        h4 = fleet.submit([1, 2, 3], 2, session="chat-1")
        assert fleet._inflight[h4.request_id].replica is not first

    def test_all_fenced_sheds_with_engine_unhealthy(self, lm):
        fleet = _fleet(lm, 2, auto_restart=False)
        for rep in fleet._replicas:
            fleet._fence(rep, ChaosFault("drill"))
        with pytest.raises(EngineUnhealthyError):
            fleet.submit([1, 2], 2)

    def test_all_queues_full_raises_queue_full(self, lm):
        fleet = _fleet(lm, 2, queue_capacity=0)
        with pytest.raises(QueueFullError):
            fleet.submit([1, 2], 2, block=False)
        t0 = time.monotonic()
        with pytest.raises(QueueFullError):
            fleet.submit([1, 2], 2, timeout=0.05)
        assert time.monotonic() - t0 < 5

    def test_infeasible_request_rejected_everywhere(self, lm):
        fleet = _fleet(lm, 2, max_seq_len=16)
        with pytest.raises(ValueError, match="exceeds max_seq_len"):
            fleet.submit([1] * 10, 10)

    def test_nonpositive_deadline_is_a_value_error(self, lm):
        """Same client-error classification as the single engine (HTTP
        400), not a 504-shaped DeadlineExceededError from placement."""
        fleet = _fleet(lm, 2)
        for bad in (0, -1):
            with pytest.raises(ValueError, match="deadline"):
                fleet.submit([1, 2], 2, deadline=bad)


# ---------------------------------------------------------------------------


class TestFleetServing:
    def test_streams_match_solo_greedy_and_sampled(self, lm):
        rng = np.random.default_rng(60)
        fleet = _fleet(lm, 2)
        prompts = _prompts(rng, (3, 5, 2, 7, 4, 6))
        with fleet:
            greedy = [fleet.submit(p, 6) for p in prompts[:3]]
            sampled = [
                fleet.submit(p, 6, temperature=0.8, top_p=0.9, seed=70 + i)
                for i, p in enumerate(prompts[3:])
            ]
            for p, h in zip(prompts[:3], greedy):
                np.testing.assert_array_equal(
                    h.result(timeout=60), _solo(lm, p, 6)
                )
            for i, (p, h) in enumerate(zip(prompts[3:], sampled)):
                np.testing.assert_array_equal(
                    h.result(timeout=60),
                    _solo(lm, p, 6, temperature=0.8, top_p=0.9, seed=70 + i),
                )
        assert all(n <= 2 for n in fleet.program_counts().values())

    def test_failover_mid_stream_is_byte_identical(self, lm, fast_retries):
        """The tentpole regression: kill the replica with active work
        mid-stream; every survivor replays on the other replica and the
        consumer streams stay byte-identical — greedy AND seeded
        sampling — with zero new compiled programs; the dead replica is
        restarted, probed, and re-admitted."""
        rng = np.random.default_rng(61)
        fleet = _fleet(lm, 2, max_seq_len=64)
        prompts = _prompts(rng, (3, 5, 2, 7))
        temps = [0.0, 0.8, 0.0, 0.9]
        seeds = [0, 81, 0, 83]
        replays0 = _counter_value("fleet.replays_total")
        failovers0 = _counter_value("fleet.failovers_total")
        with chaos.scoped("serve.decode_step=latency:ms=25"):
            with fleet:
                handles = [
                    fleet.submit(p, 20, temperature=t, top_p=0.9, seed=s)
                    for p, t, s in zip(prompts, temps, seeds)
                ]
                time.sleep(0.3)  # streams mid-flight (25 ms/step x 20)
                victim = next(
                    rep
                    for rep in fleet._replicas
                    if any(
                        s is not None for s in rep.engine.scheduler.slots
                    )
                )
                fleet._kill_replica(victim, ChaosFault("mid-stream kill"))
                outs = [h.result(timeout=120) for h in handles]
                for p, t, s, o in zip(prompts, temps, seeds, outs):
                    np.testing.assert_array_equal(
                        o,
                        _solo(
                            lm, p, 20, temperature=t, top_p=0.9, seed=s
                        ),
                    )
                _wait_for(
                    lambda: victim.state == "active",
                    what="restart + probe re-admission",
                )
        assert _counter_value("fleet.replays_total") > replays0
        assert _counter_value("fleet.failovers_total") > failovers0
        assert all(n <= 2 for n in fleet.program_counts().values())

    def test_chaos_site_kills_named_replica(self, lm, fast_retries):
        """``fleet.replica_fault.<name>`` kills exactly that replica on
        the watchdog's schedule; traffic continues on the survivor."""
        rng = np.random.default_rng(62)
        fleet = _fleet(lm, 2, auto_restart=False, max_seq_len=64)
        prompts = _prompts(rng, (4, 3, 5, 2))
        failovers0 = _counter_value("fleet.failovers_total")
        with chaos.scoped(
            "serve.decode_step=latency:ms=10;"
            "fleet.replica_fault.r1=fatal:every=5:times=1"
        ):
            with fleet:
                handles = [fleet.submit(p, 15) for p in prompts]
                _wait_for(
                    lambda: fleet.replica_state("r1") == "fenced",
                    what="chaos kill of r1",
                )
                assert fleet.replica_state("r0") == "active"
                for p, h in zip(prompts, handles):
                    np.testing.assert_array_equal(
                        h.result(timeout=120), _solo(lm, p, 15)
                    )
                # the fleet keeps serving on the survivor
                h = fleet.submit(prompts[0], 4)
                np.testing.assert_array_equal(
                    h.result(timeout=60), _solo(lm, prompts[0], 4)
                )
        assert _counter_value("fleet.failovers_total") > failovers0

    def test_deadline_is_terminal_not_replayed(self, lm):
        fleet = _fleet(lm, 2, max_seq_len=64)
        replays0 = _counter_value("fleet.replays_total")
        with chaos.scoped("serve.decode_step=latency:ms=30"):
            with fleet:
                h = fleet.submit([1, 2, 3], 40, deadline=0.15)
                with pytest.raises(DeadlineExceededError):
                    h.result(timeout=60)
        assert _counter_value("fleet.replays_total") == replays0

    def test_replay_cap_fails_instead_of_bouncing(self, lm):
        fleet = _fleet(lm, 2, max_replays=0, max_seq_len=64)
        with chaos.scoped("serve.decode_step=latency:ms=25"):
            with fleet:
                h = fleet.submit([1, 2, 3], 20)
                _wait_for(
                    lambda: fleet._inflight.get(h.request_id) is not None
                    and fleet._inflight[h.request_id].replica is not None,
                    what="placement",
                )
                time.sleep(0.1)
                rep = fleet._inflight[h.request_id].replica
                fleet._kill_replica(rep, ChaosFault("kill"))
                with pytest.raises(ChaosFault):
                    h.result(timeout=60)

    def test_replay_of_completed_stream_settles_success(self, lm):
        """A replica can die in the window between a stream's final
        emission and its clean close (the wedged drain path); replaying
        it would submit ``max_new_tokens=0`` (ValueError) or keep
        generating past EOS. The router must settle such records as
        SUCCESS — the client already has every byte."""
        fleet = _fleet(lm, 2)
        h = fleet.submit([1, 2, 3], 4)  # unstarted fleet: queued only
        rec = fleet._inflight[h.request_id]
        rec.handle._tokens.extend([5, 6, 7, 8])  # budget fully delivered
        assert fleet._replay(rec) is True
        assert h.done and h.error is None
        np.testing.assert_array_equal(h.result(timeout=1), [5, 6, 7, 8])
        assert h.request_id not in fleet._inflight
        # EOS variant: the engine-level default eos ended the stream
        fleet2 = _fleet(lm, 2, eos_id=9)
        h2 = fleet2.submit([1, 2], 6)
        rec2 = fleet2._inflight[h2.request_id]
        rec2.handle._tokens.extend([4, 9])
        assert fleet2._replay(rec2) is True
        assert h2.done and h2.error is None

    def test_all_fenced_forever_fails_fast_with_replica_error(self, lm):
        """The fail-fast rule, fleet edition: when no healthy replica
        appears within ``failover_timeout_s``, a parked survivor's
        handle fails with the replica's REAL error — a deadline-less
        consumer must never hang forever against a dead fleet."""
        fleet = _fleet(
            lm, 1, auto_restart=False, failover_timeout_s=0.2,
            max_seq_len=64,
        )
        with chaos.scoped("serve.decode_step=latency:ms=25"):
            with fleet:
                h = fleet.submit([1, 2, 3], 20)
                time.sleep(0.1)
                fleet._kill_replica(
                    fleet._replicas[0], ChaosFault("down for good")
                )
                t0 = time.monotonic()
                with pytest.raises(ChaosFault):
                    h.result(timeout=30)
                assert time.monotonic() - t0 < 10

    def test_stop_fails_inflight_handles(self, lm):
        fleet = _fleet(lm, 2, max_seq_len=64)
        with chaos.scoped("serve.decode_step=latency:ms=30"):
            fleet.start()
            h = fleet.submit([1, 2, 3], 40)
            time.sleep(0.1)
            fleet.stop()
        assert h.done and h.error is not None
        with pytest.raises(RuntimeError):
            h.result(timeout=1)


# ---------------------------------------------------------------------------


def _http(addr, req: bytes) -> bytes:
    host, port = addr.rsplit(":", 1)
    with socket.create_connection((host, int(port)), timeout=30) as c:
        c.sendall(req)
        out = b""
        while True:
            b = c.recv(65536)
            if not b:
                break
            out += b
    return out


def _post_generate(addr, spec) -> tuple:
    body = json.dumps(spec).encode()
    req = (
        b"POST /generate HTTP/1.1\r\nContent-Length: "
        + str(len(body)).encode()
        + b"\r\n\r\n"
        + body
    )
    resp = _http(addr, req)
    status = int(resp.split(b" ", 2)[1])
    payload = json.loads(resp.split(b"\r\n\r\n", 1)[1] or b"{}")
    return status, payload, resp


class TestWedgeWatchdog:
    """``wedge_timeout_s``: a step that outlives it with work pending is
    a wedge — unless the step is compiling a program it has not run
    before, which at real model widths takes longer than any sane wedge
    bound (on the chip, four GPT-2-small replicas compiling side by
    side were all fenced at the 30 s default before this exemption)."""

    def test_a_compiling_step_is_not_a_wedge(self, lm):
        fleet = _fleet(lm, 1, wedge_timeout_s=0.15)
        failovers0 = _counter_value("fleet.failovers_total")
        try:
            fleet.start()
            # the stall lands inside the FIRST prefill dispatch — the one
            # that compiles — and outlasts the wedge bound twice over
            with chaos.scoped("serve.prefill=latency:ms=400:times=1"):
                h = fleet.submit([3, 1, 4], 6)
                np.testing.assert_array_equal(
                    np.asarray(h.result(timeout=60)), _solo(lm, [3, 1, 4], 6)
                )
            assert fleet.replica_state("r0") == "active"
            assert _counter_value("fleet.failovers_total") == failovers0
        finally:
            fleet.stop()

    def test_a_compiled_step_that_stalls_is_fenced(self, lm):
        fleet = _fleet(lm, 2, wedge_timeout_s=0.15)
        try:
            fleet.start()
            for eng in fleet.engines:  # warm both replicas' programs
                eng.submit([2, 7], 3).result(timeout=60)
            assert set(fleet.program_counts().values()) == {2}
            with chaos.scoped("serve.decode_step=latency:ms=600:times=1"):
                h = fleet.submit([5, 9, 2], 6)
                _wait_for(
                    lambda: "fenced" in {
                        fleet.replica_state(n) for n in fleet.replica_names
                    },
                    what="the stalled replica fenced as wedged",
                )
                # the stream replays on the survivor, byte-identical
                np.testing.assert_array_equal(
                    np.asarray(h.result(timeout=60)), _solo(lm, [5, 9, 2], 6)
                )
        finally:
            fleet.stop()


class TestFleetEndpoint:
    def test_generate_healthz_aggregate_and_fencing(self, lm):
        from tensorframes_tpu.interop.serving import ScoringServer

        rng = np.random.default_rng(63)
        fleet = _fleet(lm, 2, auto_restart=False)
        p = _prompts(rng, (4,))[0]
        with ScoringServer(engine=fleet) as addr:
            status, payload, _ = _post_generate(
                addr, {"prompt": p, "max_new_tokens": 6, "session": "u1"}
            )
            assert status == 200
            np.testing.assert_array_equal(payload["tokens"], _solo(lm, p, 6))
            resp = _http(addr, b"GET /healthz HTTP/1.1\r\n\r\n")
            assert int(resp.split(b" ", 2)[1]) == 200
            body = json.loads(resp.split(b"\r\n\r\n", 1)[1])
            assert body["replicas_total"] == 2
            assert body["replicas_healthy"] == 2
            assert set(body["replicas"]) == {"r0", "r1"}
            assert body["replicas"]["r0"]["state"] == "active"

            # ONE replica fenced: healthz stays 200, generate keeps going
            fleet._fence(fleet._replicas[0], ChaosFault("drill"))
            resp = _http(addr, b"GET /healthz HTTP/1.1\r\n\r\n")
            assert int(resp.split(b" ", 2)[1]) == 200
            body = json.loads(resp.split(b"\r\n\r\n", 1)[1])
            assert body["replicas_healthy"] == 1
            assert body["replicas"]["r0"]["state"] == "fenced"
            status, payload, _ = _post_generate(
                addr, {"prompt": p, "max_new_tokens": 6}
            )
            assert status == 200
            np.testing.assert_array_equal(payload["tokens"], _solo(lm, p, 6))

            # ALL replicas fenced: 503 + the adaptive Retry-After on both
            fleet._fence(fleet._replicas[1], ChaosFault("drill"))
            status, payload, resp = _post_generate(
                addr, {"prompt": p, "max_new_tokens": 6}
            )
            assert status == 503 and b"Retry-After:" in resp
            resp = _http(addr, b"GET /healthz HTTP/1.1\r\n\r\n")
            assert int(resp.split(b" ", 2)[1]) == 503
            assert b"Retry-After:" in resp

    def test_malformed_sampling_params_are_400(self, lm):
        """REGRESSION: a non-numeric temperature/top_p/seed must answer
        400 like any other bad request — not crash the connection
        thread and drop the connection without a response."""
        from tensorframes_tpu.interop.serving import ScoringServer

        eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
        with ScoringServer(engine=eng) as addr:
            for bad in (
                {"temperature": "hot"},
                {"top_p": []},
                {"seed": "x"},
                {"deadline_s": "soon"},
            ):
                status, payload, _ = _post_generate(
                    addr, {"prompt": [1, 2], "max_new_tokens": 2, **bad}
                )
                assert status == 400 and "error" in payload, bad

    def test_session_on_plain_engine_is_a_400(self, lm):
        from tensorframes_tpu.interop.serving import ScoringServer

        eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
        with ScoringServer(engine=eng) as addr:
            status, payload, _ = _post_generate(
                addr,
                {"prompt": [1, 2], "max_new_tokens": 2, "session": "u1"},
            )
            assert status == 400


class TestHTTPRouting:
    """Satellite: unknown paths 404, wrong verbs 405 + Allow."""

    def test_unknown_path_is_404(self):
        from tensorframes_tpu.interop.serving import ScoringServer

        with ScoringServer(lambda x: {"y": x}) as addr:
            resp = _http(addr, b"GET /nope HTTP/1.1\r\n\r\n")
            assert int(resp.split(b" ", 2)[1]) == 404
            resp = _http(
                addr, b"POST /also/nope HTTP/1.1\r\nContent-Length: 0\r\n\r\n"
            )
            assert int(resp.split(b" ", 2)[1]) == 404

    def test_wrong_verb_is_405_with_allow(self):
        from tensorframes_tpu.interop.serving import ScoringServer

        with ScoringServer(lambda x: {"y": x}) as addr:
            resp = _http(addr, b"GET /generate HTTP/1.1\r\n\r\n")
            assert int(resp.split(b" ", 2)[1]) == 405
            assert b"Allow: POST" in resp
            resp = _http(
                addr, b"POST /metrics HTTP/1.1\r\nContent-Length: 0\r\n\r\n"
            )
            assert int(resp.split(b" ", 2)[1]) == 405
            assert b"Allow: GET" in resp
            resp = _http(
                addr, b"POST /healthz HTTP/1.1\r\nContent-Length: 0\r\n\r\n"
            )
            assert int(resp.split(b" ", 2)[1]) == 405
            assert b"Allow: GET" in resp
            # trailing slash normalizes to the same route
            resp = _http(addr, b"GET /metrics/ HTTP/1.1\r\n\r\n")
            assert int(resp.split(b" ", 2)[1]) == 200


class TestAdaptiveRetryAfter:
    """Satellite: Retry-After = queue depth x p50 inter-token latency,
    clamped to [1, 30]; 1 while no latency samples exist."""

    class _Stub:
        def __init__(self, depth):
            self._depth = depth

        def health(self):
            return {"queue_depth": self._depth}

    def _seed_itl(self, value, n=10):
        import tensorframes_tpu.serve.engine  # noqa: F401 — registers it

        hist = obs_metrics.registry().get("serve.inter_token_seconds")
        hist._reset()
        for _ in range(n):
            hist.observe(value)
        return hist

    def test_no_samples_falls_back_to_one(self):
        from tensorframes_tpu.interop.serving import _adaptive_retry_after

        hist = self._seed_itl(0.5, n=0)
        assert _adaptive_retry_after(self._Stub(50)) == "1"
        hist._reset()

    def test_scales_with_depth_and_latency_and_clamps(self):
        from tensorframes_tpu.interop.serving import _adaptive_retry_after

        hist = self._seed_itl(0.5)  # p50 bucket bound = 4^10 us = 1.048576 s
        try:
            assert _adaptive_retry_after(self._Stub(0)) == "1"  # floor
            assert _adaptive_retry_after(self._Stub(10)) == "11"
            assert _adaptive_retry_after(self._Stub(1000)) == "30"  # ceiling
            assert _adaptive_retry_after(None) == "1"
        finally:
            hist._reset()

    def test_fast_tokens_still_floor_at_one(self):
        from tensorframes_tpu.interop.serving import _adaptive_retry_after

        hist = self._seed_itl(1e-4)  # 100 us/token: depth 3 -> well under 1s
        try:
            assert _adaptive_retry_after(self._Stub(3)) == "1"
        finally:
            hist._reset()

    def test_histogram_quantile(self):
        hist = self._seed_itl(0.5)  # all samples in the 1.048576 s bucket
        try:
            assert hist.quantile(0.5) == pytest.approx(4.0 ** 10 * 1e-6)
            assert hist.quantile(1.0) == pytest.approx(4.0 ** 10 * 1e-6)
            hist.observe(1e9)  # +Inf tail reports the top bound
            assert hist.quantile(1.0) == hist.bounds[-1]
            with pytest.raises(ValueError):
                hist.quantile(1.5)
        finally:
            hist._reset()
        assert hist.quantile(0.5) is None  # no samples


# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestFleetSoak:
    def test_chaos_soak_replica_kill_under_staggered_traffic(
        self, lm, fast_retries
    ):
        """The acceptance soak: 16 staggered requests (greedy + seeded
        sampling) against 3 replicas while the chaos schedule kills one
        replica mid-stream and injects transient step faults (p=0.1).
        Every request completes within its deadline, every stream is
        byte-identical to its solo decode, ``fleet.failovers_total``
        advances, and no replica compiles more than its two step
        programs."""
        rng = np.random.default_rng(64)
        fleet = Fleet(
            lm,
            replicas=3,
            max_slots=4,
            page_size=4,
            max_seq_len=64,
            queue_capacity=32,
            watchdog_interval_s=0.02,
            probe_timeout_s=60,
        )
        plens = [int(rng.integers(1, 11)) for _ in range(16)]
        nnews = [int(rng.integers(4, 15)) for _ in range(16)]
        temps = [0.0 if i % 2 == 0 else 0.8 for i in range(16)]
        seeds = [90 + i for i in range(16)]
        prompts = _prompts(rng, plens)
        failovers0 = _counter_value("fleet.failovers_total")
        replays0 = _counter_value("fleet.replays_total")
        deadline = 120.0
        t0 = time.monotonic()
        handles = []
        with chaos.scoped(
            "seed=21;"
            "serve.decode_step=transient:p=0.1;"
            "serve.prefill=transient:p=0.1;"
            "serve.decode_step=latency:ms=10;"
            "fleet.replica_fault.r1=fatal:every=8:times=1"
        ):
            with fleet:
                waves = [
                    prompts[:5], prompts[5:9], prompts[9:13], prompts[13:]
                ]
                k = 0
                for wave in waves:
                    for p in wave:
                        handles.append(
                            fleet.submit(
                                p,
                                nnews[k],
                                temperature=temps[k],
                                top_p=0.9,
                                seed=seeds[k],
                                deadline=deadline,
                            )
                        )
                        k += 1
                    time.sleep(0.04)
                for i, h in enumerate(handles):
                    toks = h.result(timeout=deadline)
                    np.testing.assert_array_equal(
                        toks,
                        _solo(
                            lm,
                            prompts[i],
                            nnews[i],
                            temperature=temps[i],
                            top_p=0.9,
                            seed=seeds[i],
                        ),
                        err_msg=(
                            f"stream {i} diverged (plen={plens[i]}, "
                            f"n={nnews[i]}, temp={temps[i]})"
                        ),
                    )
        wall = time.monotonic() - t0
        assert wall < deadline  # nobody outlived the per-request budget
        assert _counter_value("fleet.failovers_total") > failovers0
        assert _counter_value("fleet.replays_total") > replays0
        counts = fleet.program_counts()
        assert all(n <= 2 for n in counts.values()), counts
