"""Tensor-parallel serving (serve/tp.py): mesh-sharded step programs +
the sharded KV PagePool, on the CPU-simulated mesh (conftest provisions
8 virtual devices; `make test-tp` provisions them itself).

The correctness bar, inherited from every serve feature and now pinned
ACROSS TP degrees: decode streams at TP=2 and TP=4 must be
BYTE-IDENTICAL to TP=1 solo decode — greedy and seeded — under chunked
prefill, prefix-cache hits, defragmentation, restart, and a mid-stream
chaos kill with failover onto a replica of a DIFFERENT TP degree. The
capacity contract: aggregate KV pages scale with the degree
(``num_pages`` is the per-chip budget), so a workload that exhausts
TP=1 admission serves preemption-free at TP=2.
"""

import json
import socket
import time

import numpy as np
import pytest

from tensorframes_tpu.models import TransformerLM
from tensorframes_tpu.obs import metrics as obs_metrics
from tensorframes_tpu.parallel import make_mesh
from tensorframes_tpu.serve import Fleet, GenerationEngine

pytestmark = [pytest.mark.serve, pytest.mark.tp]

VOCAB = 32


@pytest.fixture(scope="module")
def lm():
    # 8 MHA heads so tp in {1, 2, 4} slices whole KV heads; d_ff = 128
    # divides by 4 for the at-rest weight shards
    return TransformerLM.init(0, VOCAB, d_model=32, n_heads=8, max_len=64)


def _prompts(seed, lens):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(1, VOCAB, size=n).astype(np.int32).tolist()
        for n in lens
    ]


def _counter_total(name, **labels):
    try:
        return obs_metrics.registry().get(name).value(**labels)
    except KeyError:
        return 0.0


def _mesh(tp):
    return make_mesh({"tp": tp}) if tp > 1 else None


# ---------------------------------------------------------------------------
# the byte-identity matrix
# ---------------------------------------------------------------------------


class TestByteIdentityMatrix:
    def test_tp_matrix_greedy_and_seeded(self, lm):
        """TP=1 (no mesh) vs TP=1 (1-chip mesh) vs TP=2 vs TP=4, greedy
        AND seeded sampling, under chunked prefill + prefix-cache hits:
        every stream byte-identical, ≤ 3 compiled step programs per
        engine at every degree."""
        prompts = _prompts(0, (5, 12, 23, 17))
        kw = dict(
            max_slots=4, page_size=8, max_seq_len=64,
            prefill_chunk_tokens=8, prefix_cache=True,
        )
        solo = GenerationEngine(lm, **kw)
        base_g = solo.generate(prompts, 12)
        base_s = solo.generate(prompts, 12, temperature=0.8, seed=11,
                               top_p=0.9)
        assert solo.num_step_programs <= 3
        for tp in (1, 2, 4):
            eng = GenerationEngine(lm, mesh=make_mesh({"tp": tp}), **kw)
            assert eng.tp_degree == tp
            got_g = eng.generate(prompts, 12)
            # a second pass hits the prefix cache (shared pages + COW)
            got_cached = eng.generate(prompts, 12)
            got_s = eng.generate(prompts, 12, temperature=0.8, seed=11,
                                 top_p=0.9)
            for a, b in zip(base_g, got_g):
                np.testing.assert_array_equal(a, b)
            for a, b in zip(base_g, got_cached):
                np.testing.assert_array_equal(a, b)
            for a, b in zip(base_s, got_s):
                np.testing.assert_array_equal(a, b)
            assert eng.num_step_programs <= 3, (
                f"tp={tp} compiled {eng.num_step_programs} step programs"
            )

    def test_tp_matches_models_oracle(self, lm):
        """The chain closes: TP decode == solo engine == the models
        oracle (transformer_generate) for the same request."""
        prompt = _prompts(3, (14,))[0]
        oracle = lm.generate(np.asarray([prompt], np.int32), 10)[0, 14:]
        eng = GenerationEngine(
            lm, max_slots=2, page_size=8, max_seq_len=64, mesh=_mesh(2)
        )
        np.testing.assert_array_equal(eng.generate([prompt], 10)[0], oracle)

    def test_defragment_and_restart_stay_identical(self, lm):
        prompts = _prompts(5, (9, 21))
        solo = GenerationEngine(lm, max_slots=2, page_size=8,
                                max_seq_len=64)
        base = solo.generate(prompts, 10, temperature=0.5, seed=2)
        eng = GenerationEngine(lm, max_slots=2, page_size=8,
                               max_seq_len=64, mesh=_mesh(4))
        eng.generate(prompts, 10)
        eng.defragment()
        after_defrag = eng.generate(prompts, 10, temperature=0.5, seed=2)
        eng.restart()
        after_restart = eng.generate(prompts, 10, temperature=0.5, seed=2)
        for a, b in zip(base, after_defrag):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(base, after_restart):
            np.testing.assert_array_equal(a, b)


class TestShardedPrefillAttention:
    def test_one_pass_prefill_byte_identical_at_every_degree(self, lm):
        """ISSUE 15 satellite (ROADMAP 1 follow-on): the TP prefill's
        ATTENTION is now sharded along KV heads — each shard computes
        only its heads' causal scores/softmax/weighted-sum and the
        tiled gather reassembles the solo context bit-for-bit. No
        chunking here, so every prompt takes the one-pass prefill
        program; greedy AND seeded first tokens (and the decode that
        follows from the scattered k/v) must match solo at TP=1/2/4."""
        prompts = _prompts(31, (3, 17, 40))
        solo = GenerationEngine(lm, max_slots=4, page_size=8,
                                max_seq_len=64)
        base_g = solo.generate(prompts, 8)
        base_s = solo.generate(prompts, 8, temperature=0.9, seed=17,
                               top_p=0.85)
        for tp in (1, 2, 4):
            eng = GenerationEngine(
                lm, max_slots=4, page_size=8, max_seq_len=64,
                mesh=make_mesh({"tp": tp}),
            )
            for a, b in zip(base_g, eng.generate(prompts, 8)):
                np.testing.assert_array_equal(a, b)
            for a, b in zip(
                base_s,
                eng.generate(prompts, 8, temperature=0.9, seed=17,
                             top_p=0.85),
            ):
                np.testing.assert_array_equal(a, b)
            assert eng.num_step_programs <= 2


class TestSpeculativeUnderTP:
    def test_spec_streams_match_solo_at_tp_degrees(self, lm):
        """ISSUE 15: the verify program shards on KV heads like decode
        (the draft runs replicated); speculative streams at TP=2/4 are
        byte-identical to solo non-speculative decode, within the <= 5
        program budget."""
        prompts = _prompts(41, (7, 19))
        solo = GenerationEngine(lm, max_slots=2, page_size=8,
                                max_seq_len=64)
        base_g = solo.generate(prompts, 10)
        base_s = solo.generate(prompts, 10, temperature=0.7, seed=23)
        for tp in (2, 4):
            eng = GenerationEngine(
                lm, max_slots=2, page_size=8, max_seq_len=64,
                mesh=make_mesh({"tp": tp}),
                draft_params=lm.params, draft_len=3,
            )
            for a, b in zip(base_g, eng.generate(prompts, 10)):
                np.testing.assert_array_equal(a, b)
            for a, b in zip(
                base_s,
                eng.generate(prompts, 10, temperature=0.7, seed=23),
            ):
                np.testing.assert_array_equal(a, b)
            assert eng.num_step_programs <= 5
            assert eng.health()["speculative"]["proposed"] > 0


# ---------------------------------------------------------------------------
# mesh validation + pool semantics
# ---------------------------------------------------------------------------


class TestMeshAndPool:
    def test_mesh_must_be_1d(self, lm):
        with pytest.raises(ValueError, match="1-D"):
            GenerationEngine(lm, mesh=make_mesh({"dp": 2, "tp": 2}))

    def test_heads_must_divide(self, lm):
        bad = TransformerLM.init(0, VOCAB, d_model=24, n_heads=6,
                                 max_len=32)
        with pytest.raises(ValueError, match="divide"):
            GenerationEngine(bad, mesh=make_mesh({"tp": 4}))

    def test_moe_blocks_rejected(self):
        moe = TransformerLM.init(
            0, VOCAB, d_model=16, n_heads=4, max_len=32, moe_experts=2
        )
        with pytest.raises(ValueError, match="[Mm]oe|experts"):
            GenerationEngine(moe, mesh=make_mesh({"tp": 2}))

    def test_num_pages_is_per_chip_budget(self, lm):
        """Same constructor kwargs, higher degree → N× aggregate pages
        (serve.pages_capacity reports the scaled total) at ~flat
        per-chip KV bytes."""
        caps = {}
        for tp in (1, 2, 4):
            eng = GenerationEngine(
                lm, max_slots=4, page_size=8, num_pages=8,
                max_seq_len=64, mesh=_mesh(tp),
            )
            caps[tp] = eng.pool.num_pages
            assert _counter_total("serve.pages_capacity") == float(
                eng.pool.num_pages
            )
            if tp > 1:
                h = eng.health()
                assert h["tp_degree"] == tp
                assert h["tp"]["pages_capacity"] == 8 * tp
        assert caps == {1: 8, 2: 16, 4: 32}

    def test_capacity_scaling_unlocks_admission(self, lm):
        """The acceptance drill: a pool budget that forces TP=1 to
        preempt serves the same workload preemption-free at TP=2 (the
        aggregate pool doubled)."""
        prompts = _prompts(9, (16, 16, 16, 16))
        base = None
        preempts = {}
        for tp in (1, 2):
            before = _counter_total(
                "failures.preemptions_total", op="serve"
            )
            eng = GenerationEngine(
                lm, max_slots=4, page_size=8, num_pages=12,
                max_seq_len=64, mesh=_mesh(tp),
            )
            out = eng.generate(prompts, 16)
            if base is None:
                base = out
            else:
                for a, b in zip(base, out):
                    np.testing.assert_array_equal(a, b)
            preempts[tp] = (
                _counter_total("failures.preemptions_total", op="serve")
                - before
            )
        # TP=1: 4 slots × 4 pages full-length vs 12 pages — must preempt.
        # TP=2: 24 aggregate pages hold all four sequences outright.
        assert preempts[1] > 0, "workload was meant to exhaust TP=1"
        assert preempts[2] == 0, (
            f"TP=2 still preempted {preempts[2]} time(s) with the "
            f"doubled pool"
        )

    def test_tuned_geometry_scales_per_chip_under_tp(self, lm,
                                                     tmp_path,
                                                     monkeypatch):
        """A tuned serve.page_slots budget is a PER-CHIP quantity like
        an explicit num_pages: the defaulted pool scales it by the TP
        degree (floored at one full-length request)."""
        from tensorframes_tpu import tune
        from tensorframes_tpu.utils import get_config, set_config

        monkeypatch.setenv("TFT_TUNE_FILE", str(tmp_path / "t.jsonl"))
        monkeypatch.delenv("TFT_TUNE", raising=False)
        prev = (get_config().autotune, get_config().tune_mode)
        tune.reset()
        try:
            set_config(autotune=True, tune_mode="cached")
            sig = tune.serve_signature(np.float32, 4, 64)
            tune.pin(
                "serve.page_slots", sig,
                {"slots": 4, "pages_per_slot": 3},
            )
            e1 = GenerationEngine(lm, max_seq_len=64, page_size=8)
            e2 = GenerationEngine(
                lm, max_seq_len=64, page_size=8, mesh=_mesh(2)
            )
            assert e1.pool.num_pages == max(e1._max_pages, 4 * 3)
            assert e2.pool.num_pages == max(e2._max_pages, 4 * 3 * 2)
        finally:
            set_config(autotune=prev[0], tune_mode=prev[1])
            tune.reset()

    def test_replica_kwargs_reserved_keys_rejected(self, lm):
        with pytest.raises(ValueError, match="fleet-owned"):
            Fleet(
                lm, replicas=2,
                replica_kwargs=[{"name": "primary"}, {}],
            )

    def test_collective_estimate_and_metric(self, lm):
        before = _counter_total("serve.collective_seconds")
        eng = GenerationEngine(
            lm, max_slots=2, page_size=8, max_seq_len=64, mesh=_mesh(2)
        )
        assert eng._collective_step_s > 0.0
        assert eng._collective_bytes_per_step > 0
        eng.generate([_prompts(1, (6,))[0]], 4)
        assert _counter_total("serve.collective_seconds") > before
        assert (
            eng.health()["tp"]["collective_seconds_per_step_est"] > 0.0
        )


# ---------------------------------------------------------------------------
# fused ragged kernel under the mesh
# ---------------------------------------------------------------------------


class TestFusedUnderTP:
    def test_fused_read_matches_solo(self, lm):
        """The ragged paged-attention kernel (interpret mode on CPU) is
        head-batched, so its local-head walk shards like the gather:
        streams match the solo engine byte-for-byte."""
        prompts = _prompts(7, (11, 19))
        solo = GenerationEngine(
            lm, max_slots=2, page_size=8, max_seq_len=64,
        )
        base = solo.generate(prompts, 8)
        eng = GenerationEngine(
            lm, max_slots=2, page_size=8, max_seq_len=64, mesh=_mesh(2),
        )
        for a, b in zip(base, eng.generate(prompts, 8)):
            np.testing.assert_array_equal(a, b)

    def test_gather_oracle_under_tp_matches_solo(self, lm, gather_read):
        """The gather oracle through the engine's hook shards the same
        way: the tp=2 engine's streams are the solo kernel engine's."""
        prompts = _prompts(9, (5, 23))
        base = GenerationEngine(
            lm, max_slots=2, page_size=8, max_seq_len=64,
        ).generate(prompts, 8)
        with gather_read():
            eng = GenerationEngine(
                lm, max_slots=2, page_size=8, max_seq_len=64,
                mesh=_mesh(2),
            )
            got = eng.generate(prompts, 8)
        for a, b in zip(base, got):
            np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# heterogeneous-TP fleet: chaos kill + failover across degrees
# ---------------------------------------------------------------------------


class TestHeteroFleet:
    def test_failover_across_tp_degrees_mid_stream(self, lm):
        """A TP=2 replica dies mid-stream (chaos kill: fence + injected
        fault + pool scramble); the survivor replays onto the TP=1
        replica and the client stream stays byte-identical to solo —
        greedy and seeded — with ≤ 3 programs per replica."""
        prompt = _prompts(13, (9,))[0]
        solo = GenerationEngine(lm, max_slots=4, page_size=8,
                                max_seq_len=64)
        for temp, seed in ((0.0, 0), (0.6, 5)):
            base = solo.generate([prompt], 24, temperature=temp,
                                 seed=seed)[0]
            fleet = Fleet(
                lm, replicas=2, max_slots=4, page_size=8, max_seq_len=64,
                watchdog_interval_s=0.01,
                replica_kwargs=[{"mesh": make_mesh({"tp": 2})}, {}],
            )
            with fleet:
                assert [
                    r.engine.tp_degree for r in fleet._replicas
                ] == [2, 1]
                h = fleet.submit(prompt, 24, temperature=temp, seed=seed,
                                 session="s")
                got = []
                it = iter(h)
                for _ in range(4):
                    got.append(next(it))
                fleet._kill_replica(
                    fleet._replica("r0"), RuntimeError("chaos kill")
                )
                for tok in it:
                    got.append(tok)
                assert all(
                    n <= 3 for n in fleet.program_counts().values()
                )
            np.testing.assert_array_equal(np.asarray(got, np.int32), base)
        health = fleet.health()
        assert health["replicas"]["r0"]["tp_degree"] == 2
        assert health["replicas"]["r1"]["tp_degree"] == 1

    def test_chunked_prefill_prefix_cache_failover_combo(self, lm):
        """The full satellite matrix in one drill: chunked prefill +
        prefix-cache hits + a chaos kill mid-stream, failing over FROM
        TP=1 ONTO TP=4."""
        sys_prefix = _prompts(21, (16,))[0]
        prompt = sys_prefix + _prompts(22, (7,))[0]
        kw = dict(
            max_slots=4, page_size=8, max_seq_len=64,
            prefill_chunk_tokens=8, prefix_cache=True,
        )
        solo = GenerationEngine(lm, **kw)
        solo.generate([sys_prefix], 2)  # register the shared prefix
        base = solo.generate([prompt], 20, temperature=0.7, seed=9)[0]
        fleet = Fleet(
            lm, replicas=2, watchdog_interval_s=0.01,
            replica_kwargs=[{}, {"mesh": make_mesh({"tp": 4})}], **kw
        )
        with fleet:
            # warm both replicas' prefix caches so the replay path hits
            for eng in fleet.engines:
                eng.submit(sys_prefix, 2, block=False)
            deadline = time.monotonic() + 30
            while (
                any(e.scheduler.has_work() for e in fleet.engines)
                and time.monotonic() < deadline
            ):
                time.sleep(0.01)
            h = fleet.submit(prompt, 20, temperature=0.7, seed=9,
                             session="u")
            got = []
            it = iter(h)
            for _ in range(3):
                got.append(next(it))
            victim = next(
                r.name for r in fleet._replicas
                if r.engine.scheduler.has_work()
            )
            fleet._kill_replica(
                fleet._replica(victim), RuntimeError("chaos kill")
            )
            for tok in it:
                got.append(tok)
        np.testing.assert_array_equal(np.asarray(got, np.int32), base)


# ---------------------------------------------------------------------------
# healthz / statusz surfaces
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


class TestOperatorSurfaces:
    def test_healthz_and_statusz_report_tp(self, lm):
        from tensorframes_tpu.interop.serving import ScoringServer

        eng = GenerationEngine(
            lm, max_slots=2, page_size=8, max_seq_len=64, mesh=_mesh(2)
        )
        with ScoringServer(engine=eng) as addr:
            resp = _http(addr, b"GET /healthz HTTP/1.1\r\n\r\n")
            body = json.loads(resp.split(b"\r\n\r\n", 1)[1])
            assert body["tp_degree"] == 2
            tp = body["tp"]
            assert tp["degree"] == 2 and tp["axis"] == "tp"
            assert tp["pages_capacity"] == eng.pool.num_pages
            assert tp["kv_bytes_per_shard"] > 0
            assert "pages_in_use_per_shard" in tp
            resp = _http(addr, b"GET /statusz HTTP/1.1\r\n\r\n")
            sbody = json.loads(resp.split(b"\r\n\r\n", 1)[1])
            assert sbody["serving"]["tp_degree"] == 2
            assert sbody["serving"]["tp"]["degree"] == 2

    def test_statusz_serving_for_fleet_lists_replicas(self, lm):
        from tensorframes_tpu.interop.serving import ScoringServer

        fleet = Fleet(
            lm, replicas=2, max_slots=2, page_size=8, max_seq_len=64,
            replica_kwargs=[{"mesh": make_mesh({"tp": 2})}, {}],
        )
        with ScoringServer(engine=fleet) as addr:
            resp = _http(addr, b"GET /statusz HTTP/1.1\r\n\r\n")
            body = json.loads(resp.split(b"\r\n\r\n", 1)[1])
            reps = body["serving"]["replicas"]
            assert reps["r0"]["tp_degree"] == 2
            assert reps["r1"]["tp_degree"] == 1
