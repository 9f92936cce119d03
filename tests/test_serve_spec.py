"""Speculative decoding (ISSUE 15): draft-model step programs, the
batched multi-token verify, and acceptance-tuned draft length.

The correctness bar, inherited from every serve feature: speculative
streams must be BYTE-IDENTICAL to solo non-speculative decode — greedy
AND seeded — because acceptance is exact-match against the target's own
sampled token (per-step keys folded at absolute positions). The matrix
here drives that through chunked prefill, prefix-cache hits,
preemption, defragment, restart, chaos at ``serve.verify``, and fleet
failover across replicas with DIFFERENT draft lengths. Program budget:
<= 5 compiled step programs with speculation on (draft + verify added,
plain decode retired), <= 3 off.
"""

import numpy as np
import pytest

from tensorframes_tpu.models import TransformerLM, init_draft_transformer
from tensorframes_tpu.obs import metrics as obs_metrics
from tensorframes_tpu.serve import Fleet, GenerationEngine, PagePool
from tensorframes_tpu.utils import chaos, get_config, set_config

pytestmark = [pytest.mark.serve, pytest.mark.spec]

VOCAB = 32


@pytest.fixture
def fast_retries():
    old = (get_config().max_retries, get_config().retry_backoff_s)
    set_config(max_retries=2, retry_backoff_s=0.001)
    yield
    set_config(max_retries=old[0], retry_backoff_s=old[1])


@pytest.fixture(scope="module")
def lm():
    return TransformerLM.init(0, VOCAB, d_model=16, n_heads=4, max_len=64)


@pytest.fixture(scope="module")
def draft(lm):
    # a real (mismatched) draft: half the layers, its own seed — wrong
    # often enough to exercise rejection + rollback on every run
    return init_draft_transformer(lm.params, seed=99, n_layers=1)


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


# ---------------------------------------------------------------------------
# the byte-identity matrix
# ---------------------------------------------------------------------------


class TestByteIdentity:
    def test_self_draft_and_cold_draft_match_solo(self, lm, draft):
        """Streams match solo decode bit-for-bit whether the draft is
        perfect (the target's own weights — acceptance 1.0) or cold (a
        fresh 1-layer model — heavy rejection), greedy and seeded."""
        prompts = _prompts(0, (5, 12, 23, 9))
        solo = GenerationEngine(lm, max_slots=4, page_size=8,
                                max_seq_len=64)
        base_g = solo.generate(prompts, 12)
        base_s = solo.generate(prompts, 12, temperature=0.8, seed=11,
                               top_p=0.9)
        for dp, label in ((lm.params, "self"), (draft, "cold")):
            eng = GenerationEngine(
                lm, max_slots=4, page_size=8, max_seq_len=64,
                draft_params=dp, draft_len=3,
            )
            got_g = eng.generate(prompts, 12)
            got_s = eng.generate(prompts, 12, temperature=0.8, seed=11,
                                 top_p=0.9)
            for a, b in zip(base_g, got_g):
                np.testing.assert_array_equal(a, b, err_msg=label)
            for a, b in zip(base_s, got_s):
                np.testing.assert_array_equal(a, b, err_msg=label)
            assert eng.num_step_programs <= 5
            spec = eng.health()["speculative"]
            assert spec["proposed"] > 0
            if label == "self":
                # a perfect draft accepts everything
                assert spec["acceptance_rate"] == 1.0
            else:
                assert spec["accepted"] < spec["proposed"]

    def test_every_k_matches_and_matches_the_models_oracle(self, lm,
                                                           draft):
        prompt = _prompts(3, (14,))[0]
        oracle = lm.generate(np.asarray([prompt], np.int32), 10)[0, 14:]
        for k in (1, 2, 4, 8):
            eng = GenerationEngine(
                lm, max_slots=2, page_size=8, max_seq_len=64,
                draft_params=draft, draft_len=k,
            )
            np.testing.assert_array_equal(
                eng.generate([prompt], 10)[0], oracle
            )

    def test_chunked_prefill_and_prefix_cache_combo(self, lm, draft):
        """Speculation composes with chunked prefill + shared-prefix
        hits (the draft KV rides the shared pages): second pass hits
        the cache, both passes byte-identical to solo."""
        kw = dict(
            max_slots=4, page_size=8, max_seq_len=64,
            prefill_chunk_tokens=8, prefix_cache=True,
        )
        prompts = _prompts(5, (21, 17))
        solo = GenerationEngine(lm, **kw)
        base = solo.generate(prompts, 10, temperature=0.6, seed=7)
        eng = GenerationEngine(lm, draft_params=draft, draft_len=3, **kw)
        first = eng.generate(prompts, 10, temperature=0.6, seed=7)
        cached = eng.generate(prompts, 10, temperature=0.6, seed=7)
        assert eng.prefix_cache.stats()["hits"] > 0
        for a, b in zip(base, first):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(base, cached):
            np.testing.assert_array_equal(a, b)
        assert eng.num_step_programs <= 5

    def test_preempt_defrag_restart_stay_identical(self, lm, draft):
        """A pool tight enough to force preemption, an explicit
        defragment, and a restart — speculative streams still match
        solo (speculative lookahead degrades k, never evicts live
        work)."""
        prompts = _prompts(9, (16, 16, 16, 16))
        solo = GenerationEngine(lm, max_slots=4, page_size=8,
                                max_seq_len=64)
        base = solo.generate(prompts, 16)
        before = _counter_total("failures.preemptions_total", op="serve")
        eng = GenerationEngine(
            lm, max_slots=4, page_size=8, num_pages=12, max_seq_len=64,
            draft_params=draft, draft_len=2,
        )
        out = eng.generate(prompts, 16)
        assert (
            _counter_total("failures.preemptions_total", op="serve")
            > before
        ), "workload was meant to exhaust the pool"
        for a, b in zip(base, out):
            np.testing.assert_array_equal(a, b)
        eng.defragment()
        for a, b in zip(base, eng.generate(prompts, 16)):
            np.testing.assert_array_equal(a, b)
        eng.restart()
        for a, b in zip(base, eng.generate(prompts, 16)):
            np.testing.assert_array_equal(a, b)

    def test_eos_mid_burst_truncates_identically(self, lm):
        """An EOS accepted mid-burst finishes the stream at the same
        byte solo would — nothing past the EOS is emitted."""
        prompt = _prompts(13, (9,))[0]
        solo = GenerationEngine(lm, max_slots=2, page_size=8,
                                max_seq_len=64)
        ref = solo.generate([prompt], 12)[0]
        eos = int(ref[3])  # force an early stop on a token we know lands
        base = solo.generate([prompt], 12, eos_id=eos)
        eng = GenerationEngine(
            lm, max_slots=2, page_size=8, max_seq_len=64,
            draft_params=lm.params, draft_len=4,
        )
        got = eng.generate([prompt], 12, eos_id=eos)
        np.testing.assert_array_equal(base[0], got[0])
        assert len(got[0]) <= 4


# ---------------------------------------------------------------------------
# mechanism: multi-token steps, adaptive k, timings, page group
# ---------------------------------------------------------------------------


class TestMechanism:
    def test_self_draft_advances_multiple_tokens_per_step(self, lm):
        """With a perfect draft, each engine step emits up to k+1
        tokens: far fewer steps than tokens."""
        prompt = _prompts(1, (6,))[0]
        eng = GenerationEngine(
            lm, max_slots=1, page_size=8, max_seq_len=64,
            draft_params=lm.params, draft_len=4,
        )
        h = eng.submit(prompt, 20)
        steps = 0
        while eng.step():
            steps += 1
        toks = h.result(timeout=60)
        assert len(toks) == 20
        # prefill step + ceil(19 / 5) verify steps ~= 5; decode would
        # need 20
        assert steps <= 8
        spec = eng.health()["speculative"]
        assert spec["acceptance_rate"] == 1.0
        t = h.timings
        assert t["draft_s"] > 0 and t["verify_s"] > 0
        assert t["spec_accepted"] == t["spec_proposed"] > 0
        assert t["spec_rolled_back"] == 0

    def test_adaptive_k_shrinks_on_cold_slots(self, lm, draft):
        """A cold draft's per-slot k walks down toward the floor (1);
        rolled-back proposals land in the timings breakdown."""
        prompt = _prompts(2, (8,))[0]
        eng = GenerationEngine(
            lm, max_slots=1, page_size=8, max_seq_len=64,
            draft_params=draft, draft_len=6,
        )
        h = eng.submit(prompt, 24)
        seen_k = []
        while eng.step():
            act = eng.scheduler.slots[0]
            if act is not None and act.spec_k >= 0:
                seen_k.append(act.spec_k)
        h.result(timeout=60)
        assert seen_k and min(seen_k) < 6, (
            f"cold draft never shrank k: {seen_k}"
        )
        assert h.timings.get("spec_rolled_back", 0) > 0
        assert h.timings.get("rollback_s", 0.0) >= 0.0

    def test_metrics_and_health_surface(self, lm, draft):
        before_p = _counter_total("serve.spec_proposed_total")
        before_a = _counter_total("serve.spec_accepted_total")
        eng = GenerationEngine(
            lm, max_slots=2, page_size=8, max_seq_len=64,
            draft_params=draft, draft_len=2,
        )
        eng.generate(_prompts(4, (7, 11)), 8)
        assert _counter_total("serve.spec_proposed_total") > before_p
        assert _counter_total("serve.spec_accepted_total") >= before_a
        hist = obs_metrics.registry().get("serve.verify_seconds")
        assert hist.series()["count"] > 0
        spec = eng.health()["speculative"]
        assert spec["draft_len"] == 2
        assert 0.0 <= spec["acceptance_rate"] <= 1.0
        plain = GenerationEngine(lm, max_slots=1, page_size=8,
                                 max_seq_len=64)
        assert plain.health()["speculative"] is None

    def test_page_group_defrag_and_reset(self):
        """kv_pages satellite: a group's rows move with the pool's
        defragment permutation and re-zero on reset."""
        import jax.numpy as jnp

        from tensorframes_tpu.serve import SequencePages

        pool = PagePool(
            n_layers=1, n_kv_heads=1, head_dim=2, num_pages=6,
            page_size=4,
        )
        g = pool.add_group("draft", n_layers=2, n_kv_heads=1, head_dim=3)
        assert g.k.shape == (2, 7, 4, 1 * 3)
        with pytest.raises(ValueError, match="already exists"):
            pool.add_group("draft", 1, 1, 1)
        seq = SequencePages(pool)
        seq.ensure(12)  # pages 0..2
        other = SequencePages(pool)
        other.ensure(4)
        # color the group rows by page index, then free the first seq
        # so defragment must move the survivor's page
        color = jnp.broadcast_to(
            jnp.arange(7, dtype=jnp.float32)[None, :, None, None, None],
            (2, 7, 4, 1, 3),
        )
        g.put_pages(np.arange(7), color, color)
        held = other.pages[0]
        seq.release()
        remap = pool.defragment([other])
        assert other.pages[0] == remap[held]
        # the group row followed its page: contents still the ORIGINAL
        # page's color
        np.testing.assert_allclose(
            np.asarray(g.take_pages([other.pages[0]])[0]), float(held)
        )
        pool.reset()
        np.testing.assert_allclose(np.asarray(g.k), 0.0)

    def test_draft_model_validation(self, lm):
        wrong_vocab = TransformerLM.init(0, VOCAB + 1, d_model=16,
                                         n_heads=4, max_len=64)
        with pytest.raises(ValueError, match="vocab"):
            GenerationEngine(lm, max_seq_len=64,
                             draft_params=wrong_vocab)
        short_pos = TransformerLM.init(0, VOCAB, d_model=16, n_heads=4,
                                       max_len=16)
        with pytest.raises(ValueError, match="positional"):
            GenerationEngine(lm, max_seq_len=64, draft_params=short_pos)
        with pytest.raises(ValueError, match="draft_len"):
            GenerationEngine(lm, max_seq_len=64, draft_params=lm.params,
                             draft_len=0)


# ---------------------------------------------------------------------------
# chaos at serve.verify + fleet failover across different k
# ---------------------------------------------------------------------------


class TestFaults:
    def test_transient_verify_chaos_retries_invisibly(self, lm, draft,
                                                      fast_retries):
        solo = GenerationEngine(lm, max_slots=2, page_size=8,
                                max_seq_len=64)
        prompts = _prompts(6, (9, 13))
        base = solo.generate(prompts, 10, temperature=0.5, seed=3)
        before = _counter_total(
            "chaos.injections_total", site="serve.verify",
            kind="transient",
        )
        with chaos.scoped("seed=7;serve.verify=transient:every=3"):
            eng = GenerationEngine(
                lm, max_slots=2, page_size=8, max_seq_len=64,
                draft_params=draft, draft_len=2,
            )
            got = eng.generate(prompts, 10, temperature=0.5, seed=3)
        assert (
            _counter_total(
                "chaos.injections_total", site="serve.verify",
                kind="transient",
            )
            > before
        ), "the schedule never fired"
        for a, b in zip(base, got):
            np.testing.assert_array_equal(a, b)

    def test_failover_across_different_k_mid_stream(self, lm, draft):
        """A speculative replica dies mid-stream; the survivor replays
        onto a replica with a DIFFERENT k (and a different draft) and
        the client stream stays byte-identical to solo."""
        import time

        prompt = _prompts(13, (9,))[0]
        solo = GenerationEngine(lm, max_slots=4, page_size=8,
                                max_seq_len=64)
        base = solo.generate([prompt], 24, temperature=0.6, seed=5)[0]
        fleet = Fleet(
            lm, replicas=2, max_slots=4, page_size=8, max_seq_len=64,
            watchdog_interval_s=0.01,
            replica_kwargs=[
                {"draft_params": lm.params, "draft_len": 4},
                {"draft_params": draft, "draft_len": 2},
            ],
        )
        with fleet:
            h = fleet.submit(prompt, 24, temperature=0.6, seed=5,
                             session="s")
            got = []
            it = iter(h)
            for _ in range(4):
                got.append(next(it))
            fleet._kill_replica(
                fleet._replica("r0"), RuntimeError("chaos kill")
            )
            deadline = time.monotonic() + 60
            for tok in it:
                got.append(tok)
                assert time.monotonic() < deadline
            assert all(
                n <= 5 for n in fleet.program_counts().values()
            )
        np.testing.assert_array_equal(np.asarray(got, np.int32), base)


# ---------------------------------------------------------------------------
# tuned draft length
# ---------------------------------------------------------------------------


class TestTunedDraftLen:
    def test_engine_picks_up_stored_draft_len(self, lm, tmp_path,
                                              monkeypatch):
        from tensorframes_tpu import tune
        from tensorframes_tpu.utils import get_config, set_config

        monkeypatch.setenv("TFT_TUNE_FILE", str(tmp_path / "t.jsonl"))
        monkeypatch.delenv("TFT_TUNE", raising=False)
        prev = (get_config().autotune, get_config().tune_mode)
        tune.reset()
        try:
            set_config(autotune=True, tune_mode="cached")
            sig = tune.serve_signature(np.float32, 4, 64)
            tune.pin("serve.draft_len", sig, {"k": 2})
            eng = GenerationEngine(
                lm, max_seq_len=64, page_size=8,
                draft_params=lm.params,
            )
            assert eng.draft_len == 2
            # an explicit argument always wins
            eng2 = GenerationEngine(
                lm, max_seq_len=64, page_size=8,
                draft_params=lm.params, draft_len=5,
            )
            assert eng2.draft_len == 5
        finally:
            set_config(autotune=prev[0], tune_mode=prev[1])
            tune.reset()
