"""Transformer LM tests: forward parity across attention impls, training,
and frame scoring."""

import numpy as np
import pytest

import jax.numpy as jnp

import tensorframes_tpu as tft
from tensorframes_tpu.models import (
    TransformerLM,
    init_transformer,
    transformer_logits,
    transformer_loss,
)
from tensorframes_tpu.parallel import make_mesh


VOCAB = 50


@pytest.fixture(scope="module")
def params():
    return init_transformer(
        0, VOCAB, d_model=32, n_heads=4, n_layers=2, max_len=64
    )


@pytest.fixture(scope="module")
def tokens():
    rng = np.random.default_rng(0)
    return rng.integers(0, VOCAB, (2, 32)).astype(np.int32)


def test_logits_shape_finite(params, tokens):
    out = np.asarray(transformer_logits(params, tokens))
    assert out.shape == (2, 32, VOCAB)
    assert np.isfinite(out).all()


def test_flash_matches_reference(params, tokens):
    ref = np.asarray(transformer_logits(params, tokens, attn_impl="reference"))
    fl = np.asarray(transformer_logits(params, tokens, attn_impl="flash"))
    np.testing.assert_allclose(fl, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_ring_matches_reference(params, tokens):
    mesh = make_mesh({"sp": 4})
    ref = np.asarray(transformer_logits(params, tokens, attn_impl="reference"))
    rg = np.asarray(
        transformer_logits(params, tokens, attn_impl="ring", mesh=mesh)
    )
    np.testing.assert_allclose(rg, ref, rtol=2e-4, atol=2e-4)


def test_causality(params, tokens):
    # changing future tokens must not affect earlier logits
    t2 = tokens.copy()
    t2[:, 20:] = (t2[:, 20:] + 7) % VOCAB
    a = np.asarray(transformer_logits(params, tokens))
    b = np.asarray(transformer_logits(params, t2))
    np.testing.assert_allclose(a[:, :20], b[:, :20], rtol=1e-5, atol=1e-5)
    assert not np.allclose(a[:, 20:], b[:, 20:])


def test_loss_and_fit(tokens):
    lm = TransformerLM.init(
        0, VOCAB, d_model=32, n_heads=4, n_layers=1, max_len=64
    )
    losses = lm.fit(tokens, steps=8, lr=0.5)
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()


def test_score_frame(params):
    rng = np.random.default_rng(1)
    toks = rng.integers(0, VOCAB, (6, 16)).astype(np.int32)
    df = tft.TensorFrame.from_columns({"tokens": toks}).analyze()
    lm = TransformerLM(params)
    out = lm.score_frame(df, "tokens")
    rows = out.collect()
    assert len(rows) == 6
    assert all(np.isfinite(r.nll) and r.nll > 0 for r in rows)


@pytest.mark.slow
class TestFitShardedDpSp:
    """dp x sp composition in ONE train step: batch-sharded ring attention
    plus GSPMD gradient all-reduce."""

    def test_losses_match_single_device_fit(self):
        from tensorframes_tpu.parallel import make_mesh

        rng = np.random.default_rng(5)
        vocab, L, B = 16, 17, 8  # L-1 = 16 divides sp=4; B divides dp=2
        toks = rng.integers(0, vocab, size=(B, L)).astype(np.int32)

        lm1 = TransformerLM.init(0, vocab, d_model=16, n_heads=4, max_len=L)
        losses_1 = lm1.fit(toks, steps=4, lr=0.2)

        mesh = make_mesh({"dp": 2, "sp": 4})
        lm2 = TransformerLM.init(0, vocab, d_model=16, n_heads=4, max_len=L)
        losses_2 = lm2.fit_sharded(toks, mesh, steps=4, lr=0.2)

        np.testing.assert_allclose(losses_2, losses_1, rtol=1e-4, atol=1e-5)

    def test_bad_shapes_rejected(self):
        from tensorframes_tpu.parallel import make_mesh

        mesh = make_mesh({"dp": 2, "sp": 4})
        lm = TransformerLM.init(0, 16, d_model=16, n_heads=4, max_len=20)
        toks = np.zeros((8, 20), np.int32)  # L-1 = 19 not divisible by 4
        with pytest.raises(ValueError, match="sp"):
            lm.fit_sharded(toks, mesh, steps=1)

    def test_ulysses_losses_match_single_device_fit(self):
        # ulysses trains through the flash kernel's custom VJP: the two
        # all_to_all transposes and the pallas backward compose under
        # jax.grad inside the dp x sp program
        from tensorframes_tpu.parallel import make_mesh

        rng = np.random.default_rng(6)
        vocab, L, B = 16, 17, 8  # L-1 = 16 divides sp=4; H=4 divides sp=4
        toks = rng.integers(0, vocab, size=(B, L)).astype(np.int32)

        lm1 = TransformerLM.init(0, vocab, d_model=16, n_heads=4, max_len=L)
        losses_1 = lm1.fit(toks, steps=4, lr=0.2)

        mesh = make_mesh({"dp": 2, "sp": 4})
        lm2 = TransformerLM.init(0, vocab, d_model=16, n_heads=4, max_len=L)
        losses_2 = lm2.fit_sharded(
            toks, mesh, steps=4, lr=0.2, attn_impl="ulysses"
        )

        np.testing.assert_allclose(losses_2, losses_1, rtol=1e-4, atol=1e-5)

    def test_fit_tp_matches_single_device_fit(self):
        # Megatron GSPMD sharding: qkv/up column-parallel, proj/down
        # row-parallel — same trajectory as the unsharded step
        from tensorframes_tpu.parallel import make_mesh

        rng = np.random.default_rng(3)
        toks = rng.integers(0, 16, size=(4, 12)).astype(np.int32)
        lm1 = TransformerLM.init(0, 16, d_model=16, n_heads=4, max_len=12)
        ref = lm1.fit(toks, steps=4, lr=0.2)
        mesh = make_mesh({"dp": 2, "tp": 4})
        lm2 = TransformerLM.init(0, 16, d_model=16, n_heads=4, max_len=12)
        got = lm2.fit_tp(toks, mesh, steps=4, lr=0.2)
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)

    def test_fit_tp_guards(self):
        from tensorframes_tpu.parallel import make_mesh

        mesh = make_mesh({"dp": 2, "tp": 4})
        toks = np.zeros((4, 12), np.int32)
        lm = TransformerLM.init(0, 16, d_model=18, n_heads=3, max_len=12)
        with pytest.raises(ValueError, match="head boundaries"):
            lm.fit_tp(toks, mesh, steps=1)
        lm2 = TransformerLM.init(0, 16, d_model=16, n_heads=4, max_len=12)
        with pytest.raises(ValueError, match="batch"):
            lm2.fit_tp(np.zeros((3, 12), np.int32), mesh, steps=1)

    def test_single_chip_flash_fit_matches_reference_fit(self):
        # flash's custom VJP on one chip: same training trajectory as the
        # dense reference attention (L=128 divides the kernel's tiles)
        rng = np.random.default_rng(7)
        vocab, L, B = 16, 129, 2
        toks = rng.integers(0, vocab, size=(B, L)).astype(np.int32)

        lm1 = TransformerLM.init(0, vocab, d_model=16, n_heads=4, max_len=L)
        losses_ref = lm1.fit(toks, steps=3, lr=0.2)
        lm2 = TransformerLM.init(0, vocab, d_model=16, n_heads=4, max_len=L)
        losses_flash = lm2.fit(toks, steps=3, lr=0.2, attn_impl="flash")
        np.testing.assert_allclose(
            losses_flash, losses_ref, rtol=1e-4, atol=1e-5
        )

    def test_unsupported_impl_rejected(self):
        from tensorframes_tpu.parallel import make_mesh

        mesh = make_mesh({"dp": 2, "sp": 4})
        lm = TransformerLM.init(0, 16, d_model=16, n_heads=4, max_len=17)
        toks = np.zeros((8, 17), np.int32)
        with pytest.raises(ValueError, match="ring.*ulysses"):
            lm.fit_sharded(toks, mesh, steps=1, attn_impl="reference")


class TestRemat:
    def test_remat_fit_matches_plain_fit(self):
        # jax.checkpoint must be semantics-preserving: identical losses,
        # only the backward's memory/FLOP trade differs
        rng = np.random.default_rng(0)
        toks = rng.integers(0, 16, size=(4, 12)).astype(np.int32)
        lm1 = TransformerLM.init(0, 16, d_model=16, n_heads=4, max_len=12)
        plain = lm1.fit(toks, steps=4, lr=0.2)
        lm2 = TransformerLM.init(0, 16, d_model=16, n_heads=4, max_len=12)
        remat = lm2.fit(toks, steps=4, lr=0.2, remat=True)
        np.testing.assert_allclose(remat, plain, rtol=1e-5, atol=1e-6)

    def test_remat_with_flash_and_moe(self):
        rng = np.random.default_rng(1)
        toks = rng.integers(0, 16, size=(2, 129)).astype(np.int32)
        lm = TransformerLM.init(0, 16, d_model=16, n_heads=4, max_len=129)
        losses = lm.fit(toks, steps=2, lr=0.2, attn_impl="flash", remat=True)
        assert all(np.isfinite(losses))
        toks2 = rng.integers(0, 16, size=(2, 9)).astype(np.int32)
        lm2 = TransformerLM.init(
            0, 16, d_model=16, n_heads=4, max_len=12, moe_experts=4
        )
        l2 = lm2.fit(toks2, steps=2, lr=0.2, remat=True)
        assert all(np.isfinite(l2))


@pytest.mark.slow
class TestGenerate:
    """KV-cached scan decode vs the naive oracle: re-run the full forward
    on the growing sequence and argmax the last position."""

    def _naive_greedy(self, lm, prompt, n_new):
        import jax.numpy as jnp

        from tensorframes_tpu.models import transformer_logits

        toks = np.asarray(prompt, dtype=np.int32)
        for _ in range(n_new):
            logits = transformer_logits(lm.params, jnp.asarray(toks))
            nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
            toks = np.concatenate([toks, nxt[:, None].astype(np.int32)], 1)
        return toks

    def test_greedy_matches_naive_recompute(self):
        rng = np.random.default_rng(0)
        lm = TransformerLM.init(3, 32, d_model=16, n_heads=4, max_len=24)
        prompt = rng.integers(0, 32, size=(2, 5)).astype(np.int32)
        got = lm.generate(prompt, max_new_tokens=8)
        want = self._naive_greedy(lm, prompt, 8)
        np.testing.assert_array_equal(got, want)

    def test_greedy_after_training(self):
        # decode must read the TRAINED params (cache invalidates on fit)
        rng = np.random.default_rng(1)
        lm = TransformerLM.init(0, 16, d_model=16, n_heads=4, max_len=20)
        prompt = rng.integers(0, 16, size=(1, 4)).astype(np.int32)
        before = lm.generate(prompt, max_new_tokens=6)
        toks = rng.integers(0, 16, size=(4, 12)).astype(np.int32)
        lm.fit(toks, steps=3, lr=0.3)
        after = lm.generate(prompt, max_new_tokens=6)
        want = self._naive_greedy(lm, prompt, 6)
        np.testing.assert_array_equal(after, want)
        assert before.shape == after.shape

    def test_sampled_decode_deterministic_per_seed(self):
        rng = np.random.default_rng(2)
        lm = TransformerLM.init(5, 32, d_model=16, n_heads=4, max_len=20)
        prompt = rng.integers(0, 32, size=(2, 4)).astype(np.int32)
        a = lm.generate(prompt, max_new_tokens=8, temperature=1.0, seed=7)
        b = lm.generate(prompt, max_new_tokens=8, temperature=1.0, seed=7)
        np.testing.assert_array_equal(a, b)
        c = lm.generate(prompt, max_new_tokens=8, temperature=1.0, seed=8)
        assert a.shape == c.shape == (2, 12)
        assert (a[:, :4] == prompt).all()

    def test_moe_model_greedy_matches_naive(self):
        rng = np.random.default_rng(3)
        lm = TransformerLM.init(
            1, 24, d_model=16, n_heads=4, max_len=20, moe_experts=4
        )
        prompt = rng.integers(0, 24, size=(2, 4)).astype(np.int32)
        got = lm.generate(prompt, max_new_tokens=6)
        want = self._naive_greedy(lm, prompt, 6)
        np.testing.assert_array_equal(got, want)

    def test_max_len_guard(self):
        lm = TransformerLM.init(0, 16, d_model=16, n_heads=4, max_len=10)
        with pytest.raises(ValueError, match="max_len"):
            lm.generate(np.zeros((1, 6), np.int32), max_new_tokens=8)
        with pytest.raises(ValueError, match="max_new_tokens"):
            lm.generate(np.zeros((1, 6), np.int32), max_new_tokens=0)

    def test_generate_composes_with_map_blocks(self):
        # decode over a FRAME of prompts: generation is just another
        # captured program through the dataframe plane
        import tensorframes_tpu as tft
        from tensorframes_tpu.models import transformer_generate

        rng = np.random.default_rng(5)
        lm = TransformerLM.init(1, 16, d_model=16, n_heads=4, max_len=16)
        prompts = rng.integers(0, 16, size=(6, 4)).astype(np.int32)
        df = tft.TensorFrame.from_columns({"prompt": prompts}).analyze()
        params = lm.params

        def gen_fn(prompt):
            return {"gen": transformer_generate(params, prompt, 5)}

        out = tft.map_blocks(gen_fn, df)
        got = np.asarray(out.cache().column_block("gen"))
        want = lm.generate(prompts, max_new_tokens=5)
        np.testing.assert_array_equal(got, want)

    def test_compiled_programs_reused_across_configs(self):
        # seeds and temperatures are TRACED arguments: a whole sweep runs
        # through one compiled program (the memo keys only structure), and
        # greedy decodes ignore seed entirely (it never enters the program)
        rng = np.random.default_rng(4)
        lm = TransformerLM.init(2, 16, d_model=16, n_heads=4, max_len=20)
        p = rng.integers(0, 16, size=(1, 4)).astype(np.int32)
        for seed in (1, 2, 3):
            lm.generate(p, 4, temperature=1.0, seed=seed)
        lm.generate(p, 4, temperature=0.7, seed=1)
        assert len(lm._generate_cache) == 1  # one program for the sweep
        a = lm.generate(p, 4, seed=1)
        b = lm.generate(p, 4, seed=9)
        np.testing.assert_array_equal(a, b)
        assert len(lm._generate_cache) == 2  # greedy adds ONE entry

    def test_generate_cache_is_bounded(self):
        rng = np.random.default_rng(6)
        lm = TransformerLM.init(2, 16, d_model=16, n_heads=4, max_len=64)
        for plen in range(2, 2 + lm._GENERATE_CACHE_MAX + 4):
            p = rng.integers(0, 16, size=(1, plen)).astype(np.int32)
            lm.generate(p, 2)
        assert len(lm._generate_cache) == lm._GENERATE_CACHE_MAX


@pytest.mark.slow
class TestSamplingFilters:
    """filter_logits (top-k / nucleus) and their wiring into generate."""

    def test_top_k_keeps_k_largest(self):
        from tensorframes_tpu.models import filter_logits

        logits = jnp.asarray([[0.0, 3.0, 1.0, 2.0, -1.0]])
        out = np.asarray(filter_logits(logits, top_k=2))
        kept = out > -1e30
        np.testing.assert_array_equal(kept, [[False, True, False, True, False]])
        np.testing.assert_allclose(out[0, 1], 3.0)

    def test_top_p_keeps_nucleus(self):
        from tensorframes_tpu.models import filter_logits

        # softmax of [2, 1, 0, -1] ~ [.64, .24, .09, .03]: top_p=0.7 keeps
        # the first two (mass before token 2 is .88 >= .7)
        logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
        out = np.asarray(filter_logits(logits, top_p=0.7))
        kept = out > -1e30
        np.testing.assert_array_equal(kept, [[True, True, False, False]])

    def test_tiny_top_p_keeps_argmax_only(self):
        from tensorframes_tpu.models import filter_logits

        logits = jnp.asarray([[0.5, 2.0, 1.0]])
        out = np.asarray(filter_logits(logits, top_p=1e-9))
        kept = out > -1e30
        np.testing.assert_array_equal(kept, [[False, True, False]])

    def test_top_k_1_sampling_equals_greedy(self):
        rng = np.random.default_rng(7)
        lm = TransformerLM.init(4, 24, d_model=16, n_heads=4, max_len=20)
        p = rng.integers(0, 24, size=(2, 4)).astype(np.int32)
        greedy = lm.generate(p, 6)
        k1 = lm.generate(p, 6, temperature=1.0, seed=3, top_k=1)
        np.testing.assert_array_equal(k1, greedy)

    def test_sampled_tokens_stay_within_top_k(self):
        # membership oracle via naive recompute: every sampled token must
        # be among the top-k of the step's true logits
        rng = np.random.default_rng(8)
        lm = TransformerLM.init(5, 24, d_model=16, n_heads=4, max_len=20)
        p = rng.integers(0, 24, size=(1, 3)).astype(np.int32)
        out = lm.generate(p, 5, temperature=1.3, seed=11, top_k=3)
        for t in range(3, out.shape[1]):
            logits = transformer_logits(
                lm.params, jnp.asarray(out[:, :t])
            )[:, -1]
            top3 = np.argsort(np.asarray(logits)[0])[-3:]
            assert out[0, t] in top3, (t, out[0, t], top3)

    def test_top_p_sweep_reuses_one_program(self):
        rng = np.random.default_rng(9)
        lm = TransformerLM.init(6, 16, d_model=16, n_heads=4, max_len=20)
        p = rng.integers(0, 16, size=(1, 4)).astype(np.int32)
        for tp in (0.5, 0.8, 0.95):
            lm.generate(p, 4, temperature=1.0, seed=1, top_p=tp)
        assert len(lm._generate_cache) == 1


class TestFilterLogitsEdgeCases:
    """filter_logits edge cases that matter to serving: deterministic
    top_k=1, tie-breaking exactly at the nucleus boundary, and the
    traced-scalar top_p contract under jit. Fast (pure functions + one
    tiny decode) so tier-1 keeps covering them."""

    def test_top_k_1_filter_keeps_argmax_only(self):
        from tensorframes_tpu.models import filter_logits

        logits = jnp.asarray([[0.5, 2.0, 1.0], [3.0, -1.0, 2.5]])
        out = np.asarray(filter_logits(logits, top_k=1))
        kept = out > -1e30
        np.testing.assert_array_equal(
            kept, [[False, True, False], [True, False, False]]
        )

    def test_top_k_1_sampling_equals_greedy_generate(self):
        # tiny end-to-end confirmation: with only the argmax surviving,
        # ANY temperature samples the greedy stream
        rng = np.random.default_rng(21)
        lm = TransformerLM.init(3, 16, d_model=8, n_heads=2, max_len=12)
        p = rng.integers(0, 16, size=(1, 3)).astype(np.int32)
        np.testing.assert_array_equal(
            lm.generate(p, 4, temperature=2.0, seed=5, top_k=1),
            lm.generate(p, 4),
        )

    def test_top_p_ties_at_nucleus_boundary_all_survive(self):
        from tensorframes_tpu.models import filter_logits

        # two EXACTLY tied logits, each with softmax mass 0.5 - eps: the
        # nucleus needs only the first, but masking is threshold-based
        # (logits < thresh), so its equal twin must survive too — a
        # sampled tie must never depend on sort order
        logits = jnp.asarray([[0.0, 0.0, -40.0]])
        out = np.asarray(filter_logits(logits, top_p=0.5))
        kept = out > -1e30
        np.testing.assert_array_equal(kept, [[True, True, False]])

    def test_top_p_boundary_mass_counts_strictly_before(self):
        from tensorframes_tpu.models import filter_logits

        # masses ~[.665, .245, .090]: top_p=0.7 keeps token 1 (mass
        # BEFORE it is .665 < .7) but drops token 2 (mass before .910)
        logits = jnp.asarray([[2.0, 1.0, 0.0]])
        out = np.asarray(filter_logits(logits, top_p=0.7))
        kept = out > -1e30
        np.testing.assert_array_equal(kept, [[True, True, False]])

    def test_traced_scalar_top_p_inside_jit(self):
        import jax

        from tensorframes_tpu.models import filter_logits

        calls = {"n": 0}

        def impl(logits, top_p):
            calls["n"] += 1
            return filter_logits(logits, top_p=top_p)

        f = jax.jit(impl)
        logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
        for tp, want_kept in ((0.7, 2), (0.95, 3), (1.0, 4)):
            out = np.asarray(f(logits, jnp.float32(tp)))
            assert (out > -1e30).sum() == want_kept, tp
            np.testing.assert_array_equal(
                out, np.asarray(filter_logits(logits, top_p=tp))
            )
        assert calls["n"] == 1  # one trace serves the whole sweep


class TestRaggedAgreementFast:
    """left_pad_prompts + prompt_lengths: a ragged batch must reproduce
    each row's solo decode token-for-token at temperature 0 (the fast
    tier-1 sibling of the slow TestRaggedPrompts suite)."""

    def test_left_pad_layout_agrees_with_lengths(self):
        from tensorframes_tpu.models import left_pad_prompts

        seqs = [[4], [1, 2, 3, 4], [9, 8]]
        packed, lens = left_pad_prompts(seqs, pad_id=7)
        np.testing.assert_array_equal(lens, [1, 4, 2])
        for row, s, n in zip(packed, seqs, lens):
            assert n == len(s)
            np.testing.assert_array_equal(row[len(row) - n :], s)
            assert all(row[: len(row) - n] == 7)

    def test_ragged_batch_matches_per_row_solo_decode(self):
        from tensorframes_tpu.models import left_pad_prompts

        rng = np.random.default_rng(22)
        lm = TransformerLM.init(9, 16, d_model=8, n_heads=2, max_len=16)
        seqs = [
            rng.integers(0, 16, size=n).astype(np.int32).tolist()
            for n in (1, 4, 2)
        ]
        packed, lens = left_pad_prompts(seqs)
        batch = lm.generate(packed, 4, prompt_lengths=lens)
        plen = packed.shape[1]
        for i, s in enumerate(seqs):
            solo = lm.generate(np.asarray([s], np.int32), 4)
            np.testing.assert_array_equal(
                batch[i, plen:], solo[0, len(s):],
                err_msg=f"row {i} (len {len(s)})",
            )


@pytest.mark.slow
class TestRaggedPrompts:
    """Left-padded variable-length prompt batches: each row must decode
    exactly as it would alone."""

    def test_left_pad_prompts_layout(self):
        from tensorframes_tpu.models import left_pad_prompts

        packed, lens = left_pad_prompts([[5], [1, 2, 3], [7, 8]], pad_id=0)
        np.testing.assert_array_equal(
            packed, [[0, 0, 5], [1, 2, 3], [0, 7, 8]]
        )
        np.testing.assert_array_equal(lens, [1, 3, 2])

    def test_ragged_greedy_matches_per_row_decode(self):
        from tensorframes_tpu.models import left_pad_prompts

        rng = np.random.default_rng(10)
        lm = TransformerLM.init(7, 24, d_model=16, n_heads=4, max_len=24)
        seqs = [
            rng.integers(0, 24, size=n).astype(np.int32).tolist()
            for n in (2, 4, 3)
        ]
        packed, lens = left_pad_prompts(seqs)
        batch = lm.generate(packed, 5, prompt_lengths=lens)
        p = packed.shape[1]
        for i, s in enumerate(seqs):
            alone = lm.generate(
                np.asarray([s], dtype=np.int32), 5
            )
            np.testing.assert_array_equal(
                batch[i, p:], alone[0, len(s):],
                err_msg=f"row {i} (len {len(s)})",
            )

    def test_ragged_equal_lengths_match_plain_path(self):
        rng = np.random.default_rng(11)
        lm = TransformerLM.init(8, 16, d_model=16, n_heads=4, max_len=20)
        p = rng.integers(0, 16, size=(3, 4)).astype(np.int32)
        plain = lm.generate(p, 5)
        ragged = lm.generate(
            p, 5, prompt_lengths=np.full(3, 4, np.int32)
        )
        np.testing.assert_array_equal(ragged, plain)


@pytest.mark.slow
class TestMoETransformer:
    """Transformer blocks with a routed MoE MLP (moe_experts=...)."""

    def test_moe_blocks_forward_and_fit(self):
        rng = np.random.default_rng(3)
        lm = TransformerLM.init(
            0, vocab=16, d_model=16, n_heads=4, max_len=16, moe_experts=4
        )
        toks = rng.integers(0, 16, size=(4, 16)).astype(np.int32)
        logits = np.asarray(transformer_logits(lm.params, toks))
        assert logits.shape == (4, 16, 16) and np.isfinite(logits).all()
        losses = lm.fit(toks, steps=6, lr=0.2)
        assert losses[-1] < losses[0]

    def test_ep_sharded_matches_local(self):
        from tensorframes_tpu.parallel import make_mesh

        rng = np.random.default_rng(4)
        params = TransformerLM.init(
            0, vocab=16, d_model=16, n_heads=4, max_len=16, moe_experts=8
        ).params
        toks = rng.integers(0, 16, size=(2, 16)).astype(np.int32)
        local = transformer_logits(params, toks)
        mesh = make_mesh({"ep": 8})
        sharded = transformer_logits(params, toks, mesh=mesh)
        np.testing.assert_allclose(
            np.asarray(sharded), np.asarray(local), rtol=2e-4, atol=2e-4
        )

    def test_moe_aux_loss_wired_into_training(self):
        rng = np.random.default_rng(6)
        lm = TransformerLM.init(
            0, vocab=16, d_model=16, n_heads=4, max_len=16, moe_experts=4
        )
        toks = rng.integers(0, 16, size=(4, 16)).astype(np.int32)
        losses = lm._sgd_loop(
            toks, steps=4, lr=0.2, loss_kwargs=dict(moe_aux_weight=1e-2)
        )
        assert np.isfinite(losses).all() and losses[-1] < losses[0]

    def test_aux_collection_returns_pair(self):
        from tensorframes_tpu.models.transformer import transformer_logits

        lm = TransformerLM.init(
            0, vocab=16, d_model=16, n_heads=4, max_len=16, moe_experts=4
        )
        toks = np.zeros((2, 16), np.int32)
        logits, aux = transformer_logits(
            lm.params, toks, collect_moe_aux=True
        )
        assert np.asarray(logits).shape == (2, 16, 16)
        assert float(aux) > 0


@pytest.mark.slow
class TestGQA:
    """Grouped-query attention: n_kv_heads k/v heads shared by
    n_heads/n_kv_heads query heads each. Exact oracle: an MHA model whose
    k/v projection columns are the GQA weights repeated per group
    computes identical attention."""

    def _mha_twin(self, params, n_heads, n_kv):
        import copy

        d = params["embed"].shape[1]
        hd = d // n_heads
        g = n_heads // n_kv
        twin = copy.deepcopy(params)
        for block in twin["blocks"]:
            w = np.asarray(block["qkv"])
            wq, wk, wv = w[:, :d], w[:, d:d + n_kv * hd], w[:, d + n_kv * hd:]
            rep = lambda m: np.repeat(
                m.reshape(d, n_kv, hd), g, axis=1
            ).reshape(d, d)
            block["qkv"] = np.concatenate([wq, rep(wk), rep(wv)], axis=1)
        return twin

    def test_logits_match_repeated_weight_mha(self):
        rng = np.random.default_rng(0)
        lm = TransformerLM.init(
            1, 32, d_model=32, n_heads=8, n_layers=2, max_len=16,
            n_kv_heads=2,
        )
        toks = rng.integers(0, 32, size=(3, 12)).astype(np.int32)
        got = transformer_logits(lm.params, toks)
        twin = self._mha_twin(lm.params, 8, 2)
        want = transformer_logits(twin, toks)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_mqa_single_kv_head(self):
        rng = np.random.default_rng(1)
        lm = TransformerLM.init(
            2, 16, d_model=16, n_heads=4, max_len=16, n_kv_heads=1
        )
        toks = rng.integers(0, 16, size=(2, 8)).astype(np.int32)
        got = transformer_logits(lm.params, toks)
        want = transformer_logits(self._mha_twin(lm.params, 4, 1), toks)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_generate_matches_naive_recompute(self):
        # the GQA decode path (n_kv-head cache, grouped einsums) must
        # agree with the full forward on the growing sequence
        rng = np.random.default_rng(2)
        lm = TransformerLM.init(
            3, 24, d_model=32, n_heads=8, n_layers=2, max_len=20,
            n_kv_heads=2,
        )
        prompt = rng.integers(0, 24, size=(2, 4)).astype(np.int32)
        got = lm.generate(prompt, max_new_tokens=8)
        toks = prompt
        for _ in range(8):
            logits = transformer_logits(lm.params, jnp.asarray(toks))
            nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
            toks = np.concatenate([toks, nxt[:, None].astype(np.int32)], 1)
        np.testing.assert_array_equal(got, toks)

    def test_gqa_trains(self):
        rng = np.random.default_rng(3)
        lm = TransformerLM.init(
            4, 16, d_model=16, n_heads=4, max_len=12, n_kv_heads=2
        )
        toks = rng.integers(0, 16, size=(4, 10)).astype(np.int32)
        losses = lm.fit(toks, steps=4, lr=0.3)
        assert all(np.isfinite(losses)) and losses[-1] < losses[0]

    def test_qkv_weight_shrinks(self):
        lm = TransformerLM.init(
            0, 16, d_model=32, n_heads=8, max_len=8, n_kv_heads=2
        )
        # d + 2 * n_kv * hd = 32 + 2*2*4 = 48, vs 96 for MHA
        assert lm.params["blocks"][0]["qkv"].shape == (32, 48)
        mha = TransformerLM.init(0, 16, d_model=32, n_heads=8, max_len=8)
        assert mha.params["blocks"][0]["qkv"].shape == (32, 96)

    def test_indivisible_kv_heads_rejected(self):
        with pytest.raises(ValueError, match="n_kv_heads"):
            TransformerLM.init(
                0, 16, d_model=32, n_heads=8, max_len=8, n_kv_heads=3
            )

    def test_gqa_through_ring_and_ulysses(self):
        rng = np.random.default_rng(5)
        lm = TransformerLM.init(
            6, 24, d_model=32, n_heads=8, n_layers=1, max_len=16,
            n_kv_heads=2,
        )
        toks = rng.integers(0, 24, size=(2, 16)).astype(np.int32)
        dense = transformer_logits(lm.params, toks)
        mesh = make_mesh({"sp": 4})
        for impl in ("ring", "ulysses"):
            got = transformer_logits(
                lm.params, toks, attn_impl=impl, mesh=mesh
            )
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(dense), rtol=2e-4, atol=2e-4,
                err_msg=impl,
            )
