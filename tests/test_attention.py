"""Attention tests: flash kernel vs dense oracle (CPU interpret mode) and
ring attention over the virtual sp mesh vs the same oracle."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorframes_tpu.ops import (
    attention_reference,
    flash_attention,
    ring_attention,
    ulysses_attention,
)
from tensorframes_tpu.parallel import make_mesh


def qkv(rng, b=2, h=2, l=32, d=8, dtype=np.float32):
    def mk():
        return jnp.asarray(rng.normal(size=(b, h, l, d)).astype(dtype))

    return mk(), mk(), mk()


@pytest.fixture(scope="module")
def nprng():
    return np.random.default_rng(0)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, nprng, causal):
        q, k, v = qkv(nprng)
        out = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_multiple_kv_blocks(self, nprng):
        q, k, v = qkv(nprng, l=64)
        out = flash_attention(q, k, v, block_q=16, block_k=8)
        ref = attention_reference(q, k, v)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_cross_attention_lengths(self, nprng):
        rng = nprng
        q = jnp.asarray(rng.normal(size=(1, 2, 16, 8)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(1, 2, 48, 8)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(1, 2, 48, 8)).astype(np.float32))
        out = flash_attention(q, k, v, block_q=16, block_k=16)
        ref = attention_reference(q, k, v)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_causal_cross_attention_offset(self, nprng):
        # lq != lk: the causal diagonal aligns bottom-right (decoder step
        # batches); kernel must apply the lk - lq offset
        rng = nprng
        q = jnp.asarray(rng.normal(size=(1, 2, 16, 8)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(1, 2, 48, 8)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(1, 2, 48, 8)).astype(np.float32))
        out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_bad_block_size(self, nprng):
        q, k, v = qkv(nprng, l=30)
        with pytest.raises(ValueError, match="lane-aligned"):
            flash_attention(q, k, v, block_q=16, block_k=16)

    def test_default_tiles_fit_non_multiple_lengths(self, nprng):
        # L=640 is not a multiple of the 512/1024 default tiles but admits
        # a 128 tile; default-argument callers must keep working
        q, k, v = qkv(nprng, l=640)
        out = flash_attention(q, k, v, causal=True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_first_row_causal(self, nprng):
        # the first query attends only to itself: softmax over one key
        q, k, v = qkv(nprng, b=1, h=1, l=16)
        out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
        np.testing.assert_allclose(
            np.asarray(out)[0, 0, 0], np.asarray(v)[0, 0, 0], rtol=1e-5
        )


class TestFlashAttentionGrads:
    """The custom VJP (FlashAttention-2 backward in pallas) vs jax.grad
    through the dense oracle."""

    def _grads(self, fn, q, k, v, causal):
        def loss(q, k, v):
            o = fn(q, k, v, causal=causal)
            # weighted sum so every output element carries a distinct
            # cotangent (catches transposition/scale mistakes a plain
            # .sum() cannot)
            w = jnp.arange(o.size, dtype=jnp.float32).reshape(o.shape)
            return (o.astype(jnp.float32) * jnp.sin(w)).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_oracle(self, nprng, causal):
        q, k, v = qkv(nprng, l=64)
        flash = lambda q, k, v, causal: flash_attention(
            q, k, v, causal=causal, block_q=16, block_k=16
        )
        got = self._grads(flash, q, k, v, causal)
        want = self._grads(attention_reference, q, k, v, causal)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-4,
                err_msg=f"d{name}",
            )

    def test_grads_cross_length_causal(self, nprng):
        # lq != lk: the bottom-right-aligned causal offset must flow
        # through the backward regimes too
        rng = nprng
        q = jnp.asarray(rng.normal(size=(1, 2, 16, 8)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(1, 2, 48, 8)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(1, 2, 48, 8)).astype(np.float32))
        flash = lambda q, k, v, causal: flash_attention(
            q, k, v, causal=causal, block_q=8, block_k=8
        )
        got = self._grads(flash, q, k, v, True)
        want = self._grads(attention_reference, q, k, v, True)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-4,
                err_msg=f"d{name}",
            )

    def test_grads_empty_rows_are_zero(self, nprng):
        # causal with lq > lk: leading queries see no key; their output is
        # zero and so must every gradient flowing through them be
        rng = nprng
        q = jnp.asarray(rng.normal(size=(1, 1, 32, 8)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(1, 1, 16, 8)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(1, 1, 16, 8)).astype(np.float32))
        flash = lambda q, k, v, causal: flash_attention(
            q, k, v, causal=causal, block_q=8, block_k=8
        )
        got = self._grads(flash, q, k, v, True)
        want = self._grads(attention_reference, q, k, v, True)
        # rows 0..15 have offset+i < 0: no visible key
        assert np.all(np.asarray(got[0])[0, 0, :16] == 0.0)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-4,
                err_msg=f"d{name}",
            )

    def test_bf16_grads_close_to_f32(self, nprng):
        q, k, v = qkv(nprng, l=32)
        flash = lambda q, k, v, causal: flash_attention(
            q, k, v, causal=causal, block_q=8, block_k=8
        )
        f32 = self._grads(flash, q, k, v, True)
        b16 = self._grads(
            flash,
            q.astype(jnp.bfloat16),
            k.astype(jnp.bfloat16),
            v.astype(jnp.bfloat16),
            True,
        )
        for g32, g16, name in zip(f32, b16, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g16, dtype=np.float32),
                np.asarray(g32),
                rtol=0.1,
                atol=0.15,
                err_msg=f"d{name}",
            )

    def test_value_and_grad_through_jit(self, nprng):
        # the vjp composes with jit + other ops (the transformer path)
        q, k, v = qkv(nprng, l=32)

        @jax.jit
        def loss(q, k, v):
            return flash_attention(
                q, k, v, causal=True, block_q=8, block_k=8
            ).sum()

        val, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        assert np.isfinite(float(val))
        assert all(np.isfinite(np.asarray(g)).all() for g in grads)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, nprng, causal):
        mesh = make_mesh({"sp": 4})
        q, k, v = qkv(nprng, l=32)
        out = ring_attention(q, k, v, mesh=mesh, causal=causal)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_eight_way(self, nprng):
        mesh = make_mesh({"sp": 8})
        q, k, v = qkv(nprng, l=64, d=4)
        out = ring_attention(q, k, v, mesh=mesh, causal=True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_matches_flash_single_chip(self, nprng):
        mesh = make_mesh({"sp": 4})
        q, k, v = qkv(nprng, l=32)
        ring = ring_attention(q, k, v, mesh=mesh, causal=True)
        flash = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
        np.testing.assert_allclose(ring, flash, rtol=2e-5, atol=2e-5)

    def test_indivisible_length_rejected(self, nprng):
        mesh = make_mesh({"sp": 4})
        q, k, v = qkv(nprng, l=30)
        with pytest.raises(ValueError, match="divide"):
            ring_attention(q, k, v, mesh=mesh)

    @pytest.mark.parametrize("causal", [False, True])
    def test_blockwise_hops_multiple_tiles(self, nprng, causal):
        # chunk (L/n = 32) split into four 8-wide tiles per hop: the carry
        # kernel must stream sub-blocks within a hop, not just whole chunks
        mesh = make_mesh({"sp": 4})
        q, k, v = qkv(nprng, l=128)
        out = ring_attention(
            q, k, v, mesh=mesh, causal=causal, block_q=8, block_k=8
        )
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_bf16_matches_f32(self, nprng):
        mesh = make_mesh({"sp": 4})
        q, k, v = qkv(nprng, l=64)
        f32 = ring_attention(q, k, v, mesh=mesh, causal=True)
        b16 = ring_attention(
            q.astype(jnp.bfloat16),
            k.astype(jnp.bfloat16),
            v.astype(jnp.bfloat16),
            mesh=mesh,
            causal=True,
        )
        np.testing.assert_allclose(
            np.asarray(b16, dtype=np.float32), np.asarray(f32),
            rtol=0.05, atol=0.05,
        )

    def test_causal_cross_length_rejected(self, nprng):
        # chunk-level causal regimes assume aligned diagonals; the entry
        # point must refuse rather than silently pick an alignment
        mesh = make_mesh({"sp": 4})
        rng = nprng
        q = jnp.asarray(rng.normal(size=(1, 2, 16, 8)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(1, 2, 32, 8)).astype(np.float32))
        with pytest.raises(ValueError, match="equal q/k"):
            ring_attention(q, k, k, mesh=mesh, causal=True)


class TestRingAttentionGrads:
    """The ring-backward custom VJP (dq local, dk/dv rotating home) vs
    jax.grad through the dense oracle."""

    def _grads(self, fn, q, k, v):
        def loss(q, k, v):
            o = fn(q, k, v)
            w = jnp.arange(o.size, dtype=jnp.float32).reshape(o.shape)
            return (o.astype(jnp.float32) * jnp.sin(w)).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_oracle(self, nprng, causal):
        mesh = make_mesh({"sp": 4})
        q, k, v = qkv(nprng, l=64)
        ring = lambda q, k, v: ring_attention(q, k, v, mesh=mesh, causal=causal)
        dense = lambda q, k, v: attention_reference(q, k, v, causal=causal)
        got = self._grads(ring, q, k, v)
        want = self._grads(dense, q, k, v)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-4,
                err_msg=f"d{name}",
            )

    def test_grads_multiple_tiles_per_hop(self, nprng):
        # sub-block streaming in the BACKWARD hops too
        mesh = make_mesh({"sp": 4})
        q, k, v = qkv(nprng, l=128)
        ring = lambda q, k, v: ring_attention(
            q, k, v, mesh=mesh, causal=True, block_q=8, block_k=8
        )
        dense = lambda q, k, v: attention_reference(q, k, v, causal=True)
        got = self._grads(ring, q, k, v)
        want = self._grads(dense, q, k, v)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-4,
                err_msg=f"d{name}",
            )


class TestFullyMaskedRows:
    """Causal attention with lq > lk leaves early query rows with no visible
    key; the convention (everywhere) is zeros for such rows, not a uniform
    average of V."""

    def test_reference_zeros_fully_masked(self, nprng):
        rng = nprng
        q = jnp.asarray(rng.normal(size=(1, 2, 16, 8)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(1, 2, 8, 8)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(1, 2, 8, 8)).astype(np.float32))
        ref = np.asarray(attention_reference(q, k, v, causal=True))
        # offset = lk - lq = -8: rows 0..7 see no key at all
        np.testing.assert_array_equal(ref[:, :, :8], 0.0)
        assert np.abs(ref[:, :, 8:]).min() > 0

    def test_flash_matches_reference_lq_gt_lk(self, nprng):
        rng = nprng
        q = jnp.asarray(rng.normal(size=(1, 2, 16, 8)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(1, 2, 8, 8)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(1, 2, 8, 8)).astype(np.float32))
        out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


class TestUlyssesAttention:
    """All-to-all sequence parallelism: seq-sharded -> head-sharded ->
    attend full-L -> shard back (ops/ulysses.py)."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, nprng, causal):
        mesh = make_mesh({"sp": 4})
        q, k, v = qkv(nprng, h=4, l=32)
        out = ulysses_attention(q, k, v, mesh=mesh, causal=causal)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_eight_way(self, nprng):
        mesh = make_mesh({"sp": 8})
        q, k, v = qkv(nprng, h=8, l=64, d=4)
        out = ulysses_attention(q, k, v, mesh=mesh, causal=True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_matches_ring(self, nprng):
        mesh = make_mesh({"sp": 4})
        q, k, v = qkv(nprng, h=4, l=32)
        u = ulysses_attention(q, k, v, mesh=mesh, causal=True)
        r = ring_attention(q, k, v, mesh=mesh, causal=True)
        np.testing.assert_allclose(u, r, rtol=2e-5, atol=2e-5)

    def test_indivisible_heads_rejected(self, nprng):
        mesh = make_mesh({"sp": 4})
        q, k, v = qkv(nprng, h=2, l=32)  # 2 heads on a 4-way axis
        with pytest.raises(ValueError, match="head count"):
            ulysses_attention(q, k, v, mesh=mesh)

    def test_indivisible_length_rejected(self, nprng):
        mesh = make_mesh({"sp": 4})
        q, k, v = qkv(nprng, h=4, l=30)
        with pytest.raises(ValueError, match="divide"):
            ulysses_attention(q, k, v, mesh=mesh)

    def test_transformer_ulysses_impl(self, nprng):
        from tensorframes_tpu.models import init_transformer, transformer_logits

        mesh = make_mesh({"sp": 4})
        params = init_transformer(
            0, vocab=16, d_model=16, n_heads=4, n_layers=1, max_len=32
        )
        toks = nprng.integers(0, 16, size=(2, 32)).astype(np.int32)
        u = transformer_logits(params, toks, attn_impl="ulysses", mesh=mesh)
        d = transformer_logits(params, toks)
        np.testing.assert_allclose(
            np.asarray(u), np.asarray(d), rtol=2e-4, atol=2e-4
        )
