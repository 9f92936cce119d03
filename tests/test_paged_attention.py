"""Fused ragged paged-attention kernel, shared-prefix KV cache, chunked
prefill (PR 7).

Three correctness bars:

- the fused kernel (``ops.ragged_paged_attention``) matches the gather
  ``paged_attention`` oracle within float tolerance across a ragged
  length matrix — 1-token to max-pages sequences, MHA and GQA, f32 and
  bf16;
- engine decode streams stay BYTE-IDENTICAL to solo
  ``transformer_generate`` — greedy and seeded sampling — with the
  prefix cache and chunked prefill enabled, including under preemption,
  mid-run defragment, restart, and chaos;
- the compiled-program budget: <= 2 step programs with the new features
  off (the PR-2 invariant, untouched), <= 3 with them on (the one new
  program is the prefill chunk).
"""

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorframes_tpu.models import TransformerLM
from tensorframes_tpu.obs import metrics as obs_metrics
from tensorframes_tpu.ops import (
    paged_attention,
    paged_page_size_hint,
    ragged_paged_attention,
)
from tensorframes_tpu.serve import GenerationEngine, PagePool, SequencePages
from tensorframes_tpu.serve.kv_pages import PrefixCache
from tensorframes_tpu.utils import get_config, set_config

pytestmark = pytest.mark.attn

VOCAB = 32


@pytest.fixture(scope="module")
def lm():
    return TransformerLM.init(0, VOCAB, d_model=16, n_heads=4, max_len=48)


@pytest.fixture(scope="module")
def lm_gqa():
    return TransformerLM.init(
        1, VOCAB, d_model=16, n_heads=4, n_kv_heads=2, max_len=48
    )


def _solo(lm, prompt, n, **kw):
    return lm.generate(np.asarray([prompt], np.int32), n, **kw)[
        0, len(prompt):
    ]


def _prompts(rng, lens):
    return [
        rng.integers(1, VOCAB, size=n).astype(np.int32).tolist()
        for n in lens
    ]


def _counter_value(name, **labels):
    try:
        return obs_metrics.registry().get(name).value(**labels)
    except KeyError:
        return 0.0


# ---------------------------------------------------------------------------


class TestRaggedKernelOracle:
    """ragged_paged_attention vs the gather paged_attention oracle."""

    def _case(self, rng, slots, n_kv, group, hd, ps, mp, pool, dtype):
        q = jnp.asarray(
            rng.normal(size=(slots, n_kv, group, hd)).astype(np.float32)
        ).astype(dtype)
        # the pool's layout (serve/kv_pages.py): heads merged into lanes
        kp = jnp.asarray(
            rng.normal(size=(pool + 1, ps, n_kv * hd)).astype(np.float32)
        ).astype(dtype)
        vp = jnp.asarray(
            rng.normal(size=(pool + 1, ps, n_kv * hd)).astype(np.float32)
        ).astype(dtype)
        ptab = rng.integers(0, pool, size=(slots, mp)).astype(np.int32)
        return q, kp, vp, ptab

    @pytest.mark.parametrize(
        "n_kv,group", [(2, 1), (2, 2), (1, 4)],
        ids=["mha-ish", "gqa2", "mqa"],
    )
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_matches_gather_over_ragged_lengths(self, rng, n_kv, group,
                                                dtype):
        dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
        ps, mp = 4, 4
        # every regime: single token, partial page, exact page boundary,
        # mid-sequence, and the full max_pages * page_size length
        lengths = np.asarray([1, 3, 4, 9, 16], np.int32)
        q, kp, vp, ptab = self._case(
            rng, len(lengths), n_kv, group, hd=8, ps=ps, mp=mp, pool=12,
            dtype=dt,
        )
        ref = paged_attention(q, kp, vp, ptab, lengths)
        got = ragged_paged_attention(q, kp, vp, ptab, lengths)
        assert got.dtype == q.dtype
        tol = 2e-2 if dtype == "bfloat16" else 2e-5
        np.testing.assert_allclose(
            np.asarray(got, np.float32),
            np.asarray(ref, np.float32),
            rtol=tol,
            atol=tol,
        )

    def test_under_jit_and_every_length(self, rng):
        # exhaustive 1..T sweep of one slot's length under jit — the
        # boundary-page mask has to be right at every offset
        ps, mp = 4, 3
        t = ps * mp
        fn = jax.jit(ragged_paged_attention)
        q, kp, vp, ptab = self._case(
            rng, 2, 2, 2, hd=8, ps=ps, mp=mp, pool=8, dtype=jnp.float32
        )
        for length in range(1, t + 1):
            lengths = np.asarray([length, t], np.int32)
            ref = paged_attention(q, kp, vp, ptab, lengths)
            got = fn(q, kp, vp, ptab, lengths)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5,
                err_msg=f"length={length}",
            )

    def test_trash_paged_idle_slot_is_finite(self, rng):
        # an idle slot (all-trash table, length 1) must produce finite
        # output — the engine discards it, but NaN would poison the
        # whole decode batch through the shared program
        ps, mp, pool = 4, 2, 6
        q, kp, vp, _ = self._case(
            rng, 1, 2, 1, hd=8, ps=ps, mp=mp, pool=pool, dtype=jnp.float32
        )
        ptab = np.full((1, mp), pool, np.int32)  # trash page everywhere
        got = ragged_paged_attention(
            q, kp, vp, ptab, np.asarray([1], np.int32)
        )
        assert np.isfinite(np.asarray(got)).all()

    def test_page_size_hint_comes_from_tile_table(self):
        # the hint is the flash sweep's measured block_k — currently 1024
        # for every (dtype, head_dim) bucket
        assert paged_page_size_hint(jnp.bfloat16, 128) == 1024
        assert paged_page_size_hint(jnp.float32, 64) == 1024


class TestDecodeReadKernel:
    """The decode read's walk of each slot's live pages, several a step,
    against the gather oracle in float32 at ``highest``."""

    def _pool(self, rng, n_layers, pages, ps, lanes):
        def one():
            return jnp.asarray(rng.normal(
                size=(n_layers, pages + 1, ps, lanes)
            ).astype(np.float32))
        return one(), one()

    def _tables(self, rng, lengths, rows, ps, trash):
        """Distinct live pages for each slot, the rest of its row the
        trash page (as the engine lays an idle slot's row)."""
        need = [-(-int(n) // ps) for n in lengths]
        free = iter(rng.permutation(trash))
        table = np.full((len(lengths), rows), trash, np.int32)
        for s, n in enumerate(need):
            table[s, :n] = [next(free) for _ in range(n)]
        return table

    def _check(self, q, kp, vp, table, lengths, layer, **kw):
        with jax.default_matmul_precision("highest"):
            want = paged_attention(q, kp, vp, table, lengths, layer=layer)
            got = ragged_paged_attention(
                q, kp, vp, table, lengths, layer=layer, **kw
            )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )
        return np.asarray(got)

    @pytest.mark.parametrize("block_pages", [None, 1, 3, 8])
    def test_every_length_regime(self, rng, block_pages):
        # a key, the last row of a page, a whole page, one past it, 8 and
        # 9 pages' worth, and every row of a 64-row table but one, and all
        ps, rows = 16, 64
        lengths = np.asarray(
            [1, 15, 16, 17, 128, 129, 1023, 1024], np.int32
        )
        kp, vp = self._pool(rng, 1, 8 * rows, ps, 2 * 8)
        table = self._tables(rng, lengths, rows, ps, 8 * rows)
        q = rng.normal(size=(8, 2, 1, 8)).astype(np.float32)
        self._check(q, kp, vp, table, lengths, 0, block_pages=block_pages)

    @pytest.mark.parametrize("block_pages", [None, 2])
    def test_idle_and_full_slots_mixed(self, rng, block_pages):
        # idle slots (one key on the trash page) between full ones
        ps, rows, pages = 4, 8, 40
        lengths = np.asarray([32, 1, 1, 32, 1, 13], np.int32)
        kp, vp = self._pool(rng, 2, pages, ps, 2 * 8)
        table = self._tables(rng, lengths, rows, ps, pages)
        for s in (1, 2, 4):
            table[s, 0] = pages  # an idle slot's row names the trash page
        q = rng.normal(size=(6, 2, 2, 8)).astype(np.float32)
        self._check(q, kp, vp, table, lengths, 1, block_pages=block_pages)

    @pytest.mark.parametrize(
        "n_kv, group, hd", [(25, 1, 64), (2, 2, 64)], ids=["gpt2-xl", "gqa2"]
    )
    def test_heads_on_lanes(self, rng, n_kv, group, hd):
        # 25 heads of 64 on 1,600 lanes (gpt2-xl's row), and a GQA group
        ps, rows, pages = 16, 8, 24
        lengths = np.asarray([5, 128, 70], np.int32)
        kp, vp = self._pool(rng, 1, pages, ps, n_kv * hd)
        table = self._tables(rng, lengths, rows, ps, pages)
        q = rng.normal(size=(3, n_kv, group, hd)).astype(np.float32)
        self._check(q, kp, vp, table, lengths, 0)

    def test_layers_of_one_pool_through_one_callee(self, rng):
        # the layer is data: every layer of one pool is the same traced
        # callee, under one jit as in a step program
        from tensorframes_tpu.ops import attention

        ps, rows, pages, n_layers = 4, 6, 30, 3
        lengths = np.asarray([9, 24, 1], np.int32)
        kp, vp = self._pool(rng, n_layers, pages, ps, 2 * 8)
        table = self._tables(rng, lengths, rows, ps, pages)
        q = rng.normal(size=(n_layers, 3, 2, 1, 8)).astype(np.float32)

        def step(q, kp, vp):
            return [
                ragged_paged_attention(q[li], kp, vp, table, lengths,
                                       layer=li)
                for li in range(n_layers)
            ]

        text = jax.jit(step).lower(q, kp, vp).as_text()
        assert text.count("func.func private @_ragged_paged_read") == 1
        assert text.count("call @_ragged_paged_read") == n_layers
        with jax.default_matmul_precision("highest"):
            got = jax.jit(step)(q, kp, vp)
            for li in range(n_layers):
                want = paged_attention(
                    q[li], kp, vp, table, lengths, layer=li
                )
                np.testing.assert_allclose(
                    np.asarray(got[li]), np.asarray(want),
                    rtol=2e-5, atol=2e-5, err_msg=f"layer {li}",
                )
        assert attention._ragged_paged_read._cache_size() >= 1

    @pytest.mark.parametrize("fill", [np.nan, 3e38, -3e38])
    @pytest.mark.parametrize("block_pages", [None, 3])
    def test_pages_past_the_end_are_never_read(self, rng, fill,
                                               block_pages):
        # every page no slot holds a key in, the trash page and the rows
        # of the last pages past each slot's end: the result's bits are
        # those of a pool with ordinary numbers there
        ps, rows, pages = 4, 8, 48
        lengths = np.asarray([3, 17, 32, 6], np.int32)
        kp, vp = self._pool(rng, 1, pages, ps, 2 * 8)
        table = self._tables(rng, lengths, rows, ps, pages)
        q = rng.normal(size=(4, 2, 1, 8)).astype(np.float32)
        keep = np.zeros((pages + 1, ps), bool)
        for s, n in enumerate(lengths):
            for t in range(n):
                keep[table[s, t // ps], t % ps] = True
        wild = (
            jnp.where(keep[None, :, :, None], x, fill) for x in (kp, vp)
        )
        with jax.default_matmul_precision("highest"):
            clean = ragged_paged_attention(
                q, kp, vp, table, lengths, layer=0, block_pages=block_pages
            )
            dirty = ragged_paged_attention(
                q, *wild, table, lengths, layer=0, block_pages=block_pages
            )
        assert np.isfinite(np.asarray(dirty)).all()
        np.testing.assert_array_equal(np.asarray(dirty), np.asarray(clean))

    @pytest.mark.parametrize(
        "lengths, block_pages",
        [([1, 15, 16, 17, 128, 129, 1023, 1024], 8),
         ([1, 15, 16, 17, 128, 129, 1023, 1024], 3),
         ([250, 1, 1, 1, 1, 1, 1, 1], 8),
         ([5, 1, 1, 1, 1, 1, 1, 1], 8),
         ([700, 650, 540, 580, 1, 1, 1, 1], 16)],
        ids=["regimes-8", "regimes-3", "chat", "short", "docs-16"],
    )
    def test_positions_read_are_the_pages_copied(self, lengths, block_pages):
        # the account (the span's kv_tokens_read) against the pages the
        # schedule hands the pipeline: a buffer's page is copied where it
        # changes, and once at the walk's start
        from tensorframes_tpu.ops import attention

        ps, rows = 16, 64
        lengths = np.asarray(lengths, np.int32)
        table = np.arange(8 * rows, dtype=np.int32).reshape(8, rows)
        steps = 8 * -(-rows // block_pages)
        slot, trip, fetch, total = attention._decode_schedule(
            jnp.asarray(table), jnp.asarray(lengths), ps, block_pages, steps
        )
        fetch = np.asarray(fetch).reshape(steps, block_pages)
        copies = 1 + (np.diff(fetch, axis=0) != 0).sum(axis=0)
        copied = set(fetch.reshape(-1).tolist())
        live = {
            table[s, p] for s, n in enumerate(lengths)
            for p in range(-(-int(n) // ps))
        }
        assert copied <= live  # nothing past a slot's end
        account = attention.decode_read_positions(
            lengths, rows, ps, 0, block_pages=block_pages
        )
        assert account == int(copies.sum()) * ps
        assert int(total) == sum(
            max(1, -(-int(-(-n // ps)) // block_pages)) for n in lengths
        )

    def test_block_pages_follow_the_pages_bytes(self):
        from tensorframes_tpu.ops import attention

        # gpt2-xl: pages of 16 x 1,600 float32, 100 KB, tables of 64
        assert attention.decode_block_pages(16 * 1600 * 4, 64) == 8
        assert attention.decode_block_pages(16 * 512 * 2, 1048) == 64
        # never wider than the table, at least one page
        assert attention.decode_block_pages(64, 12) == 12
        assert attention.decode_block_pages(1 << 30, 64) == 1


class TestPagedInputValidation:
    """A wrong page_table/lengths dtype used to miscompute the mask
    silently; both reads must reject it loudly."""

    def _args(self, rng):
        q = jnp.zeros((2, 2, 1, 8), jnp.float32)
        kp = jnp.zeros((5, 4, 2 * 8), jnp.float32)
        ptab = np.zeros((2, 3), np.int32)
        lengths = np.ones(2, np.int32)
        return q, kp, ptab, lengths

    @pytest.mark.parametrize("impl", [paged_attention, ragged_paged_attention])
    def test_bad_dtypes_rejected(self, rng, impl):
        q, kp, ptab, lengths = self._args(rng)
        with pytest.raises(ValueError, match="page_table must be int32"):
            impl(q, kp, kp, ptab.astype(np.int64), lengths)
        with pytest.raises(ValueError, match="lengths must be int32"):
            impl(q, kp, kp, ptab, lengths.astype(np.float32))

    @pytest.mark.parametrize("impl", [paged_attention, ragged_paged_attention])
    def test_bad_shapes_rejected(self, rng, impl):
        q, kp, ptab, lengths = self._args(rng)
        with pytest.raises(ValueError, match="lengths must be \\[slots"):
            impl(q, kp, kp, ptab, np.ones(3, np.int32))
        with pytest.raises(ValueError, match="page_table must be \\[slots"):
            impl(q, kp, kp, np.zeros((3, 3), np.int32), lengths)
        with pytest.raises(ValueError, match="n_kv"):
            impl(q, jnp.zeros((5, 4, 3 * 8), jnp.float32),
                 jnp.zeros((5, 4, 3 * 8), jnp.float32), ptab, lengths)
        with pytest.raises(ValueError, match="share a shape"):
            impl(q, kp, jnp.zeros((5, 4, 2 * 4), jnp.float32), ptab, lengths)
        with pytest.raises(ValueError, match="leading layer axis"):
            impl(q, kp[None], kp[None], ptab, lengths)


# ---------------------------------------------------------------------------


class TestPrefixCacheUnit:
    def _pool(self, num_pages=12, page_size=4):
        return PagePool(
            n_layers=1, n_kv_heads=1, head_dim=4,
            num_pages=num_pages, page_size=page_size,
        )

    def test_refcount_share_and_release(self):
        pool = self._pool()
        pages = pool.alloc(3)
        pool.ref(pages[:2])
        assert pool.pages_shared == 2
        assert pool.free(pages) == 1  # two still referenced
        assert pool.pages_in_use == 2
        assert pool.free(pages[:2]) == 2
        assert pool.pages_in_use == 0 and pool.pages_shared == 0
        with pytest.raises(ValueError, match="double free"):
            pool.free([pages[0]])
        with pytest.raises(ValueError, match="ref free page"):
            pool.ref([pages[0]])

    def test_insert_acquire_exact_and_partial(self):
        pool = self._pool()
        cache = PrefixCache(pool)
        prompt = np.arange(100, 110, dtype=np.int32)  # 2 full pages + 2
        seq = SequencePages(pool)
        seq.ensure(len(prompt))
        assert cache.insert(prompt, seq.pages)
        assert not cache.insert(prompt, seq.pages)  # idempotent
        # exact prefix: both full pages, cow for the partial third page
        # is impossible (entry only holds full pages)
        shared, cow, cached = cache.acquire(prompt)
        assert shared == seq.pages[:2] and cached == 8 and cow is None
        pool.free(shared)
        # divergence INSIDE page 1 -> 1 shared page + cow of page 1
        p2 = prompt.copy()
        p2[6] = 7
        shared, cow, cached = cache.acquire(p2)
        assert shared == seq.pages[:1]
        assert cow == seq.pages[1] and cached == 6
        pool.free(shared)
        pool.free([cow])
        # total miss
        assert cache.acquire(np.asarray([9, 9, 9, 9, 9], np.int32)) == (
            [], None, 0
        )
        st = cache.stats()
        assert st["hits"] == 2 and st["lookups"] == 3

    def test_last_position_always_recomputed(self):
        # a prompt the cache covers ENTIRELY must still leave >= 1
        # position to prefill (the first sampled token needs its logits)
        pool = self._pool()
        cache = PrefixCache(pool)
        prompt = np.arange(8, dtype=np.int32)  # exactly 2 pages
        seq = SequencePages(pool)
        seq.ensure(8)
        cache.insert(prompt, seq.pages)
        shared, cow, cached = cache.acquire(prompt)
        assert cached == 7  # page 0 shared + 3 cow positions, not 8
        assert shared == seq.pages[:1] and cow == seq.pages[1]
        pool.free(shared)
        pool.free([cow])

    def test_eviction_frees_only_unshared(self):
        pool = self._pool(num_pages=6)
        cache = PrefixCache(pool)
        seq = SequencePages(pool)
        seq.ensure(8)
        prompt = np.arange(8, dtype=np.int32)
        cache.insert(prompt, seq.pages)
        seq.release()  # cache is now sole owner
        assert pool.pages_in_use == 2
        assert cache.evict_pages(1) == 2  # whole entry drops
        assert len(cache) == 0 and pool.pages_in_use == 0

    def test_lru_bound(self):
        pool = self._pool(num_pages=12)
        cache = PrefixCache(pool, max_entries=2)
        seqs = []
        for i in range(3):
            seq = SequencePages(pool)
            seq.ensure(4)
            cache.insert(np.arange(i * 10, i * 10 + 4, dtype=np.int32),
                         seq.pages)
            seqs.append(seq)
        assert len(cache) == 2  # oldest evicted
        assert cache.acquire(np.arange(0, 4, dtype=np.int32))[2] == 0

    def test_defragment_renumbers_cache_entries(self):
        pool = self._pool()
        cache = PrefixCache(pool)
        junk = SequencePages(pool)
        junk.ensure(8)  # occupy low pages, then free -> fragmentation
        seq = SequencePages(pool)
        seq.ensure(8)
        prompt = np.arange(8, dtype=np.int32)
        cache.insert(prompt, seq.pages)
        junk.release()
        remap = pool.defragment(
            [seq], page_lists=cache.entry_page_lists()
        )
        assert seq.pages == [0, 1]
        shared, _, cached = cache.acquire(prompt)
        assert shared == seq.pages[:1] or shared == seq.pages[:2]
        pool.free(shared)
        assert len(remap) == 2


# ---------------------------------------------------------------------------


class TestEngineFusedDecode:
    """The ragged kernel wired into the decode step: stream parity."""

    def test_fused_streams_match_gather_and_solo(self, lm, gather_read):
        rng = np.random.default_rng(3)
        prompts = _prompts(rng, [5, 9, 3, 17])
        outs = {}
        for impl in ("gather", "fused"):
            with gather_read() if impl == "gather" else contextlib.nullcontext():
                eng = GenerationEngine(
                    lm, max_slots=4, page_size=4, max_seq_len=48,
                )
                outs[impl] = eng.generate(prompts, 8)
            assert eng.num_step_programs <= 2
        for p, g, f in zip(prompts, outs["gather"], outs["fused"]):
            solo = _solo(lm, p, 8)
            assert np.array_equal(g, solo)
            assert np.array_equal(f, solo)

    def test_fused_gqa_streams_match_solo(self, lm_gqa):
        rng = np.random.default_rng(4)
        prompts = _prompts(rng, [6, 11])
        eng = GenerationEngine(
            lm_gqa, max_slots=2, page_size=4, max_seq_len=48,
        )
        for p, o in zip(prompts, eng.generate(prompts, 8)):
            assert np.array_equal(o, _solo(lm_gqa, p, 8))

    def test_a_slot_walks_several_steps(self, lm, monkeypatch):
        # a fast-memory budget of three pages: two pages a step, so a
        # 37-token slot's ten pages take five steps and an idle slot one
        from tensorframes_tpu.ops import attention

        eng = GenerationEngine(lm, max_slots=3, page_size=4, max_seq_len=48)
        page = 4 * eng.pool.k.shape[-1] * 4
        monkeypatch.setattr(attention, "_DECODE_STEP_BYTES", 3 * page)
        assert attention.decode_block_pages(page, eng._max_pages) == 2
        rng = np.random.default_rng(8)
        prompts = _prompts(rng, [37, 2])
        for p, o in zip(prompts, eng.generate(prompts, 6)):
            assert np.array_equal(o, _solo(lm, p, 6))


class TestChunkedPrefill:
    def test_streams_identical_and_third_program(self, lm):
        rng = np.random.default_rng(5)
        prompts = _prompts(rng, [17, 5, 23, 9])  # mix: chunked and not
        before = _counter_value("serve.prefill_chunks_total")
        eng = GenerationEngine(
            lm, max_slots=4, page_size=4, max_seq_len=48,
            prefill_chunk_tokens=8,
        )
        outs = eng.generate(prompts, 8)
        for p, o in zip(prompts, outs):
            assert np.array_equal(o, _solo(lm, p, 8))
        # prompts of 17 and 23 tokens chunk (3 chunks each); 5 and 9
        # run the one-pass program
        assert eng.num_step_programs <= 3
        assert _counter_value("serve.prefill_chunks_total") - before >= 6

    def test_seeded_sampling_identical(self, lm):
        rng = np.random.default_rng(6)
        prompts = _prompts(rng, [19, 21])
        eng = GenerationEngine(
            lm, max_slots=2, page_size=4, max_seq_len=48,
            prefill_chunk_tokens=4,
        )
        kw = dict(temperature=0.8, seed=11, top_p=0.9)
        for p, o in zip(prompts, eng.generate(prompts, 8, **kw)):
            assert np.array_equal(o, _solo(lm, p, 8, **kw))

    def test_chunk_interleaves_with_decode(self, lm):
        # a long prompt admitted while another stream decodes must not
        # stall it: between the long prompt's chunks the short stream
        # keeps emitting (one decode step per engine step)
        eng = GenerationEngine(
            lm, max_slots=2, page_size=4, max_seq_len=48,
            prefill_chunk_tokens=4,
        )
        rng = np.random.default_rng(7)
        short = _prompts(rng, [4])[0]
        long = _prompts(rng, [24])[0]
        h_short = eng.submit(short, 16)
        eng.step()  # short prefilled, emits token 1
        h_long = eng.submit(long, 4)
        emitted_before = len(h_short._tokens)
        # long needs 6 chunks; each step must also decode short
        for _ in range(6):
            eng.step()
        assert len(h_short._tokens) >= emitted_before + 6
        eng.run_until_idle()
        assert np.array_equal(h_short.result(5), _solo(lm, short, 16))
        assert np.array_equal(h_long.result(5), _solo(lm, long, 4))


class TestPrefixCacheEngine:
    def test_identical_prompts_hit_and_match(self, lm):
        rng = np.random.default_rng(8)
        shared = _prompts(rng, [17])[0]
        eng = GenerationEngine(
            lm, max_slots=4, page_size=4, max_seq_len=48,
            prefix_cache=True,
        )
        hits0 = _counter_value("serve.prefix_cache_hits_total")
        first = eng.generate([shared], 8)[0]
        again = eng.generate([shared, shared], 8)
        solo = _solo(lm, shared, 8)
        assert np.array_equal(first, solo)
        assert np.array_equal(again[0], solo)
        assert np.array_equal(again[1], solo)
        assert _counter_value("serve.prefix_cache_hits_total") - hits0 >= 2
        assert eng.prefix_cache.stats()["hits"] >= 2
        assert eng.num_step_programs <= 3

    def test_divergent_prompt_cow_matches_solo(self, lm):
        rng = np.random.default_rng(9)
        base = _prompts(rng, [16])[0]
        diverged = list(base[:10]) + [1, 2, 3]  # splits inside page 2
        eng = GenerationEngine(
            lm, max_slots=2, page_size=4, max_seq_len=48,
            prefix_cache=True,
        )
        eng.generate([base], 8)
        out = eng.generate([diverged], 8)[0]
        assert np.array_equal(out, _solo(lm, diverged, 8))
        assert eng.prefix_cache.stats()["hits"] >= 1

    def test_sampled_streams_with_cache_and_chunking(self, lm):
        rng = np.random.default_rng(10)
        shared = _prompts(rng, [20])[0]
        eng = GenerationEngine(
            lm, max_slots=4, page_size=4, max_seq_len=48,
            prefix_cache=True, prefill_chunk_tokens=8,
        )
        kw = dict(temperature=0.7, seed=3, top_p=0.85)
        solo = _solo(lm, shared, 8, **kw)
        outs = eng.generate([shared, shared, shared], 8, **kw)
        for o in outs:
            assert np.array_equal(o, solo)

    def test_preemption_under_pressure_stays_identical(self, lm):
        # tight pool + cache refs: eviction must go before preemption,
        # and every stream must stay byte-identical through requeues
        rng = np.random.default_rng(11)
        sys_prompt = _prompts(rng, [12])[0]
        prompts = [
            sys_prompt + _prompts(rng, [4])[0] for _ in range(6)
        ]
        eng = GenerationEngine(
            lm, max_slots=3, page_size=4, max_seq_len=48, num_pages=18,
            prefix_cache=True, prefill_chunk_tokens=4, queue_capacity=8,
        )
        for p, o in zip(prompts, eng.generate(prompts, 8)):
            assert np.array_equal(o, _solo(lm, p, 8))
        assert eng.num_step_programs <= 3

    def test_defragment_mid_run_with_cache(self, lm):
        rng = np.random.default_rng(12)
        prompts = [_prompts(rng, [14])[0] for _ in range(2)]
        eng = GenerationEngine(
            lm, max_slots=2, page_size=4, max_seq_len=48,
            prefix_cache=True,
        )
        handles = [eng.submit(p, 12) for p in prompts]
        for _ in range(3):
            eng.step()
        eng.defragment()
        eng.run_until_idle()
        for h, p in zip(handles, prompts):
            assert np.array_equal(h.result(5), _solo(lm, p, 12))
        # the cache survived compaction and still hits
        out = eng.generate([prompts[0]], 8)[0]
        assert np.array_equal(out, _solo(lm, prompts[0], 8))
        assert eng.prefix_cache.stats()["hits"] >= 1

    def test_defragment_remaps_pending_cow_donor(self, lm):
        # regression: a defragment landing between admission (which
        # pins a copy-on-write donor page by index) and the clone —
        # an earlier slot's prefill OOM does exactly this — must
        # renumber the pending donor, or the clone copies whatever page
        # took the old index and frees the wrong reference
        rng = np.random.default_rng(16)
        base = _prompts(rng, [14])[0]
        diverged = list(base[:10]) + [2, 4, 6]  # splits inside page 2
        eng = GenerationEngine(
            lm, max_slots=2, page_size=4, max_seq_len=48,
            prefix_cache=True,
        )
        # fragment the pool so compaction actually moves pages
        junk = eng.scheduler.pool.alloc(5)
        eng.generate([base], 8)
        eng.pool.free(junk)
        h = eng.submit(diverged, 8)
        admitted = eng.scheduler.admit()
        (idx, act), = admitted
        assert act.cow_src is not None
        donor_before = act.cow_src
        eng._defragment_locked()
        assert act.cow_src is not None and act.cow_src != donor_before
        assert eng._try_prefill(idx, act, first=True) is None
        eng.run_until_idle()
        assert np.array_equal(h.result(5), _solo(lm, diverged, 8))

    def test_admit_eviction_covers_only_the_shortfall(self, lm):
        # regression: eviction on admission must free only the pages
        # the free list cannot cover — not the full prompt's worth —
        # so warm prefixes survive, and an admission the pool CAN
        # satisfy is not spuriously requeued
        rng = np.random.default_rng(17)
        eng = GenerationEngine(
            lm, max_slots=2, page_size=4, max_seq_len=48, num_pages=12,
            prefix_cache=True,
        )
        cold = _prompts(rng, [8])[0]
        warm = _prompts(rng, [8])[0]
        eng.generate([cold], 2)  # LRU-oldest entry: 2 pages
        eng.generate([warm], 2)  # newer entry: 2 pages
        assert len(eng.prefix_cache) == 2 and eng.pool.pages_in_use == 4
        big = _prompts(rng, [37])[0]  # 37 + 3 new = 10 pages > 8 free
        h = eng.submit(big, 3)
        eng.step()
        # admitted THIS step (not requeued — its own registration at
        # prefill completion proves it), and only the COLD entry paid:
        # the shortfall was 2 pages, so the warm entry survives
        assert eng.prefix_cache.acquire(np.asarray(cold, np.int32))[2] == 0
        got = eng.prefix_cache.acquire(np.asarray(warm, np.int32))
        assert got[2] > 0
        eng.pool.free(got[0])
        if got[1] is not None:
            eng.pool.free([got[1]])
        eng.run_until_idle()
        assert np.array_equal(h.result(5), _solo(lm, big, 3))

    def test_restart_clears_cache_and_recovers(self, lm):
        rng = np.random.default_rng(13)
        prompts = [_prompts(rng, [15])[0] for _ in range(2)]
        eng = GenerationEngine(
            lm, max_slots=2, page_size=4, max_seq_len=48,
            prefix_cache=True, prefill_chunk_tokens=4,
        )
        handles = [eng.submit(p, 12) for p in prompts]
        for _ in range(4):
            eng.step()
        eng.restart()
        assert len(eng.prefix_cache) == 0  # device contents are gone
        eng.run_until_idle()
        for h, p in zip(handles, prompts):
            assert np.array_equal(h.result(5), _solo(lm, p, 12))
        assert eng.num_step_programs <= 3

    def test_kv_pages_shared_gauge_tracks_sharing(self, lm):
        rng = np.random.default_rng(14)
        shared = _prompts(rng, [16])[0]
        eng = GenerationEngine(
            lm, max_slots=2, page_size=4, max_seq_len=48,
            prefix_cache=True,
        )
        eng.generate([shared], 4)  # registers the prefix (cache-only ref)
        assert eng.pool.pages_shared == 0  # one ref each: not shared yet
        h = eng.submit(shared, 8)  # hit: sequence + cache share pages
        eng.step()
        assert eng.pool.pages_shared > 0
        g = obs_metrics.registry().get("serve.kv_pages_shared")
        assert g.value() > 0
        eng.run_until_idle()
        assert np.array_equal(h.result(5), _solo(lm, shared, 8))


@pytest.mark.chaos
class TestChaosWithPrefixAndChunks:
    def test_soak_transient_and_pool_faults(self, lm):
        # the PR-3 soak contract with the PR-7 features on: seeded
        # transient faults on every dispatch site (including the new
        # prefill-chunk site) + periodic pool exhaustion; streams stay
        # byte-identical and the program budget holds
        from tensorframes_tpu.utils import chaos
        old = (get_config().max_retries, get_config().retry_backoff_s)
        set_config(
            max_retries=3, retry_backoff_s=0.001,
            chaos=(
                "seed=7;serve.prefill=transient:p=0.1;"
                "serve.prefill_chunk=transient:p=0.1;"
                "serve.decode_step=transient:p=0.1;"
                "kv_pages.alloc=pool:every=13"
            ),
        )
        try:
            rng = np.random.default_rng(15)
            sys_prompt = _prompts(rng, [12])[0]
            prompts = [
                sys_prompt + _prompts(rng, [5])[0] for _ in range(5)
            ]
            eng = GenerationEngine(
                lm, max_slots=3, page_size=4, max_seq_len=48,
                num_pages=20, prefix_cache=True, prefill_chunk_tokens=4,
                queue_capacity=8,
            )
            outs = eng.generate(prompts, 8)
        finally:
            set_config(
                max_retries=old[0], retry_backoff_s=old[1], chaos=""
            )
        for p, o in zip(prompts, outs):
            assert np.array_equal(o, _solo(lm, p, 8))
        assert eng.num_step_programs <= 3
        assert chaos.active_spec() == ""
