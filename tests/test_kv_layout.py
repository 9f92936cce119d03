"""The KV pool's device layout and its accessors (``serve/kv_pages.py``).

The pool stores ``[n_layers, num_pages + 1, page_size, n_kv * head_dim]``:
heads merged into the lane axis, so that the TPU's (8, 128) tiles fit the
two minor dimensions and the step programs use the buffer in place. The
contract pinned here, on the CPU:

- every accessor — write rows, write a prompt, read pages, copy a page,
  defragment, take/put pages — agrees with a plain
  ``[L, pages, page_size, n_kv, hd]`` numpy model, at GPT-2 XL's
  (25, 64), a toy (4, 16) and a grouped-query shape;
- the decode read on the merged lanes (``ops.paged_attention``, and the
  fused kernel in interpret mode) equals per-head dense attention;
- decode through the pool equals ``transformer_generate``'s dense cache;
- the lowered decode and prefill programs hold no ``transpose`` of the
  pool, a layer of it or the gathered block, and alias the pool's inputs
  to their outputs (the optimised-HLO form of the same property is the
  chip's: ``chip_smoke.py`` phase ``pool_layout``).
"""

import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorframes_tpu.models import TransformerLM
from tensorframes_tpu.ops import paged_attention, ragged_paged_attention
from tensorframes_tpu.serve import GenerationEngine, PagePool, SequencePages
from tensorframes_tpu.serve.kv_pages import (
    kv_pool_shape,
    read_pages,
    split_heads,
    write_prompt,
    write_rows,
)

#: (n_kv, hd, group): GPT-2 XL's widths, a toy, and a grouped-query shape
SHAPES = [(25, 64, 1), (4, 16, 1), (2, 32, 4)]
SHAPE_IDS = ["xl-25x64", "toy-4x16", "gqa-2x32x4"]
L, PAGES, PS = 2, 9, 4


@pytest.fixture
def rng():
    return np.random.default_rng(26)


def _pool(n_kv, hd, num_pages=PAGES, page_size=PS, n_layers=L):
    return PagePool(
        n_layers=n_layers, n_kv_heads=n_kv, head_dim=hd,
        num_pages=num_pages, page_size=page_size,
    )


def _model(rng, n_kv, hd, num_pages=PAGES, page_size=PS, n_layers=L):
    """A plain numpy pool in the LOGICAL geometry, trash row included."""
    return rng.normal(
        size=(n_layers, num_pages + 1, page_size, n_kv, hd)
    ).astype(np.float32)


def _stored(model):
    return jnp.asarray(model.reshape(model.shape[:3] + (-1,)))


def _logical(arr, n_kv, hd):
    return np.asarray(arr).reshape(arr.shape[:3] + (n_kv, hd))


@pytest.mark.parametrize("n_kv,hd,group", SHAPES, ids=SHAPE_IDS)
class TestAccessorsAgainstPlainModel:
    def test_shape_is_heads_merged_into_lanes(self, n_kv, hd, group):
        pool = _pool(n_kv, hd)
        want = (L, PAGES + 1, PS, n_kv * hd)
        assert kv_pool_shape(L, PAGES, PS, n_kv, hd) == want
        assert pool.k.shape == want and pool.v.shape == want
        assert pool.k.dtype == jnp.float32
        g = pool.add_group("draft", n_layers=1, n_kv_heads=1, head_dim=hd)
        assert g.k.shape == (1, PAGES + 1, PS, hd)

    def test_write_rows_one_token_per_slot(self, rng, n_kv, hd, group):
        model = _model(rng, n_kv, hd)
        page = np.asarray([3, 0, PAGES, 7], np.int32)  # one slot on trash
        off = np.asarray([1, 3, 0, 2], np.int32)
        rows = rng.normal(size=(4, n_kv, hd)).astype(np.float32)
        got = jax.jit(write_rows, static_argnums=1)(
            _stored(model), 1, page, off, rows
        )
        model[1, page, off] = rows
        np.testing.assert_array_equal(_logical(got, n_kv, hd), model)

    def test_write_rows_span_per_slot(self, rng, n_kv, hd, group):
        # the verify / draft shape: [slots, span] indices, merged rows
        model = _model(rng, n_kv, hd)
        page = np.asarray([[1, 1, 2], [5, 6, PAGES]], np.int32)
        off = np.asarray([[2, 3, 0], [3, 0, 1]], np.int32)
        rows = rng.normal(size=(2, 3, n_kv * hd)).astype(np.float32)
        got = write_rows(_stored(model), 0, page, off, rows)
        model[0, page, off] = rows.reshape(2, 3, n_kv, hd)
        np.testing.assert_array_equal(_logical(got, n_kv, hd), model)

    @pytest.mark.parametrize("length", [1, 3, 4, 9, 12, 14])
    def test_write_prompt_equals_row_writes(self, rng, n_kv, hd, group,
                                            length):
        # 14 positions over a 4-page table: whole pages go in as slabs,
        # the page the prompt ends in row by row, the rest to the trash
        # page — and nothing past `length` reaches a real page
        plen = 14
        model = _model(rng, n_kv, hd)
        table = np.asarray([6, 2, 8, 0], np.int32)
        rows = rng.normal(size=(plen, n_kv, hd)).astype(np.float32)
        got = jax.jit(write_prompt, static_argnums=(1, 5))(
            _stored(model), 1, table, np.int32(length), rows, PAGES
        )
        pos = np.arange(length)
        want = model.copy()
        want[1, table[pos // PS], pos % PS] = rows[:length]
        got = _logical(got, n_kv, hd)
        np.testing.assert_array_equal(got[:, :PAGES], want[:, :PAGES])

    def test_read_pages_in_position_order(self, rng, n_kv, hd, group):
        model = _model(rng, n_kv, hd)
        tables = np.asarray([[4, 1, 7], [0, PAGES, PAGES]], np.int32)
        got = jax.jit(read_pages, static_argnums=1)(
            _stored(model), 1, tables
        )
        assert got.shape == (2, 3 * PS, n_kv * hd)
        want = model[1][tables].reshape(2, 3 * PS, n_kv, hd)
        np.testing.assert_array_equal(
            np.asarray(split_heads(got, hd)), want
        )
        one = read_pages(_stored(model), 0, tables[0])  # a 1-D table
        np.testing.assert_array_equal(
            np.asarray(split_heads(one, hd)),
            model[0][tables[0]].reshape(3 * PS, n_kv, hd),
        )

    def test_copy_page_carries_every_group(self, rng, n_kv, hd, group):
        pool = _pool(n_kv, hd)
        g = pool.add_group("draft", n_layers=1, n_kv_heads=1, head_dim=3)
        model = _model(rng, n_kv, hd)
        gmodel = _model(rng, 1, 3, n_layers=1)
        pool.put_pages(np.arange(PAGES + 1), model, -model)
        g.put_pages(np.arange(PAGES + 1), gmodel, gmodel)
        pool.copy_page(2, 5)
        model[:, 5] = model[:, 2]
        gmodel[:, 5] = gmodel[:, 2]
        np.testing.assert_array_equal(_logical(pool.k, n_kv, hd), model)
        np.testing.assert_array_equal(_logical(pool.v, n_kv, hd), -model)
        np.testing.assert_array_equal(_logical(g.k, 1, 3), gmodel)

    def test_take_and_put_pages_round_trip(self, rng, n_kv, hd, group):
        pool = _pool(n_kv, hd)
        model = _model(rng, n_kv, hd)
        pool.put_pages(np.arange(PAGES + 1), model, 2 * model)
        rows = np.asarray([7, 0, 3], np.int32)
        k, v = pool.take_pages(rows)
        # the logical geometry a tier snapshot carries
        assert k.shape == (L, 3, PS, n_kv, hd)
        np.testing.assert_array_equal(np.asarray(k), model[:, rows])
        np.testing.assert_array_equal(np.asarray(v), 2 * model[:, rows])
        other = _pool(n_kv, hd)
        other.put_pages(np.asarray([1, 2, 8]), np.asarray(k), np.asarray(v))
        np.testing.assert_array_equal(
            _logical(other.k, n_kv, hd)[:, [1, 2, 8]], model[:, rows]
        )
        assert float(jnp.abs(other.k[:, 0]).max()) == 0.0

    def test_defragment_moves_rows_with_their_pages(self, rng, n_kv, hd,
                                                    group):
        pool = _pool(n_kv, hd)
        a, b = SequencePages(pool), SequencePages(pool)
        a.ensure(2 * PS)
        b.ensure(3 * PS)
        model = _model(rng, n_kv, hd)
        pool.put_pages(np.arange(PAGES + 1), model, -model)
        held = list(b.pages)
        a.release()
        remap = pool.defragment([b])
        assert b.pages == [remap[p] for p in held] == [0, 1, 2]
        got = _logical(pool.k, n_kv, hd)
        for old, new in remap.items():
            np.testing.assert_array_equal(got[:, new], model[:, old])
        np.testing.assert_array_equal(  # trash stays trash
            got[:, PAGES], model[:, PAGES]
        )
        pool.reset()
        assert pool.k.shape == kv_pool_shape(L, PAGES, PS, n_kv, hd)
        assert float(jnp.abs(pool.k).max()) == 0.0


def _dense_read(q, k_model, v_model, tables, lengths):
    """Per-head softmax attention from the LOGICAL pool, in float64."""
    slots, n_kv, group, hd = q.shape
    out = np.zeros(q.shape, np.float64)
    for s in range(slots):
        k = k_model[tables[s]].reshape(-1, n_kv, hd)[: lengths[s]]
        v = v_model[tables[s]].reshape(-1, n_kv, hd)[: lengths[s]]
        for h in range(n_kv):
            sc = q[s, h].astype(np.float64) @ k[:, h].T.astype(np.float64)
            sc = sc / math.sqrt(hd)
            p = np.exp(sc - sc.max(axis=-1, keepdims=True))
            p /= p.sum(axis=-1, keepdims=True)
            out[s, h] = p @ v[:, h].astype(np.float64)
    return out


@pytest.mark.parametrize("n_kv,hd,group", SHAPES, ids=SHAPE_IDS)
class TestDecodeReadOnMergedLanes:
    def _case(self, rng, n_kv, hd, group, mp=3):
        lengths = np.asarray([1, PS, PS + 1, mp * PS], np.int32)
        slots = len(lengths)
        q = rng.normal(size=(slots, n_kv, group, hd)).astype(np.float32)
        k_model = _model(rng, n_kv, hd)
        v_model = _model(rng, n_kv, hd)
        tables = rng.integers(0, PAGES, size=(slots, mp)).astype(np.int32)
        return q, k_model, v_model, tables, lengths

    def test_gather_read_is_per_head_attention(self, rng, n_kv, hd, group):
        q, km, vm, tables, lengths = self._case(rng, n_kv, hd, group)
        want = _dense_read(q, km[1], vm[1], tables, lengths)
        got = paged_attention(
            q, _stored(km), _stored(vm), tables, lengths, layer=1
        )
        assert got.shape == q.shape
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                                   atol=2e-5)
        # a single layer's array, no layer index: the same read
        solo = paged_attention(
            q, _stored(km)[1], _stored(vm)[1], tables, lengths
        )
        np.testing.assert_allclose(
            np.asarray(solo), np.asarray(got), rtol=1e-6, atol=1e-6
        )

    def test_fused_kernel_agrees_with_gather(self, rng, n_kv, hd, group):
        q, km, vm, tables, lengths = self._case(rng, n_kv, hd, group)
        ref = paged_attention(
            q, _stored(km), _stored(vm), tables, lengths, layer=0
        )
        got = ragged_paged_attention(
            q, _stored(km), _stored(vm), tables, lengths, layer=0,
            interpret=True,
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestDecodeThroughThePool:
    @pytest.fixture(scope="class")
    def lm(self):
        # grouped-query, head_dim 8: n_kv * hd = 16 lanes per row
        return TransformerLM.init(
            3, 61, d_model=32, n_heads=4, n_layers=2, max_len=48,
            n_kv_heads=2,
        )

    @pytest.mark.parametrize("impl", ["gather", "fused"])
    def test_streams_equal_the_dense_cache(self, lm, impl):
        rng = np.random.default_rng(5)
        prompts = [
            rng.integers(1, 61, size=n).astype(np.int32).tolist()
            for n in (3, 9, 17)
        ]
        eng = GenerationEngine(
            lm, max_slots=2, page_size=4, max_seq_len=48,
            attention_impl=impl,
        )
        assert eng.pool.k.shape == (2, eng.pool.num_pages + 1, 4, 2 * 8)
        handles = [eng.submit(p, 10) for p in prompts]
        eng.run_until_idle()
        for p, h in zip(prompts, handles):
            solo = np.asarray(lm.generate(np.asarray([p]), 10))[0, len(p):]
            np.testing.assert_array_equal(h.result(timeout=1), solo)
        assert eng.pool.pages_in_use == 0

    def test_logits_agree_to_float_tolerance(self, lm):
        """One decode step's context through the pool against the dense
        read of the same rows: the tolerance ``test_paged_attention``
        holds the fused kernel to."""
        rng = np.random.default_rng(6)
        n_kv, hd, group, ps, mp = 2, 8, 2, 4, 3
        pool = _pool(n_kv, hd, num_pages=6, page_size=ps, n_layers=1)
        lengths = np.asarray([5, 12], np.int32)
        tables = np.asarray([[4, 0, 6], [1, 5, 2]], np.int32)
        k, v = pool.k, pool.v
        rows_k = rng.normal(size=(2, 12, n_kv, hd)).astype(np.float32)
        rows_v = rng.normal(size=(2, 12, n_kv, hd)).astype(np.float32)
        for s in range(2):
            k = write_prompt(k, 0, tables[s], lengths[s], rows_k[s], 6)
            v = write_prompt(v, 0, tables[s], lengths[s], rows_v[s], 6)
        q = rng.normal(size=(2, n_kv, group, hd)).astype(np.float32)
        got = paged_attention(q, k, v, tables, lengths, layer=0)
        for s in range(2):
            n = lengths[s]
            sc = np.einsum("kgd,tkd->kgt", q[s], rows_k[s, :n])
            sc = sc / math.sqrt(hd)
            p = np.exp(sc - sc.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            want = np.einsum("kgt,tkd->kgd", p, rows_v[s, :n])
            np.testing.assert_allclose(np.asarray(got[s]), want,
                                       rtol=2e-5, atol=2e-5)


# ------------------------------------------------ the programs, lowered


def _tensor_elements(text):
    return [
        math.prod(int(d) for d in dims.split("x") if d.isdigit())
        for dims in re.findall(r"tensor<([\dx]+)x[a-z]", text)
    ]


def _lowered(eng):
    s, mp = eng.max_slots, eng._max_pages
    decode = eng._decode_jit.lower(
        eng._params_dev, eng.pool.k, eng.pool.v,
        np.zeros(s, np.int32), np.zeros(s, np.int32),
        np.zeros((s, mp), np.int32), np.zeros(s, np.float32),
        np.zeros(s, np.int32), np.ones(s, np.float32),
    )
    prefill = eng._prefill_jit.lower(
        eng._params_dev, eng.pool.k, eng.pool.v,
        np.zeros((1, eng.max_seq_len), np.int32), np.int32(3),
        np.zeros(mp, np.int32), np.float32(0), np.int32(0), np.float32(1),
    )
    return {"decode": decode, "prefill": prefill}


class TestLoweredStepPrograms:
    @pytest.fixture(scope="class")
    def eng(self):
        # 5 heads of 8: like 25 of 64, a head count that is no multiple
        # of the sublane tile. 40 pages: a layer of the pool, the
        # gathered block and the prompt's own [P, n_kv * hd] rows all
        # differ in size, so a transpose of one cannot hide as another
        lm = TransformerLM.init(
            0, 67, d_model=40, n_heads=5, n_layers=2, max_len=32
        )
        return GenerationEngine(
            lm, max_slots=2, page_size=4, max_seq_len=32, num_pages=40
        )

    @pytest.mark.parametrize("program", ["decode", "prefill"])
    def test_no_transpose_of_pool_layer_or_gathered_block(self, eng,
                                                          program):
        whole = math.prod(eng.pool.k.shape)
        layer = whole // eng.pool.k.shape[0]
        block = eng.max_slots * eng._max_pages * 4 * 40
        sizes = {whole, layer, block}
        assert 32 * 40 not in sizes  # prefill's own rows are not suspect
        text = _lowered(eng)[program].as_text()
        assert "stablehlo.gather" in text or program == "prefill"
        for line in text.splitlines():
            if "stablehlo.transpose" in line:
                moved = sizes & set(_tensor_elements(line))
                assert not moved, line.strip()[:300]

    @pytest.mark.parametrize("program", ["decode", "prefill"])
    def test_pool_inputs_alias_pool_outputs(self, eng, program):
        text = _lowered(eng)[program].as_text()
        main = next(
            l for l in text.splitlines() if "func.func public @main" in l
        )
        # arguments in order: the params' leaves, then k, then v; the
        # program's results in order: k, v, the sampled tokens
        pool_t = "tensor<" + "x".join(map(str, eng.pool.k.shape)) + "xf32>"
        donated = re.findall(
            re.escape(pool_t) + r" \{[^}]*tf\.aliasing_output = (\d+)",
            main,
        )
        assert sorted(donated) == ["0", "1"], main[:500]

    def test_decode_read_never_splits_the_lanes(self, eng):
        """The gathered block ``[slots, T, n_kv * hd]`` reaches the score
        product as it was gathered: no reshape of a block-sized tensor
        to a ``[..., n_kv, hd]`` shape anywhere in the decode program."""
        block = eng.max_slots * eng._max_pages * 4 * 40
        text = _lowered(eng)["decode"].as_text()
        for line in text.splitlines():
            if "stablehlo.reshape" in line and block in _tensor_elements(
                line
            ):
                assert "x5x8x" not in line.split("->")[-1], line.strip()


# ------------------------------------- chip_smoke's optimised-HLO reader


def _chip_smoke():
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "chip_smoke.py",
    )
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_HLO = """HloModule jit_decode

%fused_copy (p: f32[177,16,1600]) -> bf16[177,16,1600] {
  %p = f32[177,16,1600]{2,1,0} parameter(0)
  %s = f32[177,16,1600]{2,1,0} slice(%p), slice={[0:177], [0:16], [0:1600]}
  ROOT %c = bf16[177,16,1600]{2,1,0} convert(%s)
}

%fused_work (p: f32[8,1024,1600]) -> f32[8,1024,1600] {
  %p = f32[8,1024,1600]{2,1,0} parameter(0)
  ROOT %m = f32[8,1024,1600]{2,1,0} multiply(%p, %p)
}

ENTRY %main (a: f32[48,177,16,1600], b: f32[8,1024,1600]) -> f32[8] {
  %a = f32[48,177,16,1600]{3,2,1,0} parameter(0)
  %b = f32[8,1024,1600]{2,1,0} parameter(1)
  %copy.1 = f32[48,177,16,1600]{1,3,2,0:T(8,128)} copy(%a)
  %fusion.1 = bf16[177,16,1600]{2,1,0} fusion(%a), kind=kLoop, calls=%fused_copy
  %fusion.2 = f32[8,1024,1600]{2,1,0} fusion(%b), kind=kLoop, calls=%fused_work
  %transpose.1 = f32[8,1600,1024]{2,1,0} transpose(%b), dimensions={0,2,1}
  %copy.2 = f32[8,25]{1,0} copy(%small)
  ROOT %r = f32[8]{0} custom-call(%fusion.2)
}
"""


def test_pool_relayouts_reads_optimised_hlo():
    found = _chip_smoke().pool_relayouts(
        _HLO, (48 * 177 * 16 * 1600, 177 * 16 * 1600, 8 * 1024 * 1600)
    )
    assert found == [
        ("copy", "f32[48,177,16,1600]"),
        ("fusion", "bf16[177,16,1600]"),
        ("transpose", "f32[8,1600,1024]"),
    ]
    # a program that only works on arrays of those sizes is clean
    clean = "\n".join(
        l for l in _HLO.splitlines()
        if not any(k in l for k in ("%copy.1", "%fusion.1", "%transpose.1"))
    )
    assert _chip_smoke().pool_relayouts(clean, (8 * 1024 * 1600,)) == []


_CHUNK_HLO = """HloModule jit_chunk_step

%fused_scores (p: bf16[1,1024,4,8,128]) -> f32[1,1024,4,8,1056] {
  %p = bf16[1,1024,4,8,128]{4,3,2,1,0} parameter(0)
  ROOT %s = f32[1,1024,4,8,1056]{4,3,2,1,0} convolution(%p, %p)
}

%fused_block_copy (p: bf16[1,1056,512]) -> bf16[1,1056,4,128] {
  %p = bf16[1,1056,512]{2,1,0} parameter(0)
  ROOT %r = bf16[1,1056,4,128]{3,2,1,0} reshape(%p)
}

%walk_body (w: (s32[], f32[1,1024,32], f32[1,1024,4096])) -> (s32[], f32[1,1024,32], f32[1,1024,4096]) {
  %w = (s32[], f32[1,1024,32]{2,1,0}, f32[1,1024,4096]{2,1,0}) parameter(0)
  %g = bf16[1,1056,512]{2,1,0} gather(%pool, %rows)
  %fold = (f32[1,1024,32]{2,1,0}, f32[1,1024,32]{2,1,0}, f32[1,1024,4096]{2,1,0}) custom-call(%g), custom_call_target="tpu_custom_call"
  ROOT %t = (s32[], f32[1,1024,32]{2,1,0}, f32[1,1024,4096]{2,1,0}) tuple(%i, %m, %acc)
}

%old_body (w: (s32[], f32[1,1024,4,8])) -> (s32[], f32[1,1024,4,8]) {
  %w = (s32[], f32[1,1024,4,8]{3,2,1,0}) parameter(0)
  %g = bf16[1,1056,512]{2,1,0} gather(%pool, %rows)
  %retiled = bf16[1,1056,4,128]{3,2,1,0} fusion(%g), kind=kLoop, calls=%fused_block_copy
  %scores = f32[1,1024,4,8,1056]{4,3,2,1,0} fusion(%q), kind=kOutput, calls=%fused_scores
  %m = f32[1,1024,4,8]{3,2,1,0} reduce(%scores, %neg), dimensions={4}, to_apply=%max
  ROOT %t = (s32[], f32[1,1024,4,8]{3,2,1,0}) tuple(%i, %m)
}

ENTRY %main (pool: bf16[3,16385,16,512]) -> f32[1,1024,4096] {
  %pool = bf16[3,16385,16,512]{3,2,1,0} parameter(0)
  %q = f32[1,1024,4,8,128]{4,3,2,1,0} fusion(%x), kind=kLoop, calls=%rotary
  %while.1 = (s32[], f32[1,1024,32]{2,1,0}, f32[1,1024,4096]{2,1,0}) while(%init), condition=%cond, body=%walk_body
  ROOT %out = f32[1,1024,4096]{2,1,0} get-tuple-element(%while.1), index=2
}
"""


def test_the_chunk_guard_reads_loop_bodies_and_not_fusion_insides():
    smoke = _chip_smoke()
    arrays = smoke.hlo_arrays(_CHUNK_HLO)
    shapes = [shape for _, shape in arrays]
    # a loop body's gather is an array in memory, a fusion's inside is not
    assert "bf16[1,1056,512]" in shapes
    assert ("convolution", "f32[1,1024,4,8,1056]") not in arrays
    leaks = smoke.span_attention_leaks(arrays, 1024, 4, 8, (1040, 1056))
    assert leaks == [
        ("fusion", "f32[1,1024,4,8,1056]"), ("reduce", "f32[1,1024,4,8]"),
    ]
    # the same module without the walk that keeps its scores in memory
    # (and the two fusions only it calls)
    cut = lambda text, a, b: text.replace(text[text.index(a):text.index(b)], "")
    clean = cut(
        cut(_CHUNK_HLO, "%fused_scores", "%walk_body"), "%old_body", "ENTRY"
    )
    # the fold's dense carry and the query's own [.., 4, 8, 128] are fine
    assert smoke.span_attention_leaks(
        smoke.hlo_arrays(clean), 1024, 4, 8, (1040, 1056)
    ) == []
    block = (1056 * 512,)
    assert smoke.pool_relayouts(_CHUNK_HLO, block) == []  # entry only
    assert smoke.pool_relayouts(_CHUNK_HLO, block, loops=True) == [
        ("fusion", "bf16[1,1056,4,128]")
    ]
    assert smoke.pool_relayouts(clean, block, loops=True) == []
