"""The rotary / RMS / gated-expert block with window and full layers, at a
small size on the CPU: the serving engine against the plain reference of
``chipbench/models/mellum.py`` (which shares no code with the package) on
seeded weights, and the parts it is made of — the model description, the
rotary frequencies, the grouped expert path against ``moe_ffn``, the cache
kinds, the live-bounded paged read."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.models import mellum  # noqa: E402
from tensorframes_tpu.models import transformer as tr  # noqa: E402
from tensorframes_tpu.ops import attention  # noqa: E402
from tensorframes_tpu.ops.attention import (  # noqa: E402
    live_read_positions,
    live_read_trips,
    paged_attention_live,
)
from tensorframes_tpu.parallel.moe import (  # noqa: E402
    init_moe,
    moe_ffn,
    moe_grouped,
)
from tensorframes_tpu.serve import GenerationEngine  # noqa: E402
from tensorframes_tpu.serve.kv_pages import (  # noqa: E402
    CacheLayout,
    PagePool,
    SequencePages,
)

YARN = {
    "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
    "original_max_position_embeddings": 8192, "beta_fast": 32,
    "beta_slow": 1, "attention_factor": 1.2772588722239782,
}
PUBLISHED = {
    "head_dim": 128,
    "rope_parameters": {
        "full_attention": YARN,
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    },
}
TINY = {
    "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2,
    "head_dim": 16, "num_hidden_layers": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "moe_intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 2,
    "rms_norm_eps": 1e-6, "sliding_window": 32, "vocab_size": 256,
    "tie_word_embeddings": False, "n_positions": 256,
    "rope_parameters": PUBLISHED["rope_parameters"],
}


@pytest.fixture(scope="module")
def params():
    return mellum.init_params(3, TINY, "float32")


def gaps(params, prompts, outs):
    """How far each served token's reference logit lies below the
    reference's best at its position (float32 reference, the whole
    sequence forward, no cache)."""
    import jax.numpy as jnp

    tokens = np.zeros((len(prompts), TINY["n_positions"]), np.int32)
    rows, cols, served = [], [], []
    for i, (p, o) in enumerate(zip(prompts, outs)):
        seq = list(p) + list(o[:-1])
        tokens[i, : len(seq)] = seq
        for j, t in enumerate(o):
            rows.append(i), cols.append(len(p) - 1 + j), served.append(t)
    ref = mellum.reference_logits(
        params, TINY, tokens, np.asarray(rows), np.asarray(cols), "float32"
    )
    got = jnp.take_along_axis(ref, jnp.asarray(served)[:, None], -1)[:, 0]
    return np.asarray(jnp.max(ref, -1) - got)


def serve(params, prompts, new_tokens, **engine):
    geometry = dict(
        max_slots=4, page_size=16, num_pages=64, max_seq_len=256,
        queue_capacity=16, prefill_chunk_tokens=32,
    )
    geometry.update(engine)
    eng = GenerationEngine(params, **geometry)
    handles = [
        eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, new_tokens)
    ]
    eng.run_until_idle()
    return eng, handles, [h.result().tolist() for h in handles]


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, size=n).tolist() for n in lengths]


# ------------------------------------------------- engine against reference

CASES = {
    # prompt + generation runs far past the window of 32: window pages
    # are released under the sequence while it decodes
    "decode_across_the_window": dict(lengths=(100, 17, 70, 5), new=(40, 12, 30, 8)),
    # one chunk, several chunks, a chunk that ends on a page boundary
    "chunked_prefill": dict(lengths=(31, 32, 33, 130, 64), new=(4, 4, 4, 4, 4)),
    # a pool too small for all four: the youngest is preempted, requeued
    # and recomputed, its stream unbroken
    "recompute_after_preemption": dict(
        lengths=(90, 80, 70, 60), new=(60, 60, 60, 60), num_pages=48,
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_agrees_with_the_reference_at_logit_level(params, case):
    spec = dict(CASES[case])
    prompts = prompts_of(spec.pop("lengths"))
    eng, handles, outs = serve(params, prompts, spec.pop("new"), **spec)
    # the token the engine served is the reference's own first choice, or
    # within float32 rounding of it, at every position
    assert gaps(params, prompts, outs).max() < 1e-3
    assert eng.pool.pages_in_use == 0
    assert eng.num_step_programs == 2  # chunk and decode, nothing else
    preempted = sum(h.timings.get("preemptions", 0) for h in handles)
    assert (preempted > 0) == (case == "recompute_after_preemption")
    if case == "recompute_after_preemption":
        assert any(h.timings.get("recomputed_tokens", 0) for h in handles)


@pytest.mark.parametrize("chunk", [16, 48, 256])
def test_chunked_prefill_serves_what_whole_prefill_serves(params, chunk):
    prompts = prompts_of((130, 47))
    _, _, whole = serve(params, prompts, (6, 6), prefill_chunk_tokens=256)
    _, _, parts = serve(params, prompts, (6, 6), prefill_chunk_tokens=chunk)
    assert parts == whole


def test_walks_agree_with_the_reference_on_whole_sequences(params):
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    tokens = rng.integers(0, 256, (2, 128)).astype(np.int32)
    ours = tr.transformer_logits(params, jnp.asarray(tokens))
    rows = np.repeat(np.arange(2), 128)
    cols = np.tile(np.arange(128), 2)
    ref = mellum.reference_logits(params, TINY, tokens, rows, cols, "float32")
    np.testing.assert_allclose(
        np.asarray(ours).reshape(-1, 256), np.asarray(ref), atol=2e-4
    )
    # the scan decode walks the same block, one token at a time
    lm = tr.TransformerLM(params)
    out = lm.generate(tokens[:, :40], 8)
    assert gaps(params, tokens[:, :40].tolist(), out[:, 40:].tolist()).max() < 1e-3


def test_page_accounting_of_both_kinds_returns_to_zero(params):
    eng, _, _ = serve(params, prompts_of((100, 40, 9)), (30, 30, 30))
    pool = eng.pool
    assert pool.pages_in_use == 0
    assert pool.kind_in_use == {"full": 0, "window": 0}
    assert pool.kind_released["window"] > 0
    assert pool.kind_allocated["window"] > pool.kind_released["window"]
    assert "full" not in pool.kind_released  # full layers keep every page


def test_decode_span_and_counters_carry_routing_and_cache_kinds(params):
    import io
    import json

    from tensorframes_tpu import obs

    sink = io.StringIO()
    obs.set_trace_sink(sink)
    try:
        serve(params, prompts_of((100, 20)), (12, 12))
    finally:
        obs.set_trace_sink(None)
    events = [json.loads(l) for l in sink.getvalue().splitlines() if l.strip()]
    steps = [e["attrs"] for e in events if e["name"] == "serve.decode_step"]
    chunks = [e["attrs"] for e in events if e["name"] == "serve.prefill_chunk"]
    assert steps and chunks
    for a in steps:
        assert 1 <= a["experts_hit"] <= TINY["num_experts"]
        assert a["expert_load_max_over_mean"] >= 1.0
        for kind in ("full", "window"):
            assert a[f"kv_tokens_read_{kind}"] >= a[f"kv_tokens_live_{kind}"] > 0
        assert a["kv_read_amplification"] >= 1.0
    for a in chunks:
        assert 0.0 <= a["pad_share"] < 1.0 and a["tokens"] > 0
        assert a["tokens_routed"] == a["tokens"] * 2 * 4  # top-2, 4 layers
    snap = obs.registry().snapshot()
    assert sum(snap["moe.tokens_routed_total"]["values"].values()) > 0
    assert sum(snap["serve.window_pages_released_total"]["values"].values()) > 0


# ------------------------------------------------------ model description


def test_model_description_of_a_gpt2_tree_and_of_a_described_tree(params):
    lm = tr.init_transformer(0, 64, d_model=32, n_heads=4, n_kv_heads=2, max_len=48)
    spec = tr.model_spec(lm)
    assert (spec.n_heads, spec.n_kv_heads, spec.head_dim) == (4, 2, 8)
    assert (spec.norm, spec.position, spec.mlp, spec.tied_head) == (
        "layer", "learned", "gelu", True,
    )
    assert spec.max_len == 48 and spec.kinds == ("full",)
    spec = tr.model_spec(params)
    assert spec.head_dim * spec.n_heads != TINY["hidden_size"]  # 128 != 64
    assert spec.kinds == ("full", "window") and spec.layer_window(0) == 32
    assert spec.layer_window(3) == 0 and spec.rope_of(3).kind == "yarn"
    assert hash(spec) == hash(tr.model_spec(params))
    eng = GenerationEngine(params, max_slots=2, page_size=16, num_pages=32)
    assert eng.spec == spec and eng.max_seq_len == 256
    assert eng._long and eng.prefill_chunk_tokens % 16 == 0


@pytest.mark.parametrize("who", ["program", "reference"])
def test_rotary_frequencies_match_hand_worked_values(who):
    if who == "reference":
        assert mellum.yarn_range(PUBLISHED) == (18, 35)
        full, factor = mellum.inv_freq(PUBLISHED, "full_attention")
        plain, one = mellum.inv_freq(PUBLISHED, "sliding_attention")
    else:
        rope = tr.RopeSpec(
            theta=500000.0, kind="yarn", factor=16.0,
            original_max_position=8192, beta_fast=32.0, beta_slow=1.0,
            attention_factor=YARN["attention_factor"],
        )
        full, factor = tr.rope_inv_freq(rope, 128), rope.attention_factor
        plain, one = tr.rope_inv_freq(tr.RopeSpec(theta=500000.0), 128), 1.0
    assert factor == pytest.approx(0.1 * np.log(16) + 1.0) and one == 1.0
    extrap = lambda i: 500000.0 ** (-2.0 * i / 128)
    np.testing.assert_allclose(plain, [extrap(i) for i in range(64)], rtol=1e-6)
    # below dimension 18 plain, above 35 divided by 16, a line between
    np.testing.assert_allclose(full[:19], [extrap(i) for i in range(19)], rtol=1e-6)
    np.testing.assert_allclose(
        full[35:], [extrap(i) / 16 for i in range(35, 64)], rtol=1e-6
    )
    r = (26 - 18) / (35 - 18)
    assert full[26] == pytest.approx(extrap(26) * (r / 16 + 1 - r), rel=1e-6)


# ----------------------------------------------------- grouped expert path


def routed_inputs(case, tokens):
    """A SiLU-gated expert layer (``init_moe``'s shapes and a gate
    matrix) and inputs whose routing is known: tokens are scaled basis
    vectors and the router a scaled identity, so token ``i`` prefers
    expert ``i % 8``."""
    p = init_moe(1, 8, 16, 8)
    rng = np.random.default_rng(2)
    if case == "even":
        router = (8.0 * np.eye(8)).astype(np.float32)
        x = np.eye(8, dtype=np.float32)[np.arange(tokens) % 8]
    elif case == "one_expert":
        router = np.zeros((8, 8), np.float32)
        router[:, 3] = 4.0
        x = np.abs(rng.normal(size=(tokens, 8))).astype(np.float32)
    else:  # experts 0 and 1 take everything at k = 2, six get no token
        router = np.zeros((8, 8), np.float32)
        router[:, 0], router[:, 1] = 4.0, 3.0
        x = np.abs(rng.normal(size=(tokens, 8))).astype(np.float32)
    gate = np.random.default_rng(4).normal(size=p["w_up"].shape)
    p = {
        "router": router, "w_up": p["w_up"], "w_down": p["w_down"],
        "w_gate": (0.3 * gate).astype(np.float32),
    }
    return p, (x + 0.01 * rng.normal(size=x.shape).astype(np.float32))


@pytest.mark.parametrize("tokens", [32, 88])
@pytest.mark.parametrize("case", ["even", "one_expert", "empty_experts"])
def test_grouped_expert_path_agrees_with_the_masked_oracle(case, tokens):
    p, x = routed_inputs(case, tokens)
    k = 2 if case == "empty_experts" else 1
    want = moe_ffn(p, x[None], k=k)[0]
    got, counts = moe_grouped(p, x, k=k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    counts = np.asarray(counts)
    assert counts.sum() == tokens * k  # no pair dropped
    if case == "even":
        assert (counts == tokens // 8).all()
    elif case == "one_expert":
        assert counts[3] == tokens
    else:
        assert counts[0] == counts[1] == tokens and counts[2:].sum() == 0


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_the_shares_of_an_expert_layer_add_up_to_the_layer(params, shares):
    """Guide section 4's test: the experts divided over ``shares`` chips,
    each told which it holds, every share routing over the router's
    whole width; the parts add up to what the reference's layer gives."""
    import jax.numpy as jnp

    moe = params["blocks"][0]["moe"]
    x = np.random.default_rng(6).normal(size=(24, 64)).astype(np.float32)
    whole, counts = moe_grouped(moe, x, k=2)
    held = 8 // shares
    total = 0.0
    seen = []
    for s in range(shares):
        mine = {
            name: (w if name == "router" else w[s * held : (s + 1) * held])
            for name, w in moe.items()
        }
        part, c = moe_grouped(mine, x, k=2, expert_offset=s * held)
        total = total + part
        seen.extend(np.asarray(c))
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=1e-5)
    assert seen == list(np.asarray(counts))
    oracle = moe_ffn(moe, jnp.asarray(x)[None], k=2)[0]
    np.testing.assert_allclose(np.asarray(whole), np.asarray(oracle), atol=1e-5)


# ------------------------------------------------------------- cache kinds


def test_cache_layout_puts_both_kinds_on_one_pool():
    lay = CacheLayout.of(["window"] * 3 + ["full"] + ["window"] * 3 + ["full"], 32, 16, 32)
    assert lay.depth == 2
    assert [(k.name, k.units, k.window) for k in lay.kinds] == [
        ("full", 1, 0), ("window", 3, 32),
    ]
    assert lay.locate(3) == (0, 0, 0) and lay.locate(7) == (0, 0, 1)
    assert lay.locate(0) == (1, 0, 0) and lay.locate(6) == (1, 2, 1)
    # 400 positions: 25 full pages; the window kind never holds more than
    # the window, the chunk ahead and a page: 5 position-pages of 3
    assert lay.units_needed(400) == 25 + 3 * 5
    with pytest.raises(ValueError):
        CacheLayout.of(["window", "full"], 24, 16)  # not whole pages


def test_window_pages_are_released_behind_the_window():
    lay = CacheLayout.of(["window"] * 3 + ["full"], 32, 16, 32)
    pool = PagePool(lay.depth, 2, 8, 64, 16)
    seq = SequencePages(pool, lay)
    seq.ensure(200)  # admission: every full page, the window's reach
    assert len(seq.pages) == 13 and len(seq.held[1]) == 3 * 5
    held = []
    for start in range(0, 200, 32):  # prefill, a chunk at a time
        seq.advance(start)
        seq.ensure(min(200, start + 32))
        assert seq.capacity >= min(200, start + 32)
        # keys start - 31 .. start + 31 are all on held pages
        assert seq.first[1] * 16 <= max(0, start - 31)
        held.append(len(seq.held[1]) // 3)
    assert max(held) <= 5 and seq.first[1] > 0
    table = seq.table(6, 1)
    assert table.shape == (6, 3) and (table[-1] == pool.trash_page).all()
    seq.release()
    assert pool.pages_in_use == 0 and pool.kind_in_use == {"full": 0, "window": 0}
    # every window page but the last five position-pages went back early
    assert pool.kind_released == {"window": pool.kind_allocated["window"] - 3 * held[-1]}


# ------------------------------------------------------ live-bounded read


def _scattered_pool(rng, lengths, pages, idle, ps=4, n_kv=2, hd=8):
    """Keys and values of ``len(lengths)`` slots, each slot's pages
    scattered through layer 1 of a two-layer pool; the slots of ``idle``
    name the trash page (the pool's last) in every row."""
    slots = len(lengths)
    k = rng.normal(size=(slots, pages * ps, n_kv, hd)).astype(np.float32)
    v = rng.normal(size=k.shape).astype(np.float32)
    trash = slots * pages
    table = rng.permutation(trash).reshape(slots, pages).astype(np.int32)
    pool_k = np.zeros((2, trash + 1, ps, n_kv * hd), np.float32)
    pool_v = np.zeros_like(pool_k)
    for s in range(slots):
        pool_k[1, table[s]] = k[s].reshape(pages, ps, n_kv * hd)
        pool_v[1, table[s]] = v[s].reshape(pages, ps, n_kv * hd)
    # what idle slots and padding wrote there: finite, and never seen
    pool_k[1, trash] = rng.normal(size=(ps, n_kv * hd))
    pool_v[1, trash] = rng.normal(size=(ps, n_kv * hd))
    for s in idle:
        table[s] = trash
        k[s] = pool_k[1, trash].reshape(ps, n_kv, hd)[0]
        v[s] = pool_v[1, trash].reshape(ps, n_kv, hd)[0]
    return k, v, table, pool_k, pool_v


def _dense_attention(q, k, v, q_pos, length, window):
    """One slot's attention the plain way: ``q`` ``[C, n_kv, group, hd]``
    at positions ``q_pos`` ``[C]`` over its first ``length`` keys."""
    import jax
    import jax.numpy as jnp

    pos = np.arange(k.shape[0])
    out = []
    for qi, p_i in zip(q, q_pos):
        seen = (pos <= p_i) & (pos < length)
        if window:
            seen &= pos > p_i - window
        sc = np.einsum("kgd,tkd->kgt", qi, k) / np.sqrt(q.shape[-1])
        sc = np.where(seen[None, None], sc, -np.inf)
        p = np.asarray(jax.nn.softmax(jnp.asarray(sc), -1))
        out.append(np.einsum("kgt,tkd->kgd", p, v).reshape(-1))
    return np.stack(out)


@pytest.mark.parametrize("window", [0, 24])
@pytest.mark.parametrize("block_pages", [2, 64])
def test_live_read_agrees_with_dense_attention(window, block_pages):
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    slots, c, n_kv, group, hd, pages = 3, 5, 2, 2, 8, 12
    lengths = np.asarray([37, 9, 20], np.int32)
    k, v, table, pool_k, pool_v = _scattered_pool(rng, lengths, pages, ())
    q = rng.normal(size=(slots, c, n_kv, group, hd)).astype(np.float32)
    q_pos = (lengths[:, None] - c + np.arange(c)[None]).astype(np.int32)
    got = paged_attention_live(
        jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
        jnp.asarray(table), jnp.zeros(slots, jnp.int32), jnp.asarray(q_pos),
        jnp.asarray(lengths), 1, window=window, block_pages=block_pages,
    )
    for s in range(slots):
        want = _dense_attention(q[s], k[s], v[s], q_pos[s], lengths[s], window)
        np.testing.assert_allclose(np.asarray(got[s]), want, atol=2e-5)


# lengths in slot order (arbitrary, not by length), the group size the
# walk is held to, idle slots (length 1, every row the trash page)
GROUPED = {
    "ragged-8-by-2": dict(lengths=[9, 37, 1, 20, 48, 5, 33, 12], group=2),
    "ragged-8-by-4": dict(lengths=[9, 37, 1, 20, 48, 5, 33, 12], group=4),
    "ragged-32-by-8": dict(
        lengths=[int(x) for x in np.random.default_rng(3).integers(1, 49, 32)],
        group=8,
    ),
    "ragged-32-by-16": dict(
        lengths=[int(x) for x in np.random.default_rng(4).integers(1, 49, 32)],
        group=16,
    ),
    "idle-slots": dict(
        lengths=[1, 30, 1, 1, 44, 1, 17, 1], group=2, idle=(0, 2, 3, 5, 7),
    ),
    "all-idle": dict(lengths=[1] * 8, group=4, idle=tuple(range(8))),
    "one-at-the-full-table": dict(lengths=[3, 6, 48, 2, 7, 4, 5, 1], group=4),
    "all-equal": dict(lengths=[29] * 8, group=2),
}


@pytest.mark.parametrize("window", [0, 24])
@pytest.mark.parametrize("case", sorted(GROUPED))
def test_grouped_walk_gives_each_slot_what_it_had(case, window, monkeypatch):
    """One query a slot over a table of several blocks: every slot's
    result is dense attention over its own keys, and bit for bit what
    the walk with one trip count for the batch gives — whoever shares
    its group."""
    import jax.numpy as jnp

    spec = GROUPED[case]
    lengths = np.asarray(spec["lengths"], np.int32)
    slots, n_kv, group, hd, ps, pages = len(lengths), 2, 2, 8, 4, 12
    rng = np.random.default_rng(11)
    k, v, table, pool_k, pool_v = _scattered_pool(
        rng, lengths, pages, spec.get("idle", ())
    )
    q = rng.normal(size=(slots, 1, n_kv, group, hd)).astype(np.float32)
    q_pos = (lengths - 1)[:, None].astype(np.int32)

    def walk(together):
        monkeypatch.setattr(attention, "LIVE_GROUP_SLOTS", together)
        assert attention.live_read_group(slots) == min(together, slots)
        return np.asarray(paged_attention_live(
            jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(table), jnp.zeros(slots, jnp.int32),
            jnp.asarray(q_pos), jnp.asarray(lengths), 1, window=window,
            block_pages=2,
        ))

    got = walk(spec["group"])
    np.testing.assert_array_equal(got, walk(slots))
    for s in range(slots):
        want = _dense_attention(q[s], k[s], v[s], q_pos[s], lengths[s], window)
        np.testing.assert_allclose(got[s], want, atol=2e-5)


# live keys per slot, span, blocks of the table, group -> trips per group
TRIPS = {
    "descending": ([40, 30, 20, 10, 9, 8, 2, 1], 8, 6, 2, [5, 3, 2, 1]),
    "any-order": ([9, 1, 40, 8, 20, 2, 30, 10], 8, 6, 2, [5, 3, 2, 1]),
    "ties": ([16, 16, 17, 16], 8, 6, 2, [3, 2]),
    "all-idle": ([1] * 8, 8, 6, 4, [1, 1]),
    "longer-than-the-table": ([3, 100, 3, 3], 8, 6, 2, [6, 1]),
    "one-group": ([5, 40, 7], 8, 6, 3, [5]),
    "a-block-exactly": ([8, 16, 9, 24], 8, 6, 1, [3, 2, 2, 1]),
}


@pytest.mark.parametrize("case", sorted(TRIPS))
def test_live_read_trips_counts_each_groups_blocks(case):
    import jax.numpy as jnp

    live, span, n_blocks, group, want = TRIPS[case]
    order, trips = live_read_trips(np.asarray(live), span, n_blocks, group)
    assert trips.tolist() == want
    assert sorted(order.tolist()) == list(range(len(live)))
    assert [live[i] for i in order] == sorted(live, reverse=True)
    # the program's own trip counts: the same function, traced
    _, traced = live_read_trips(
        jnp.asarray(live, jnp.int32), span, n_blocks, group
    )
    assert np.asarray(traced).tolist() == want


@pytest.mark.parametrize(
    "together, want",
    [
        (2, (2 + 1) * 2 * 128),  # {150, 140} both blocks, {20, idle} one
        (4, 2 * 4 * 128),  # one group: everyone walks the longest's two
        (1, (2 + 2 + 1 + 1) * 128),  # each slot its own blocks
    ],
)
def test_live_read_positions_counts_idle_slots_as_one_key(
    together, want, monkeypatch
):
    """Four slots, three live, a 128-row table of two-position pages (two
    blocks of 64 rows)."""
    monkeypatch.setattr(attention, "LIVE_GROUP_SLOTS", together)
    assert live_read_positions([150, 20, 140], 4, 128, 2) == want


def test_decode_span_counts_what_the_grouped_walk_reads(params, monkeypatch):
    """A small live engine whose full layers' table is two blocks wide,
    four slots in groups of two: the span's ``kv_tokens_read_full`` is
    each group's trips times its slots times a block's positions, the
    window kind's count is what it was, and the tokens are those of the
    walk with one trip count for the batch."""
    import io
    import json

    from tensorframes_tpu import obs

    def traced_steps():
        sink = io.StringIO()
        obs.set_trace_sink(sink)
        try:
            _, _, outs = serve(
                params, prompts_of((150, 20, 140, 10)), (12, 12, 12, 12),
                page_size=2, num_pages=1024,  # 128 rows: two blocks
            )
        finally:
            obs.set_trace_sink(None)
        events = [json.loads(l) for l in sink.getvalue().splitlines() if l.strip()]
        return outs, [
            e["attrs"] for e in events if e["name"] == "serve.decode_step"
        ]

    whole, before = traced_steps()
    monkeypatch.setattr(attention, "LIVE_GROUP_SLOTS", 2)
    grouped, steps = traced_steps()
    assert grouped == whole
    span = 64 * 2  # a block: 64 rows of two positions
    assert [a["kv_tokens_read_window"] for a in steps] == [
        a["kv_tokens_read_window"] for a in before
    ]
    assert [a["kv_tokens_live_full"] for a in steps] == [
        a["kv_tokens_live_full"] for a in before
    ]
    assert any(a["occupancy"] == 4 for a in steps)
    full = [(a, b) for a, b in zip(steps, before) if a["occupancy"] == 4]
    assert full
    for a, b in full:
        # one trip count for the batch: the longest walks both blocks,
        # and so does everyone
        assert b["kv_tokens_read_full"] == 2 * 4 * span
        # {150.., 140..} walk both blocks, {20.., 10..} the first
        assert a["kv_tokens_read_full"] == (2 + 1) * 2 * span
    for a, b in zip(steps, before):
        assert a["kv_tokens_live_full"] <= a["kv_tokens_read_full"]
        assert a["kv_tokens_read_full"] <= b["kv_tokens_read_full"]
    # the two long requests alone: their pair walks both blocks, the idle
    # pair (one key a slot) the first
    assert steps[-1]["occupancy"] == 2
    assert steps[-1]["kv_tokens_read_full"] == (2 + 1) * 2 * span


# ------------------------------------------- a span of queries: the fused fold


def _chunk_inputs(rng, start, valid, c, first, rows, ps=4, n_kv=2, group=2,
                  hd=8, junk=1.0):
    """One slot's chunk as the engine lays it out: queries at ``start ..
    start + c`` of which ``valid`` are real, keys at ``0 .. start +
    valid`` of which those from position-page ``first`` on sit in a
    ``rows``-row table (scattered through layer 1 of a two-layer pool;
    rows past the last key name the trash page, the last page's rows past
    the last key hold what padding wrote there, ``junk`` times noise)."""
    end = start + valid
    total = (first + rows) * ps
    k = rng.normal(size=(total, n_kv, hd)).astype(np.float32)
    v = rng.normal(size=k.shape).astype(np.float32)
    trash = rows
    table = rng.permutation(rows).astype(np.int32)
    pool_k = np.zeros((2, rows + 1, ps, n_kv * hd), np.float32)
    pool_v = np.zeros_like(pool_k)
    noise = rng.normal(size=(2,) + k.shape).astype(np.float32) * junk
    for r in range(rows):
        at = (first + r) * ps
        if at >= end:
            table[r] = trash
            continue
        rows_k, rows_v = k[at : at + ps].copy(), v[at : at + ps].copy()
        past = np.arange(at, at + ps) >= end
        rows_k[past], rows_v[past] = noise[0, :ps][past], noise[1, :ps][past]
        pool_k[1, table[r]] = rows_k.reshape(ps, n_kv * hd)
        pool_v[1, table[r]] = rows_v.reshape(ps, n_kv * hd)
    pool_k[1, trash] = noise[0, ps : 2 * ps].reshape(ps, n_kv * hd)
    pool_v[1, trash] = noise[1, ps : 2 * ps].reshape(ps, n_kv * hd)
    q = rng.normal(size=(1, c, n_kv, group, hd)).astype(np.float32)
    q_pos = (start + np.arange(c))[None].astype(np.int32)
    return dict(
        q=q, k=k, v=v, q_pos=q_pos, end=end, first=first,
        args=(
            q, pool_k, pool_v, table[None], np.asarray([first], np.int32),
            q_pos, np.asarray([end], np.int32), 1,
        ),
    )


def _span_read(case, window, block_pages):
    import jax.numpy as jnp

    *arrays, layer = case["args"]
    return np.asarray(paged_attention_live(
        *(jnp.asarray(a) for a in arrays), layer, window=window,
        block_pages=block_pages,
    ))[0]


# window (0 = a full layer), the chunk's first position, its real tokens
# of 16, the table's first position-page and rows (pages of 4 positions)
CHUNKS = {
    "full-first-chunk": dict(window=0, start=0, valid=16, first=0, rows=24),
    "full-mid-table": dict(window=0, start=40, valid=16, first=0, rows=24),
    "full-last-chunk-ends-inside-a-page": dict(
        window=0, start=64, valid=7, first=0, rows=24,
    ),
    "full-to-the-tables-end": dict(
        window=0, start=80, valid=16, first=0, rows=24,
    ),
    "window-first-chunk": dict(window=24, start=0, valid=16, first=0, rows=11),
    "window-released-pages": dict(
        window=24, start=64, valid=16, first=10, rows=11,
    ),
    "window-last-chunk-ends-inside-a-page": dict(
        window=24, start=80, valid=5, first=14, rows=11,
    ),
    "window-narrower-than-the-chunk": dict(
        window=6, start=32, valid=13, first=6, rows=7,
    ),
}


@pytest.mark.parametrize("block_pages", [64, 6, 2])  # one, two.., many blocks
@pytest.mark.parametrize("case", sorted(CHUNKS))
def test_span_fold_agrees_with_dense_attention(case, block_pages):
    """A chunk's queries through the fused fold against dense masked
    attention in float32: every real query, and the padding rows past
    ``valid`` wherever they still see a key."""
    spec = dict(CHUNKS[case])
    window = spec.pop("window")
    chunk = _chunk_inputs(np.random.default_rng(13), c=16, **spec)
    got = _span_read(chunk, window, block_pages)
    assert np.isfinite(got).all()
    sees = np.ones(16, bool)
    if window:  # a padding row wholly past the window sees no key
        sees = chunk["q_pos"][0] - window < chunk["end"]
    want = _dense_attention(
        chunk["q"][0][sees], chunk["k"], chunk["v"], chunk["q_pos"][0][sees],
        chunk["end"], window,
    )
    np.testing.assert_allclose(got[sees], want, atol=2e-5)
    assert not got[~sees].any()  # and reads zero


@pytest.mark.parametrize("window", [0, 24])
def test_span_fold_never_reads_the_trash_page_or_padding_rows(window):
    """What idle rows of the table name, and what padding wrote behind
    the prompt's last token, is masked to exact zeros: another filling
    gives the same bits."""
    spec = dict(start=64, valid=7, first=10 if window else 0, rows=24)
    one = _chunk_inputs(np.random.default_rng(17), c=16, junk=1.0, **spec)
    two = _chunk_inputs(np.random.default_rng(17), c=16, junk=-300.0, **spec)
    np.testing.assert_array_equal(
        _span_read(one, window, 6), _span_read(two, window, 6)
    )


@pytest.mark.parametrize("hd", [8, 128])
def test_span_fold_widths_and_slots(hd):
    """Three slots of different lengths in one span (the function's
    contract, not the engine's one slot), heads narrower than a lane
    tile and exactly one."""
    import jax.numpy as jnp

    rng = np.random.default_rng(19)
    slots, c, n_kv, group, pages = 3, 16, 2, 2, 12
    lengths = np.asarray([37, 16, 20], np.int32)
    k, v, table, pool_k, pool_v = _scattered_pool(
        rng, lengths, pages, (), hd=hd
    )
    q = rng.normal(size=(slots, c, n_kv, group, hd)).astype(np.float32)
    q_pos = (lengths[:, None] - c + np.arange(c)[None]).astype(np.int32)
    got = paged_attention_live(
        jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
        jnp.asarray(table), jnp.zeros(slots, jnp.int32), jnp.asarray(q_pos),
        jnp.asarray(lengths), 1, window=9, block_pages=4,
    )
    for s in range(slots):
        want = _dense_attention(q[s], k[s], v[s], q_pos[s], lengths[s], 9)
        np.testing.assert_allclose(np.asarray(got[s]), want, atol=2e-5)


@pytest.mark.parametrize("window", [0, 10])
def test_span_fold_carry_after_each_block(window):
    """``live_span_fold`` block by block against a float32 reference's
    carry: the running max (base 2), the normaliser and the accumulator
    after every block, rows that have seen no key yet included."""
    import jax.numpy as jnp

    rng = np.random.default_rng(23)
    c, n_kv, group, hd, span, blocks = 16, 2, 2, 128, 12, 4
    heads, length, start = n_kv * group, 41, 27
    q = rng.normal(size=(1, c, heads, hd)).astype(np.float32)
    k = rng.normal(size=(blocks * span, n_kv, hd)).astype(np.float32)
    v = rng.normal(size=k.shape).astype(np.float32)
    q_pos = (start + np.arange(c))[None].astype(np.int32)
    scale = hd ** -0.5
    m = np.full((1, c, heads), attention._NEG_BIG, np.float32)
    l = np.zeros((1, c, heads), np.float32)
    acc = np.zeros((1, c, heads * hd), np.float32)
    scores = np.einsum(
        "chd,thd->cht", q[0], np.repeat(k, group, axis=1)
    ) * (scale * np.log2(np.e))
    pos = np.arange(blocks * span)
    seen = (pos[None] <= q_pos[0][:, None]) & (pos[None] < length)
    if window:
        seen &= pos[None] > q_pos[0][:, None] - window
    for j in range(blocks):
        at = slice(j * span, (j + 1) * span)
        m, l, acc = (np.asarray(x) for x in attention.live_span_fold(
            jnp.asarray(q.reshape(1, c, heads * hd)), jnp.asarray(q_pos),
            jnp.asarray(k[at].reshape(1, span, n_kv * hd)),
            jnp.asarray(v[at].reshape(1, span, n_kv * hd)),
            jnp.asarray([j * span], jnp.int32),
            jnp.asarray([length], jnp.int32),
            jnp.asarray(m), jnp.asarray(l), jnp.asarray(acc),
            n_kv=n_kv, window=window,
        ))
        upto = seen & (pos[None] < (j + 1) * span)
        masked = np.where(upto[:, None, :], scores, -np.inf)
        want_m = masked.max(-1)
        p = np.where(
            upto[:, None, :], np.exp2(scores - want_m[..., None]), 0.0
        )
        none = ~upto.any(-1)
        assert (m[0][none] == np.float32(attention._NEG_BIG)).all()
        np.testing.assert_allclose(m[0][~none], want_m[~none], rtol=1e-5)
        np.testing.assert_allclose(l[0], p.sum(-1), rtol=2e-5)
        np.testing.assert_allclose(
            acc[0].reshape(c, heads, hd),
            np.einsum("cht,thd->chd", p, np.repeat(v, group, axis=1)),
            atol=2e-4,
        )


def test_span_trips_count_the_blocks_to_the_longest_slots_last_key():
    import jax.numpy as jnp

    for live, want in (([1], 1), ([128], 1), ([129], 2), ([40, 300], 3),
                       ([9999], 6), ([0], 1)):
        assert attention.live_span_trips(np.asarray(live), 128, 6) == want
        traced = attention.live_span_trips(jnp.asarray(live, jnp.int32), 128, 6)
        assert int(traced) == want


TWELVE = dict(
    TINY, num_hidden_layers=12,
    layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 3,
)


def test_chunk_program_traces_the_span_walk_once_per_cache_kind():
    """Twelve layers, nine window and three full: the chunk program calls
    the jitted walk twelve times and holds two copies of it, one per
    cache kind (counted in the lowered module, not timed)."""
    import re

    params = mellum.init_params(3, TWELVE, "float32")
    eng = GenerationEngine(
        params, max_slots=4, page_size=16, num_pages=64, max_seq_len=256,
        queue_capacity=4, prefill_chunk_tokens=32,
    )
    args = eng._chunk_args(
        np.zeros(1, np.int32), 0, 1, 1, SequencePages(eng.pool, eng.layout),
        0.0, 0, 1.0,
    )
    text = eng._prefill_chunk_jit.lower(
        eng._params_dev, eng.pool.k, eng.pool.v, *args
    ).as_text()
    defined = re.findall(r"func\.func private @(_live_span_walk\w*)\(", text)
    called = re.findall(r"call @(_live_span_walk\w*)\(", text)
    assert len(defined) == 2 and len(called) == 12
    assert sorted(called.count(name) for name in defined) == [3, 9]


def test_chunk_span_counts_the_blocks_the_program_walks(params, monkeypatch):
    """A small live engine whose full layers' table is two blocks wide:
    every ``serve.prefill_chunk`` span's ``attn_blocks`` is what the
    trip counts give by hand, their sum is the folds the program itself
    ran (counted on the device's side, one callback a fold), the fused
    count is all of them and the counters carry the sums."""
    import io
    import json

    import jax

    from tensorframes_tpu import obs

    ran = []
    fold = attention.live_span_fold

    def counted(*a, **kw):
        jax.debug.callback(lambda: ran.append(1))
        return fold(*a, **kw)

    monkeypatch.setattr(attention, "live_span_fold", counted)
    attention._live_span_walk.clear_cache()
    before = obs.registry().snapshot()
    sink = io.StringIO()
    obs.set_trace_sink(sink)
    try:
        serve(
            params, prompts_of((150, 20, 140)), (4, 4, 4), page_size=2,
            num_pages=1024,  # 128 rows a full layer: two blocks of 64
        )
        jax.effects_barrier()
    finally:
        obs.set_trace_sink(None)
        attention._live_span_walk.clear_cache()
    events = [json.loads(l) for l in sink.getvalue().splitlines() if l.strip()]
    chunks = [e["attrs"] for e in events if e["name"] == "serve.prefill_chunk"]
    assert len(chunks) == 5 + 1 + 5
    for a in chunks:
        end = a["start"] + a["tokens"]
        # three window layers of one block, one full layer to the block
        # of 128 positions that holds the chunk's last token
        assert a["attn_blocks"] == 3 * 1 + -(-end // 128)
        assert a["attn_blocks_fused"] == a["attn_blocks"]
    assert {a["attn_blocks"] for a in chunks} == {4, 5}
    assert sum(a["attn_blocks"] for a in chunks) == len(ran)
    after = obs.registry().snapshot()

    def rose(name):
        total = lambda snap: sum(snap[name]["values"].values())
        return total(after) - (total(before) if name in before else 0)

    assert rose("serve.chunk_attention_blocks_total") == len(ran)
    assert rose("serve.chunk_attention_blocks_fused_total") == len(ran)
