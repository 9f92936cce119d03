"""The one-pass prefill's attention: ONE fused kernel a layer
(``ops.attention.prompt_attention``, entered through the jitted
``causal_prompt_attention``) — causal attention of a prompt row's queries
over its own keys, score tiles and softmax state in fast memory, the
tiles that no real query sees skipped. Run interpreted here: the kernel
itself, against dense masked attention in float32."""

import re

import numpy as np
import pytest

from tensorframes_tpu.models import TransformerLM
from tensorframes_tpu.ops import attention
from tensorframes_tpu.serve import GenerationEngine

pytestmark = pytest.mark.attn


def _qkv(rng, rows, n_kv, group, hd):
    q = rng.standard_normal((1, rows, n_kv, group, hd)).astype(np.float32)
    k = rng.standard_normal((1, rows, n_kv, hd)).astype(np.float32)
    v = rng.standard_normal((1, rows, n_kv, hd)).astype(np.float32)
    return q, k, v


def _dense(q, k, v, length, window=0):
    """Masked causal attention in float64 over the first ``length``
    positions: ``[length, heads * hd]``."""
    _, _, n_kv, group, hd = q.shape
    q, k, v = (np.asarray(x, np.float64)[0, :length] for x in (q, k, v))
    s = np.einsum("qngd,tnd->ngqt", q, k) / np.sqrt(hd)
    pos = np.arange(length)
    seen = pos[None, :] <= pos[:, None]
    if window:
        seen &= pos[None, :] > pos[:, None] - window
    s = np.where(seen, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("ngqt,tnd->qngd", p, v).reshape(length, -1)


def _attend(q, k, v, length, window=0):
    import jax.numpy as jnp

    out = attention.causal_prompt_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray([length], jnp.int32), window=window,
    )
    return np.asarray(out)[0]


@pytest.mark.parametrize("group", [1, 2])
@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("length", [1, 15, 16, 127, 128, 129, 640, 768])
def test_kernel_matches_dense_causal_attention(length, hd, group):
    """A row of 768 positions (three query tiles of 256, two key tiles
    of 384) at every kind of prompt length: one token, inside the first
    tile, tile edges, the whole row."""
    q, k, v = _qkv(np.random.default_rng(length), 768, 2, group, hd)
    got = _attend(q, k, v, length)
    np.testing.assert_allclose(
        got[:length], _dense(q, k, v, length), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("length", [1, 15, 16, 40, 96])
def test_a_row_narrower_than_a_lane_tile_is_one_tile(length):
    """96 positions: the whole row is one query and one key tile, four
    heads of 16 lanes padded to a lane tile each."""
    q, k, v = _qkv(np.random.default_rng(7), 96, 4, 1, 16)
    assert attention._prompt_tiles(96) == (96, 96)
    np.testing.assert_allclose(
        _attend(q, k, v, length)[:length], _dense(q, k, v, length),
        atol=2e-5, rtol=2e-5,
    )


@pytest.mark.parametrize("window", [48, 200])
def test_a_window_layer_sees_only_its_window(window):
    q, k, v = _qkv(np.random.default_rng(window), 512, 2, 2, 64)
    np.testing.assert_allclose(
        _attend(q, k, v, 400, window)[:400],
        _dense(q, k, v, 400, window), atol=2e-5, rtol=2e-5,
    )


def test_twenty_five_heads_of_64_share_thirteen_lane_tiles():
    """gpt2-xl's heads: 1,600 lanes, the row's tail padded to 1,664."""
    q, k, v = _qkv(np.random.default_rng(25), 256, 25, 1, 64)
    np.testing.assert_allclose(
        _attend(q, k, v, 200)[:200], _dense(q, k, v, 200),
        atol=2e-5, rtol=2e-5,
    )


@pytest.mark.parametrize("group, hd", [(1, 64), (2, 64), (1, 128)])
def test_real_rows_do_not_depend_on_the_padding_rows(group, hd):
    """The real rows' bits are the same whatever the padding positions'
    queries, keys and values hold — NaN and huge values included."""
    rng = np.random.default_rng(3)
    q, k, v = _qkv(rng, 512, 2, group, hd)
    length = 300
    base = _attend(q, k, v, length)[:length]
    for fill in (np.nan, 1e30, -3.0):
        q2, k2, v2 = (x.copy() for x in (q, k, v))
        for x in (q2, k2, v2):
            x[0, length:] = fill
        np.testing.assert_array_equal(
            _attend(q2, k2, v2, length)[:length], base
        )


@pytest.mark.parametrize(
    "length, rows, windows, want",
    [
        # 1,024 rows: 4 query tiles of 256 x 2 key tiles of 512; a full
        # row computes 1 + 1 + 2 + 2 pairs of 8
        (1024, 1024, [0], (6, 2)),
        # 640 real tokens: query tiles 0-2 hold real rows, tile 2 sees
        # both key tiles (the second is cut at 640): 1 + 1 + 2
        (640, 1024, [0], (4, 4)),
        (1, 1024, [0], (1, 7)),
        (256, 1024, [0, 0, 0], (3, 21)),
        # a row of 96: one pair, computed
        (40, 96, [0], (1, 0)),
        # a window of 200: query tile 3 (768-1023) reaches back to 569,
        # past key tile 0 (0-511)
        (1024, 1024, [200], (5, 3)),
        (1024, 1024, [0, 200], (11, 5)),
    ],
)
def test_tile_counts_by_hand(length, rows, windows, want):
    assert attention.prompt_attention_tiles(length, rows, windows) == want


def test_a_tile_of_padding_queries_is_skipped():
    """Query tile 1 (positions 256-511) holds no real row at 200 real
    tokens: the count skips its pair, and the kernel folds nothing into
    its state, so its rows come out as the empty state's exact zeros;
    the real rows are dense attention's."""
    q, k, v = _qkv(np.random.default_rng(11), 512, 2, 1, 64)
    assert attention.prompt_attention_tiles(200, 512, [0]) == (1, 1)
    got = _attend(q, k, v, 200)
    assert (got[256:] == 0.0).all()
    np.testing.assert_allclose(
        got[:200], _dense(q, k, v, 200), atol=2e-5, rtol=2e-5
    )


def _engine(n_layers):
    lm = TransformerLM.init(
        0, 32, d_model=32, n_heads=4, n_layers=n_layers, max_len=512
    )
    return GenerationEngine(lm, max_slots=2, page_size=16, max_seq_len=512)


def test_prefill_span_counts_the_tiles_and_feeds_the_counters():
    import io
    import json

    from tensorframes_tpu import obs

    eng = _engine(2)
    before = obs.registry().snapshot()
    sink = io.StringIO()
    obs.set_trace_sink(sink)
    try:
        eng.generate([list(range(1, 300)), [3] * 20], 2)
    finally:
        obs.set_trace_sink(None)
    events = [json.loads(l) for l in sink.getvalue().splitlines() if l.strip()]
    attrs = sorted(
        (e["attrs"]["prompt_len"], e["attrs"]["attn_tiles"],
         e["attrs"]["attn_tiles_skipped"])
        for e in events if e["name"] == "serve.prefill"
    )
    # 512 rows: 2 query tiles of 256 x 1 key tile of 512, two layers
    assert attrs == [(20, 2, 2), (299, 4, 0)]
    after = obs.registry().snapshot()

    def rose(name):
        total = lambda snap: sum(snap[name]["values"].values())
        return total(after) - (total(before) if name in before else 0)

    assert rose("serve.prefill_attention_tiles_total") == 6
    assert rose("serve.prefill_attention_tiles_skipped_total") == 2


def test_prefill_program_traces_the_kernel_once():
    """Forty-eight layers, all full: the lowered prefill program calls
    the jitted attention forty-eight times and holds one copy of it."""
    eng = _engine(48)
    args = (
        np.zeros((1, eng.max_seq_len), np.int32), np.int32(5),
        np.zeros(eng._max_pages, np.int32), np.float32(0.0), np.int32(0),
        np.float32(1.0),
    )
    text = eng._prefill_jit.lower(
        eng._params_dev, eng.pool.k, eng.pool.v, *args
    ).as_text()
    defined = re.findall(
        r"func\.func private @(causal_prompt_attention\w*)\(", text
    )
    called = re.findall(r"call @(causal_prompt_attention\w*)\(", text)
    assert len(defined) == 1 and len(called) == 48
    assert "[25, 1024, 1024]" not in text
