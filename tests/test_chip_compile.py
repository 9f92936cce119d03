"""Kernels of a cell's path compiled for the chip at real widths, from
shapes alone: the TPU's compiler is installed here and compiles for a chip
that is described and not attached (nothing runs, no time is read). What
interpret mode cannot show — a tile Mosaic refuses, more fast memory than
a kernel may use, a relayout of the pool around a kernel — fails here at
no chip time. All in this one file: a process loads the TPU's library
once, inside the fixture, and keeps it."""

import functools
import importlib.util
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    for key, value in (
        ("TPU_ACCELERATOR_TYPE", "v5litepod-4"), ("TPU_SKIP_MDS_QUERY", "1"),
        ("TPU_WORKER_HOSTNAMES", "localhost"), ("TPU_LOG_DIR", "disabled"),
    ):
        os.environ.setdefault(key, value)
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_settings():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out. And 64-bit types
    off, as in a serving process: a test that ran before in this worker
    may have turned them on for good (``ensure_x64``), and Mosaic has no
    64-bit types (neither tree's chunk program compiles with them on)."""
    import jax

    was = jax.config.jax_enable_compilation_cache, jax.config.jax_enable_x64
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_enable_x64", was[1])


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# Mellum2-12B's chunk: 1,024 queries of 4 K/V heads x 8 of 128 lanes, a
# bfloat16 pool of 48,001 pages three layers deep; a window layer's table
# (the window's and the chunk's pages) and a full layer's (every page)
@pytest.mark.parametrize(
    "rows, window", [(129, 1024), (1048, 0)], ids=["window", "full"]
)
def test_span_walk_compiles_for_the_chip_at_mellums_widths(
    one_chip, compile_settings, monkeypatch, rows, window
):
    import jax

    from tensorframes_tpu.ops import attention

    monkeypatch.setattr(
        attention, "live_span_fold",
        functools.partial(attention.live_span_fold, interpret=False),
    )
    attention._live_span_walk.clear_cache()
    c, n_kv, group, hd, ps, pages = 1024, 4, 8, 128, 16, 48001
    of = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = of((3, pages, ps, n_kv * hd), "bfloat16")
    try:
        compiled = attention._live_span_walk.lower(
            of((1, c, n_kv, group, hd), "float32"), pool, pool,
            of((1, rows), "int32"), of((1,), "int32"), of((1, c), "int32"),
            of((1,), "int32"), of((), "int32"),
            window=window, block_pages=attention.LIVE_BLOCK_PAGES,
        ).compile()
    finally:
        attention._live_span_walk.clear_cache()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and attention.LIVE_SPAN_KERNEL in text
    smoke = _chip_smoke()
    span = attention.live_read_blocks(rows)[1] * ps
    # no block of scores and no [.., 4, 8]-minor state in memory, the
    # pool read in place and the gathered block handed on as it lies
    assert smoke.span_attention_leaks(
        smoke.hlo_arrays(text), c, n_kv, group, (span,)
    ) == []
    whole = 3 * pages * ps * n_kv * hd
    assert smoke.pool_relayouts(
        text, (whole, whole // 3, span * n_kv * hd), loops=True
    ) == []
    # the walk's own buffers beside its arguments and its result: the
    # carry, one gathered block of K and V and the table (the XLA walk
    # it replaced kept a block of float32 scores, 138 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < 40 * 2**20


def _prefill_program(one_chip, n_layers, attend=None):
    """The one-pass prefill at gpt2-xl's widths (a ``[1, 1024]`` row, 25
    heads of 64, float32, the pool written as the engine writes it)
    compiled for the chip from shapes alone; with ``attend`` the same
    block walk under that attention instead of the kernel's."""
    import jax
    import jax.numpy as jnp

    from tensorframes_tpu.models.transformer import (
        init_transformer, transformer_prefill, transformer_prefill_chunk,
    )
    from tensorframes_tpu.serve.kv_pages import write_prompt

    rows, d, heads, pages = 1024, 1600, 25, 177
    shapes = jax.eval_shape(lambda: {
        k: v for k, v in init_transformer(
            0, 512, d_model=d, n_heads=heads, n_layers=n_layers,
            max_len=rows,
        ).items() if k != "n_heads"
    })
    of = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    params = jax.tree.map(lambda s: of(s.shape, s.dtype), shapes)
    pool = of((n_layers, pages, 16, d), "float32")

    def prefill(p, kp, vp, prompt, length, ptab):
        full = {**p, "n_heads": heads}
        state = [kp, vp]

        def store(li, k, v):
            for n, x in enumerate((k, v)):
                state[n] = write_prompt(
                    state[n], li, ptab, length, x[0], pages - 1
                )

        if attend is None:
            logits = transformer_prefill(
                full, prompt, store, 1, jnp.reshape(length, (1,))
            )
        else:
            logits = transformer_prefill_chunk(
                full, prompt, jnp.arange(rows),
                lambda li, q, k, v: (store(li, k, v), attend(q, k, v))[1],
            )
        return state[0], state[1], jnp.argmax(logits[0, length - 1])

    return jax.jit(prefill, donate_argnums=(1, 2)).lower(
        params, pool, pool, of((1, rows), "int32"), of((), "int32"),
        of((64,), "int32"),
    ).compile()


def _dense_causal(q, k, v):
    """Attention as the one-pass prefill computed it before the kernel:
    the scores of every head over the whole row, masked, softmax."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rows, hd = q.shape[1], q.shape[-1]
    s = jnp.einsum("bqngd,btnd->bngqt", q, k) / float(np.sqrt(hd))
    causal = jnp.tril(jnp.ones((rows, rows), bool))
    s = jnp.where(causal, s, jnp.finfo(jnp.float32).min * 0.7)
    att = jnp.einsum("bngqt,btnd->bqngd", jax.nn.softmax(s, axis=-1), v)
    return att.reshape(q.shape[0], rows, -1)


def test_one_pass_prefill_compiles_for_the_chip_at_gpt2_xls_widths(
    one_chip, compile_settings, monkeypatch
):
    from tensorframes_tpu.ops import attention

    monkeypatch.setattr(
        attention, "prompt_attention",
        functools.partial(attention.prompt_attention, interpret=False),
    )
    attention.causal_prompt_attention.clear_cache()
    try:
        kernel = _prefill_program(one_chip, 3)
    finally:
        attention.causal_prompt_attention.clear_cache()
    dense = _prefill_program(one_chip, 3, _dense_causal)
    smoke = _chip_smoke()
    scores = 25 * 1024 * 1024

    def score_blocks(compiled):
        return [
            (op, shape) for op, shape in smoke.hlo_arrays(compiled.as_text())
            if scores in smoke._elements(shape)
        ]

    # the compiler took the kernel's tiles (a kernel that asks for more
    # fast memory than it may hold fails to compile): one call a layer
    calls = re.findall(
        rf"%{attention.PROMPT_KERNEL}[.\d]* = .* custom-call\(",
        kernel.as_text(),
    )
    assert len(calls) == 3
    # no heads x P x P array anywhere in the program; the dense arm at
    # the same shapes writes its scores to memory
    assert score_blocks(kernel) == []
    assert score_blocks(dense) != []
    assert (
        kernel.memory_analysis().temp_size_in_bytes
        < dense.memory_analysis().temp_size_in_bytes
    )


def _decode_program(one_chip, n_layers, read):
    """The decode step at gpt2-xl's widths (8 slots, a 64-row table, 25
    heads of 64, a float32 pool of 177 pages) as the engine builds it —
    each layer writes its token's k/v into its page, then ``read`` — and
    compiled for the chip from shapes alone; returns the lowered and the
    compiled program."""
    import jax
    import jax.numpy as jnp

    from tensorframes_tpu.models.transformer import (
        init_transformer, transformer_step,
    )
    from tensorframes_tpu.serve.kv_pages import write_rows

    slots, rows, d, heads, pages = 8, 64, 1600, 25, 177
    shapes = jax.eval_shape(lambda: {
        k: v for k, v in init_transformer(
            0, 512, d_model=d, n_heads=heads, n_layers=n_layers,
            max_len=rows * 16,
        ).items() if k != "n_heads"
    })
    of = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    params = jax.tree.map(lambda s: of(s.shape, s.dtype), shapes)
    pool = of((n_layers, pages, 16, d), "float32")

    def decode(p, kp, vp, toks, positions, ptabs):
        state = [kp, vp]
        page = ptabs[jnp.arange(slots), positions // 16]

        def attend(li, q, k, v):
            for n, x in enumerate((k, v)):
                state[n] = write_rows(state[n], li, page, positions % 16, x)
            ctx = read(
                q, state[0], state[1], ptabs, positions + 1, layer=li
            )
            return ctx.reshape(slots, -1)

        logits = transformer_step(
            {**p, "n_heads": heads}, toks, positions, attend
        )
        return state[0], state[1], jnp.argmax(logits, axis=-1)

    lowered = jax.jit(decode, donate_argnums=(1, 2)).lower(
        params, pool, pool, of((slots,), "int32"), of((slots,), "int32"),
        of((slots, rows), "int32"),
    )
    return lowered, lowered.compile()


def test_decode_read_compiles_for_the_chip_at_gpt2_xls_widths(
    one_chip, compile_settings
):
    import jax

    from tensorframes_tpu.ops import attention

    n_layers = 4
    lowered, kernel = _decode_program(
        one_chip, n_layers, functools.partial(
            attention.ragged_paged_attention, interpret=False
        ),
    )
    _, gather = _decode_program(
        one_chip, n_layers, jax.jit(attention.paged_attention)
    )
    smoke = _chip_smoke()
    # one kernel call a layer, all of one lowered callee (the layer is
    # data to it), and the compiler took the kernel's buffers
    calls = re.findall(
        rf"%{attention.DECODE_KERNEL}[.\d]* = .* custom-call\(",
        kernel.as_text(),
    )
    assert len(calls) == n_layers
    text = lowered.as_text()
    callee = r"@_ragged_paged_read\b"
    assert len(re.findall(rf"func\.func private {callee}", text)) == 1
    assert len(re.findall(rf"call {callee}", text)) == n_layers
    # no [slots x max_pages, page_size, lanes] copy of the tables' pages
    # anywhere; the gather arm at the same shapes makes one a layer
    gathered = 8 * 64 * 16 * 1600

    def gathers(compiled):
        return [
            (op, shape) for op, shape in smoke.hlo_arrays(compiled.as_text())
            if gathered in smoke._elements(shape)
        ]

    assert gathers(kernel) == []
    assert gathers(gather) != []
    assert (
        kernel.memory_analysis().temp_size_in_bytes
        <= gather.memory_analysis().temp_size_in_bytes
    )
    # nor a copy of the pool or a layer of it around the kernel
    whole = n_layers * 177 * 16 * 1600
    moved = smoke.pool_relayouts(kernel.as_text(), (whole, whole // 4))
    assert moved == []
