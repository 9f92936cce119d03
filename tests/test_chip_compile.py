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
