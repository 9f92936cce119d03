"""Persistent-compile-cache config + ahead-of-time ``precompile``.

The reference pays zero compile cost (TF 1.x sessions execute GraphDefs
directly, ``TensorFlowOps.scala:76-95``); this framework's equivalent is
XLA's persistent executable cache plus an AOT warm-up API. These tests pin
the contract: the cache is configured at import, ``precompile`` builds one
program per distinct block shape without touching data, and the programs it
builds are the ones ``map_blocks`` then runs.
"""

import os

import numpy as np
import pytest

import tensorframes_tpu as tft
from tensorframes_tpu.utils.config import enable_compilation_cache


def _frame(n=100, parts=4):
    x = np.arange(n * 8, dtype=np.float32).reshape(n, 8)
    return (
        tft.TensorFrame.from_columns(
            {"features": x}, num_partitions=parts
        ).analyze(),
        x,
    )


def _score(features):
    return {"out": features * 2.0 + 1.0}


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: a fresh interpreter imports the package with jax.config.update
#: recorded, on request runs one program that compiles in well under
#: jax's own 1.0 s admission floor, and reports what the cache ended up as
_CACHE_PROBE = r"""
import json, os, sys
import jax
updated = []
_update = jax.config.update
def recording_update(name, value):
    updated.append(name)
    return _update(name, value)
jax.config.update = recording_update
import tensorframes_tpu as tft
import jax.numpy as jnp
def chain(x):
    # long enough to compile in well over the 0.1 s this package admits
    # from (48 links took 0.08-0.15 s here: an entry two runs in three)
    for _ in range(384):
        x = jnp.tanh(x @ x) + 1.0
    return x
if sys.argv[1:] == ["compile"]:
    jax.block_until_ready(jax.jit(chain)(jnp.ones((16, 16))))
d = tft.enable_compilation_cache()
print(json.dumps({
    "dir": d,
    "jax_dir": jax.config.jax_compilation_cache_dir,
    "updated": updated,
    "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
    "min_bytes": jax.config.jax_persistent_cache_min_entry_size_bytes,
    "entries": sorted(f for f in os.listdir(d) if f.endswith("-cache")),
    "tune": tft.tune.store_path(),
}))
"""


def _cache_probe(tmp_path, cache_env, *argv):
    import json
    import subprocess
    import sys

    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_COMPILATION_CACHE_DIR", "TFT_TUNE_FILE")
    }
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=_REPO, **cache_env)
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE, *argv], env=env, cwd=str(tmp_path),
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cache_placed_by_jax_env_var(tmp_path):
    # JAX_COMPILATION_CACHE_DIR set: jax owns the directory (no
    # jax_compilation_cache_dir update from this package), yet the two
    # admission thresholds are still lowered, so a sub-second program is
    # cached there, and the tuning store sits in the same directory
    placed = str(tmp_path / "placed")
    got = _cache_probe(
        tmp_path, {"JAX_COMPILATION_CACHE_DIR": placed}, "compile"
    )
    assert got["dir"] == got["jax_dir"] == placed
    assert "jax_compilation_cache_dir" not in got["updated"]
    assert got["min_secs"] <= 0.1 and got["min_bytes"] == -1
    assert got["entries"], "no cache entry for a sub-second program"
    assert got["tune"] == os.path.join(placed, "tune.jsonl")


def test_cache_defaults_to_one_fixed_checkout_path(tmp_path):
    # unset: one fixed git-ignored directory inside the checkout, the
    # same for every process whatever its cwd (two fresh ones agree).
    # Nothing is compiled: the tests leave no CPU entries in the checkout
    want = os.path.join(_REPO, ".jax_cache")
    for cwd in (tmp_path, tmp_path / "elsewhere"):
        cwd.mkdir(exist_ok=True)
        got = _cache_probe(cwd, {})
        assert got["dir"] == got["jax_dir"] == want
        assert got["min_secs"] <= 0.1 and got["min_bytes"] == -1
        assert got["tune"] == os.path.join(want, "tune.jsonl")


def test_cache_enabled_in_the_test_process():
    # conftest places the tests' cache outside the checkout through
    # JAX_COMPILATION_CACHE_DIR; the package import must have honoured it
    import jax

    d = enable_compilation_cache()  # idempotent: returns the active dir
    assert d == os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert jax.config.jax_compilation_cache_dir == d
    assert not d.startswith(_REPO + os.sep)
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


def test_precompile_frame_counts_distinct_block_shapes():
    df, _ = _frame(n=100, parts=4)  # 4 equal partitions of 25
    assert tft.precompile(_score, df) == 1
    # uneven partitioning: 3 parts of 33/33/34 -> two distinct sizes
    df2, _ = _frame(n=100, parts=3)
    assert tft.precompile(_score, df2) == 2


def test_precompile_then_map_blocks_matches():
    df, x = _frame()
    tft.precompile(_score, df)
    out = tft.map_blocks(_score, df)
    np.testing.assert_allclose(
        np.asarray(out.column_data("out").host()), x * 2.0 + 1.0
    )


def test_precompile_schema_path_requires_block_rows():
    df, _ = _frame()
    with pytest.raises(ValueError, match="block_rows"):
        tft.precompile(_score, df.schema)
    assert tft.precompile(_score, df.schema, block_rows=[25, 50]) == 2


def test_precompile_rejects_unknown_dims():
    x = np.arange(80, dtype=np.float32).reshape(10, 8)
    df = tft.TensorFrame.from_columns({"features": x})  # NOT analyzed
    # from_columns on a dense ndarray knows the cell dims, so force an
    # Unknown via a serialized-graph-style schema with an Unknown tail
    from tensorframes_tpu.schema import (
        ColumnInfo,
        FrameInfo,
        Shape,
        Unknown,
        for_numpy_dtype,
    )

    info = FrameInfo(
        [
            ColumnInfo(
                "features",
                for_numpy_dtype(np.dtype(np.float32)),
                analyzed_shape=Shape([Unknown, Unknown]),
                nesting=1,
            )
        ]
    )
    with pytest.raises(ValueError, match="unknown cell dims"):
        tft.precompile(_score, info, block_rows=[10])


def test_precompile_with_constants_and_feed_dict():
    df, x = _frame()
    w = np.full((8,), 3.0, dtype=np.float32)

    def affine(v, w):
        return {"out": v * w}

    assert (
        tft.precompile(
            affine, df, feed_dict={"v": "features"}, constants={"w": w}
        )
        == 1
    )
    out = tft.map_blocks(
        affine, df, feed_dict={"v": "features"}, constants={"w": w}
    )
    np.testing.assert_allclose(
        np.asarray(out.column_data("out").host()), x * 3.0
    )


def test_precompile_graph_from_artifact(tmp_path):
    # serving-process story: load a serialized graph in a process with no
    # data, precompile for the block sizes it will serve
    df, x = _frame()
    from tensorframes_tpu.schema import FLOAT32, Shape, Unknown

    g = tft.CapturedGraph.from_callable(
        _score, {"features": (FLOAT32, Shape([Unknown, 8]))}
    )
    path = tmp_path / "scoring.tfg"
    tft.save_graph(g, str(path))
    g2 = tft.load_graph(str(path))
    assert tft.precompile(g2, df.schema, block_rows=[25]) == 1
    out = tft.map_blocks(g2, df)
    np.testing.assert_allclose(
        np.asarray(out.column_data("out").host()), x * 2.0 + 1.0
    )
