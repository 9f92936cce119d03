"""The BASELINE.md benchmark configs, plus framework-specific extras (7+).

Each function runs one config and returns a result dict; ``run_all.py``
prints them as JSON lines. ``bench.py`` at the repo root runs config 3 (the
driver's headline metric). Hardware note: numbers depend on the attached
backend — real TPU via the default platform, or CPU when forced.

| # | config | reference provenance |
|---|--------|----------------------|
| 1 | README scalar add-3 map_blocks            | README.md:60-88 |
| 2 | README vector reduce_sum/min on [?,2]     | README.md:91-122 |
| 3 | MNIST LR scoring via map_blocks           | core.py:41-55 (frozen graphs) |
| 4 | image-embedding map_rows over binary rows | read_image.py:147-167 |
| 5 | distributed SGD: map_blocks(grad) + reduce_blocks(sum) | DebugRowOps.scala:290-526 |
"""

from __future__ import annotations

import os
import time
from typing import Dict

import numpy as np


def _sync(v):
    """The device barrier every timing window ends in: jax dispatch is
    asynchronous, so a window without it measures the enqueue."""
    import jax

    return jax.block_until_ready(v)


def _timeit(fn, iters=5, warmup=1):
    """Wall time per call; the returned value of ``fn`` is synchronized so
    async device dispatch cannot leak out of the timing window."""
    for _ in range(warmup):
        _sync(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        _sync(fn())
    return (time.perf_counter() - t0) / iters


def config1_add3(n_rows: int = 1_000_000) -> Dict:
    """Scalar add-3 map_blocks (README example 1, scaled up)."""
    import tensorframes_tpu as tft
    from tensorframes_tpu.capture import functions as F

    df = tft.TensorFrame.from_columns(
        {"x": np.arange(n_rows, dtype=np.float64)}
    )
    with tft.graph():
        x = tft.block(df, "x")
        g = tft.build_graph((x + 3.0).named("z"))

    def run():
        return tft.map_blocks(g, df).cache().column_block("z")

    dt = _timeit(run)
    assert float(run()[0]) == 3.0
    return {
        "metric": "config1_add3_rows_per_sec",
        "value": round(n_rows / dt, 1),
        "unit": "rows/s",
        "seconds_per_pass": round(dt, 4),
    }


def config2_vector_reduce(n_rows: int = 1_000_000) -> Dict:
    """Vector reduce_sum + reduce_min on [?, 2] doubles (README example 2)."""
    import tensorframes_tpu as tft

    y = np.stack(
        [np.arange(n_rows, dtype=np.float64), -np.arange(n_rows, dtype=np.float64)],
        axis=1,
    )
    df = tft.TensorFrame.from_columns({"y": y, "z": y.copy()}).analyze()

    # one function object across passes (capture/compile memoized on it)
    def reduce_fn(y_input, z_input):
        return {"y": y_input.sum(axis=0), "z": z_input.min(axis=0)}

    def run():
        return tft.reduce_blocks(reduce_fn, df)

    dt = _timeit(run)
    s, m = run()
    np.testing.assert_allclose(np.asarray(m)[1], -(n_rows - 1))
    return {
        "metric": "config2_vector_reduce_rows_per_sec",
        "value": round(n_rows / dt, 1),
        "unit": "rows/s",
        "seconds_per_pass": round(dt, 4),
    }


def config3_mnist_scoring(n_rows: int = 200_000) -> Dict:
    """MNIST-LR scoring via map_blocks on a frozen model (bench.py metric)."""
    import tensorframes_tpu as tft
    from tensorframes_tpu.models import MLPClassifier

    rng = np.random.default_rng(0)
    x = rng.normal(size=(n_rows, 784)).astype(np.float32)
    clf = MLPClassifier.init(0, [784, 10])
    df = tft.TensorFrame.from_columns({"features": x}).analyze()

    def run():
        return clf.score_frame(df, "features").cache().column_block("prediction")

    dt = _timeit(run)
    return {
        "metric": "config3_mnist_scoring_rows_per_sec",
        "value": round(n_rows / dt, 1),
        "unit": "rows/s",
        "seconds_per_pass": round(dt, 4),
    }


def _publish_torch_cnn(path: str, embed_dim: int = 256):
    """The external publisher for config4: a torch VGG-style net saved
    the way model hubs publish checkpoints (the reference's downloaded
    VGG-16, ``read_image.py:29-44``, played by torch). Falls back to
    ``None`` where torch isn't installed."""
    try:
        import torch
    except ImportError:
        return False
    torch.manual_seed(0)
    layers = []
    c_in = 3
    for width in (32, 64, 128):
        for _ in range(2):
            layers += [
                torch.nn.Conv2d(c_in, width, 3, padding=1),
                torch.nn.ReLU(),
            ]
            c_in = width
        layers.append(torch.nn.MaxPool2d(2))
    layers += [
        torch.nn.Flatten(),
        torch.nn.Linear(128 * 4 * 4, embed_dim),
    ]
    model = torch.nn.Sequential(*layers).eval()
    np.savez(path, **{k: v.numpy() for k, v in model.state_dict().items()})
    return True


def config4_image_scoring(n_rows: int = 100_000) -> Dict:
    """Frozen multi-layer CNN embedding over binary image rows (the
    reference's VGG-over-binaryFiles workload, ``read_image.py:147-167``):
    host codec via ``decode_column``'s thread pool, then batched bf16 convs
    on device, one XLA program per partition block. 6 conv layers + dense
    head over 32x32x3 uint8 images — with REAL imported weights: a torch
    publisher model's checkpoint imported through
    ``CNNScorer.from_pretrained`` (the reference scored a downloaded
    pre-trained VGG-16; r05 closes that realism gap)."""
    import tempfile

    import tensorframes_tpu as tft
    from tensorframes_tpu.models import CNNScorer

    rng = np.random.default_rng(0)
    with tempfile.TemporaryDirectory() as td:
        ckpt = os.path.join(td, "published.npz")
        if _publish_torch_cnn(ckpt):
            scorer = CNNScorer.from_pretrained(
                ckpt, input_hw=(32, 32), channels=3, convs_per_block=2,
                image_format="raw",  # rows below are raw packed pixels
            )
            model_name = "torch-published-cnn6-imported-embed256"
        else:  # no torch on this host: random-init fallback
            scorer = CNNScorer.init(
                0, input_hw=(32, 32), channels=3, embed_dim=256
            )
            model_name = "cnn6-bf16-32x32x3-embed256 (random init; no torch)"
    # one contiguous uint8 pool sliced into per-row byte cells: building
    # 100k bytes objects is frame-construction cost, not scoring cost
    pool = rng.integers(0, 256, size=(n_rows, 32 * 32 * 3), dtype=np.uint8)
    raws = [pool[i].tobytes() for i in range(n_rows)]
    df = tft.TensorFrame.from_columns({"image_data": raws}, num_partitions=16)

    # host codec stage, measured alone (chunked thread-pool decode with
    # dense chunk assembly — was 2.80s in round 2, per-cell futures)
    t0 = time.perf_counter()
    decoded = df.decode_column("image_data", scorer.decode).cache().analyze()
    dt_decode = time.perf_counter() - t0

    # chip scoring stage over the decoded frame: the first pass pays the
    # host->HBM transfer (memoized per column) + XLA compile, later passes
    # measure the conv pipeline itself — the reference analog is repeated
    # scoring of a resident dataset, and it isolates chip rate from link
    # bandwidth
    def run():
        out = scorer.score_frame(decoded, "image_data")
        emb = out.cache().column_data("embedding").dense
        assert emb.shape == (n_rows, 256)
        return emb

    t0 = time.perf_counter()
    _sync(run())
    dt_first = time.perf_counter() - t0
    dt = _timeit(run, iters=2, warmup=0)

    # overlapped single-shot: decode runs on the pool several partitions
    # AHEAD of the chip (map_blocks decoders=), one end-to-end pass over
    # fresh binary rows. Each pass moves the full decoded 307MB
    # host->device; whether the link or the chip bounds it is not
    # re-measured on the current machine.
    def run_overlapped():
        out = scorer.score_frame(df, "image_data")
        return out.cache().column_data("embedding").dense

    t0 = time.perf_counter()
    _sync(run_overlapped())
    dt_overlap = time.perf_counter() - t0

    # per-pass cost of a resident dataset = chip pass; decode amortizes
    # once per dataset. rows_per_sec counts BOTH (decode + one chip pass),
    # matching how round 2's number was scored.
    return {
        "metric": "config4_image_scoring_rows_per_sec",
        "value": round(n_rows / (dt + dt_decode), 1),
        "unit": "rows/s",
        "seconds_per_pass": round(dt, 4),
        "decode_seconds_per_pass": round(dt_decode, 4),
        # first execution = XLA compile + host->HBM transfer + run; the
        # components are not separable without a second compile, so this is
        # reported as one labeled number rather than a fake decomposition
        "first_pass_seconds_incl_compile_and_transfer": round(dt_first, 4),
        "overlapped_fresh_ingest_seconds_per_pass": round(dt_overlap, 4),
        "model": model_name,
    }


def config5_distributed_sgd(
    n_rows: int = 262_144, dim: int = 64, steps: int = 10
) -> Dict:
    """Distributed SGD composed from the dataframe ops: map_blocks computes
    per-block gradient partials, reduce_blocks sums them (the reference's
    composition, DebugRowOps.scala:290-526), parameters update on the host.
    Runs over the default mesh (all available devices)."""
    import tensorframes_tpu as tft
    import tensorframes_tpu.parallel as par

    rng = np.random.default_rng(0)
    w_true = rng.normal(size=dim).astype(np.float32)
    x = rng.normal(size=(n_rows, dim)).astype(np.float32)
    y = (x @ w_true + 0.01 * rng.normal(size=n_rows)).astype(np.float32)
    df = tft.TensorFrame.from_columns({"x": x, "y": y}).analyze()
    mesh = par.make_mesh()

    def grad_fn(x, y, w):
        err = x @ w - y
        return {"g": (x * err[:, None])[None].sum(axis=1)}

    w = np.zeros(dim, dtype=np.float32)
    lr = 0.1 / n_rows

    def sum_fn(g_input):
        return {"g": g_input.sum(axis=0)}

    def step(w):
        partials = par.map_blocks(
            grad_fn, df, mesh=mesh, trim=True, constants={"w": w}
        ).cache().analyze()
        g = par.reduce_blocks(sum_fn, partials, mesh=mesh)
        return w - lr * np.asarray(g)

    w = step(w)  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(steps):
        w = step(w)
    dt = (time.perf_counter() - t0) / steps
    err = float(np.linalg.norm(w - w_true) / np.linalg.norm(w_true))

    # ORACLE: a numpy SGD running the IDENTICAL schedule (same init, lr,
    # step count, full-batch gradient). rel_param_error vs w_true only
    # measures convergence progress and cannot catch a wrong gradient;
    # the oracle delta can.
    w_oracle = np.zeros(dim, dtype=np.float32)
    for _ in range(steps + 1):  # +1: the warmup step also updated w
        err_vec = x @ w_oracle - y
        w_oracle = w_oracle - lr * (x * err_vec[:, None]).sum(axis=0)
    oracle_delta = float(
        np.linalg.norm(w - w_oracle) / (np.linalg.norm(w_oracle) + 1e-12)
    )
    # tolerance sized for backends whose default matmul precision is
    # bf16: a wrong gradient produces O(1) deltas, rounding drift stays
    # well under this (1.3e-6 on a v5e in r05)
    assert oracle_delta < 5e-2, (
        f"df-ops SGD diverged from the numpy oracle running the same "
        f"schedule: {oracle_delta}"
    )
    return {
        "metric": "config5_sgd_rows_per_sec",
        "value": round(n_rows / dt, 1),
        "unit": "rows/s",
        "seconds_per_step": round(dt, 4),
        # distance to the NOISY problem's generating weights — bounded
        # below by the noise floor, NOT an optimizer error (correctness is
        # the oracle delta, ~1e-6); named so the artifact can't be misread
        # as a 31% optimizer error
        "rel_param_error_vs_ground_truth_under_noise": round(err, 4),
        "oracle_rel_delta": round(oracle_delta, 8),
    }


def config6_grouped_aggregate(
    n_rows: int = 10_000_000, n_groups: int = 1024
) -> Dict:
    """Keyed aggregation at scale: 10M rows summed into 1024 groups through
    the segmented-scan aggregate (device sort + scan), against a
    multithreaded numpy host oracle (argsort + reduceat) — the reference
    ran this entirely in the JVM shuffle (``TensorFlowUDAF``,
    ``DebugRowOps.scala:601-695``)."""
    import tensorframes_tpu as tft

    rng = np.random.default_rng(0)
    x = rng.normal(size=n_rows).astype(np.float32)
    key = rng.integers(0, n_groups, size=n_rows).astype(np.int32)
    df = tft.TensorFrame.from_columns({"x": x, "key": key}).analyze()
    grouped = df.group_by("key")

    # one function object across passes: graph capture and its compiled
    # scan programs are memoized per function identity
    def agg_fn(x_input):
        return {"x": x_input.sum(axis=0)}

    def run():
        return tft.aggregate(agg_fn, grouped).cache().column_block("x")

    dt = _timeit(run, iters=3)

    def host_oracle():
        order = np.argsort(key, kind="stable")
        ks = key[order]
        xs = x[order]
        starts = np.flatnonzero(np.r_[True, ks[1:] != ks[:-1]])
        return ks[starts], np.add.reduceat(xs, starts)

    t0 = time.perf_counter()
    ok, osum = host_oracle()
    dt_host = time.perf_counter() - t0

    res = tft.aggregate(agg_fn, grouped).cache()
    got = {
        int(k): float(v)
        for k, v in zip(
            np.asarray(res.column_block("key")), np.asarray(res.column_block("x"))
        )
    }
    want = dict(zip(ok.tolist(), osum.tolist()))
    assert set(got) == set(want)
    worst = max(abs(got[k] - want[k]) / (abs(want[k]) + 1e-6) for k in want)
    assert worst < 1e-2, f"group sums diverge: {worst}"
    return {
        "metric": "config6_grouped_aggregate_rows_per_sec",
        "value": round(n_rows / dt, 1),
        "unit": "rows/s",
        "seconds_per_pass": round(dt, 4),
        "host_numpy_seconds": round(dt_host, 4),
        "vs_host_numpy": round(dt_host / dt, 3),
        "n_groups": n_groups,
    }


def config7_dense_map_rows(n_rows: int = 1_000_000) -> Dict:
    """1M-row dense ``map_rows`` vs the equivalent ``map_blocks``: the
    all-dense single-bucket fast path (device feeds, on-device chunk
    slicing/concat, no per-chunk host round-trips) should keep row-wise
    semantics within ~2x of block execution end to end (result pulled to
    host in both, so both pay one full transfer)."""
    import tensorframes_tpu as tft

    x = np.random.default_rng(0).normal(size=n_rows).astype(np.float32)
    df = tft.TensorFrame.from_columns({"x": x}).analyze()

    def row_fn(x):
        return {"y": x * 2.0 + 1.0}

    def blk_fn(x):
        return {"z": x * 2.0 + 1.0}

    def run_rows():
        return tft.map_rows(row_fn, df).cache().column_data("y").host()

    def run_blocks():
        return tft.map_blocks(blk_fn, df).cache().column_data("z").host()

    dt_rows = _timeit(run_rows, iters=3)
    dt_blocks = _timeit(run_blocks, iters=3)
    np.testing.assert_allclose(run_rows(), x * 2.0 + 1.0, rtol=1e-6)

    # CHIP-SIDE decomposition (chain-length differential, the kernel-row
    # methodology): the two paths' compiled programs — jit(vmap(fn)) for
    # rows, jit(fn) for blocks — chained so constant RTT/dispatch terms
    # cancel. This pins whether any end-to-end gap is chip work or link
    # round-trips: the row path's retry contract costs one extra sync
    # RTT per pass (eager materialization window), which is environment
    # latency, invisible chip-side.
    import jax

    from benchmarks.attention_bench import _diff_time

    xd = df.column_data("x").device()

    def rows_chain(n):
        def f(a):
            def body(_, acc):
                return jax.vmap(lambda v: v * 2.0 + 1.0)(acc)

            return jax.lax.fori_loop(0, n, body, a)

        return jax.jit(f)

    def blocks_chain(n):
        def f(a):
            def body(_, acc):
                return acc * 2.0 + 1.0

            return jax.lax.fori_loop(0, n, body, a)

        return jax.jit(f)

    est = 2 * x.nbytes / 819e9  # HBM-bound elementwise op
    t_rows_chip, _ = _diff_time(rows_chain, (xd,), est)
    t_blocks_chip, _ = _diff_time(blocks_chain, (xd,), est)

    return {
        "metric": "config7_dense_map_rows_rows_per_sec",
        "value": round(n_rows / dt_rows, 1),
        "unit": "rows/s",
        "seconds_per_pass": round(dt_rows, 4),
        "map_blocks_seconds_per_pass": round(dt_blocks, 4),
        "vs_map_blocks": round(dt_rows / dt_blocks, 3),
        "chip_side_row_program_us": round(t_rows_chip * 1e6, 1),
        "chip_side_block_program_us": round(t_blocks_chip * 1e6, 1),
        "vs_map_blocks_chip_side": round(t_rows_chip / t_blocks_chip, 3),
    }


def config8_string_key_aggregate(
    n_rows: int = 10_000_000, n_groups: int = 1024
) -> Dict:
    """10M-row aggregate grouped by a STRING key: key coding is vectorized
    (np.unique over a fixed-width byte array, first-appearance renumber) —
    the old per-row dict loop spent the whole pass in the interpreter.
    Reports coding time vs everything-else time."""
    import tensorframes_tpu as tft
    from tensorframes_tpu.engine.ops import _group_sort_impl

    rng = np.random.default_rng(0)
    x = rng.normal(size=n_rows).astype(np.float32)
    gid = rng.integers(0, n_groups, size=n_rows)
    # one bytes pool sliced per row: building 10M bytes objects is frame
    # construction cost, not aggregation cost
    names = np.char.add("grp_", gid.astype("U8")).astype("S12")
    keys = [bytes(names[i]) for i in range(n_rows)]
    df = tft.TensorFrame.from_columns({"k": keys, "x": x}).analyze()
    grouped = df.group_by("k")

    def agg_fn(x_input):
        return {"x": x_input.sum(axis=0)}

    def run():
        return tft.aggregate(agg_fn, grouped).cache().column_data("x").host()

    dt = _timeit(run, iters=2)

    # key coding + device sort measured on a FRESH frame after everything
    # is warm (the sort permutation memoizes per frame, which is the
    # production behavior but would hide the per-dataset cost; a cold
    # frame before warmup would charge XLA compiles to coding)
    df2 = tft.TensorFrame.from_columns({"k": keys, "x": x}).analyze()
    t0 = time.perf_counter()
    _group_sort_impl(df2, ["k"], {})
    dt_coding = time.perf_counter() - t0
    got = run()
    assert got.shape[0] == n_groups
    np.testing.assert_allclose(float(got.sum()), float(x.sum()), rtol=1e-3)

    # decompose the fresh-frame cost: the host coding pass alone (the
    # native list-direct coder, r05) vs the remainder — the codes upload
    # (narrowed to the smallest dtype that fits the group ids, here
    # uint16) + device argsort + boundary readback, which scale with
    # LINK bandwidth, not host speed. Without the split, a slow link
    # reads as a coding regression (r04's 4.36 s was ~75% upload).
    from tensorframes_tpu.data.packer import code_keys

    t0 = time.perf_counter()
    codes = code_keys(keys)
    dt_code_host = time.perf_counter() - t0
    code_bytes = None
    if codes is not None:
        mx = int(codes.max())
        width = 1 if mx < 256 else (2 if mx < 65536 else 4)
        code_bytes = n_rows * width
    # the sort permutation (and its coding pass) memoizes per frame, so
    # the timed passes above exclude coding; fresh data pays both, which
    # is what value reports
    return {
        "metric": "config8_string_key_aggregate_rows_per_sec",
        "value": round(n_rows / (dt + dt_coding), 1),
        "unit": "rows/s",
        "seconds_per_pass_memoized_sort": round(dt, 4),
        "key_coding_and_sort_seconds": round(dt_coding, 4),
        "key_coding_host_seconds": round(dt_code_host, 4)
        if codes is not None
        else None,
        "codes_upload_mb": round(code_bytes / 1e6, 1)
        if code_bytes
        else None,
        "upload_sort_readback_seconds": round(dt_coding - dt_code_host, 4)
        if codes is not None
        else None,
        "n_groups": n_groups,
    }


def config9_kmeans(
    n_rows: int = 1_000_000, dim: int = 16, k: int = 32, iters: int = 10
) -> Dict:
    """Lloyd k-means through the df ops (in-graph pre-aggregation +
    reduce merge, the reference demo's optimized pattern,
    ``kmeans_demo.py:101-171``), vs a numpy oracle running the IDENTICAL
    schedule (same seeded init, same update rule) — the oracle delta
    catches a wrong assignment/update, which a convergence curve cannot.
    Per iteration the host sees only the [k,d]+[k] partials (a few KB);
    the O(n*k*d) distance work stays on the MXU."""
    import tensorframes_tpu as tft
    from tensorframes_tpu.models import kmeans

    rng = np.random.default_rng(0)
    x = rng.normal(size=(n_rows, dim)).astype(np.float32)
    # well-separated planted clusters so the oracle path is stable
    x += rng.normal(size=(k, dim)).astype(np.float32)[
        rng.integers(0, k, size=n_rows)
    ] * 4.0
    df = tft.TensorFrame.from_columns({"features": x}).analyze()

    kmeans(df, "features", k=k, num_iters=1, seed=1)  # warmup/compile
    t0 = time.perf_counter()
    cents, _ = kmeans(df, "features", k=k, num_iters=iters, seed=1)
    dt = (time.perf_counter() - t0) / iters

    # numpy oracle, identical schedule
    def numpy_lloyd():
        r = np.random.default_rng(1)
        c = x[r.choice(n_rows, size=k, replace=False)].astype(x.dtype)
        for _ in range(iters):
            d2 = ((x[:, None, :] - c[None, :, :]) ** 2).sum(axis=-1)
            closest = np.argmin(d2, axis=1)
            nc = c.copy()
            for j in range(k):
                m = closest == j
                if m.any():
                    nc[j] = x[m].mean(axis=0)
            if np.linalg.norm(nc - c) == 0.0:
                c = nc
                break
            c = nc
        return c

    t0 = time.perf_counter()
    c_oracle = numpy_lloyd()
    dt_numpy = (time.perf_counter() - t0) / iters
    oracle_delta = float(
        np.linalg.norm(cents - c_oracle) / np.linalg.norm(c_oracle)
    )
    # argmin assignments are exact (elementwise f32 distances); only the
    # mean update can pick up rounding, so the bound stays tight
    assert oracle_delta < 1e-3, (
        f"kmeans centroids diverged from the numpy oracle running the "
        f"same schedule: {oracle_delta}"
    )
    return {
        "metric": "config9_kmeans_rows_per_sec_per_iter",
        "value": round(n_rows / dt, 1),
        "unit": "rows/s",
        "seconds_per_iter": round(dt, 4),
        "numpy_seconds_per_iter": round(dt_numpy, 4),
        "vs_numpy": round(dt_numpy / dt, 2),
        "oracle_rel_delta": round(oracle_delta, 8),
        "k": k,
        "dim": dim,
    }


def config10_streaming_map_blocks(n_rows: int = 200_000, d: int = 64) -> Dict:
    """Over-budget column: streaming ``map_blocks`` (host slices feed one
    partition at a time, HBM bounded at ~one block) vs the device-resident
    mode (column memoized in HBM, the engine default under the budget).

    The headline is ``overlap_efficiency`` = max(pure link, pure chip) /
    streaming pass — a perfectly pipelined stream takes ~max(link, chip)
    seconds, so 1.0 means transfers fully hide behind compute (or vice
    versa). Unlike a raw streaming time (or the previous (link+chip)/
    streaming ratio), this is normalized against the SAME RUN's measured
    link speed, so the link's rate divides out to first order: halve the
    link rate and both the numerator's link term and the stream's
    link-bound part double. The link leg is measured before AND after the
    streaming pass; ``link_stability`` witnesses whether the rate held
    (ratios from runs with link_stability far from 1 are suspect). The
    chip and link seconds are also reported separately (config 2 pattern)
    so regressions are attributable. The reference gets this overlap
    shape from Spark's partition iterator (``DebugRowOps.scala:766-803``).
    ``vs_resident`` is bounded by the host link's bandwidth (not
    re-measured on the current machine)."""
    import jax.numpy as jnp

    import tensorframes_tpu as tft
    from tensorframes_tpu.utils import get_config, set_config

    rng = np.random.default_rng(0)
    x = rng.normal(size=(n_rows, d)).astype(np.float32)  # ~50MB
    w = jnp.asarray(rng.normal(size=(d, d)).astype(np.float32) * 0.1)
    df = tft.TensorFrame.from_columns(
        {"x": x}, num_partitions=8
    ).analyze()

    def fn(x):
        return {"y": jnp.tanh(x @ w) @ w}

    def run():
        out = tft.map_blocks(fn, df, trim=True).cache()
        # resident mode: stays in HBM (_sync reads 1 element); streaming
        # mode: already host rows (the streamed pull IS part of the pass)
        return out.column_data("y").dense

    old = get_config().device_cache_bytes
    try:
        # resident mode: column cached in HBM, passes read from HBM
        set_config(device_cache_bytes=4 << 30)
        dt_resident = _timeit(run, iters=2)

        # pure transfer round trip: a streamed pass must move every
        # partition up AND its result partition down; serialize both to
        # get the no-overlap baseline
        import jax

        bounds = df.partition_bounds()

        def transfer_round_trip():
            part = None
            for lo, hi in bounds:
                part = jax.device_put(x[lo:hi])
                np.asarray(part)
            return part

        dt_transfer_pre = _timeit(transfer_round_trip, iters=2)

        # streaming mode: budget below the column size -> host slices in,
        # result partitions pulled back as they land
        set_config(device_cache_bytes=8 << 20)
        df.unpersist_device()
        dt_streaming = _timeit(run, iters=2)

        # second link measurement AFTER the stream: witnesses whether the
        # link rate held across the measurement window
        dt_transfer_post = _timeit(transfer_round_trip, iters=2)
    finally:
        set_config(device_cache_bytes=old)

    dt_transfer = (dt_transfer_pre + dt_transfer_post) / 2.0
    efficiency = max(dt_transfer, dt_resident) / dt_streaming
    return {
        "metric": "config10_streaming_overlap_efficiency",
        "value": round(efficiency, 3),
        "unit": "x",
        "streaming_seconds_per_pass": round(dt_streaming, 4),
        "chip_seconds_per_pass": round(dt_resident, 4),
        "link_seconds_per_pass": round(dt_transfer, 4),
        "link_stability": round(dt_transfer_pre / dt_transfer_post, 3),
        "overlap_ratio_legacy": round(
            (dt_transfer + dt_resident) / dt_streaming, 3
        ),
        "vs_resident": round(dt_streaming / dt_resident, 2),
        "column_mb": round(x.nbytes / 1e6, 1),
        "link_mb_per_s_round_trip": round(
            2 * x.nbytes / 1e6 / dt_transfer, 1
        ),
        "note": "overlap_efficiency ~1 means the stream takes "
        "max(link, chip) — transfers fully pipeline against compute; "
        "normalized against the same run's link measurements "
        "(floor: >= 0.6 on a stable link). vs_resident is "
        "link-bandwidth-bound (see docstring)",
    }


ALL_CONFIGS = {
    1: config1_add3,
    2: config2_vector_reduce,
    3: config3_mnist_scoring,
    4: config4_image_scoring,
    5: config5_distributed_sgd,
    6: config6_grouped_aggregate,
    7: config7_dense_map_rows,
    8: config8_string_key_aggregate,
    9: config9_kmeans,
    10: config10_streaming_map_blocks,
}
