"""Streaming host↔device transfers (``frame/transfer.py``).

The acceptance bar (ISSUE 5): chunked h2d/d2h must be **byte-identical**
to the monolithic paths — dense f32 / bf16 / byte-payload columns, odd
remainder chunks, 0-row and 1-row frames — including under injected
transient transfer faults, and the engine's streaming feeds (map_blocks
prefetch, map_rows device-resident pass) must not change any result.
CPU-only, seeded, deterministic.
"""

import numpy as np
import pytest

import tensorframes_tpu as tft
from tensorframes_tpu.engine import map_blocks, map_rows, reduce_blocks
from tensorframes_tpu.frame import transfer
from tensorframes_tpu.obs import metrics as obs_metrics
from tensorframes_tpu.utils import chaos, get_config, set_config


def _counter(name, **labels):
    try:
        return obs_metrics.registry().get(name).value(**labels)
    except KeyError:
        return 0.0


def _hist_count(name):
    try:
        s = obs_metrics.registry().get(name).series()
    except KeyError:
        return 0
    return 0 if s is None else s["count"]


@pytest.fixture
def tiny_chunks():
    """128-byte chunks, 3 streams: any column beyond a few rows splits
    into many odd-remainder chunks."""
    old = get_config()
    set_config(transfer_chunk_bytes=128, transfer_streams=3)
    yield
    set_config(
        transfer_chunk_bytes=old.transfer_chunk_bytes,
        transfer_streams=old.transfer_streams,
    )


@pytest.fixture
def fast_retries():
    old = (get_config().max_retries, get_config().retry_backoff_s)
    set_config(max_retries=3, retry_backoff_s=0.001)
    yield
    set_config(max_retries=old[0], retry_backoff_s=old[1])


def _roundtrip_bytes(x):
    """h2d then d2h through the streaming layer; returns host bytes."""
    dev = transfer.h2d(x)
    assert tuple(dev.shape) == x.shape and dev.dtype == x.dtype
    return transfer.d2h(dev).tobytes()


class TestH2DIdentity:
    """Chunked upload == monolithic device_put, byte for byte."""

    def test_f32_odd_remainder(self, tiny_chunks, rng):
        # 128-byte chunks over 28-byte rows -> 4 rows/chunk, 41 rows ->
        # 10 full chunks + a 1-row remainder
        x = rng.normal(size=(41, 7)).astype(np.float32)
        assert _roundtrip_bytes(x) == x.tobytes()

    def test_int32_and_uint8(self, tiny_chunks, rng):
        xi = rng.integers(-(2**31), 2**31 - 1, size=(57, 5), dtype=np.int32)
        assert _roundtrip_bytes(xi) == xi.tobytes()
        # byte payloads (the binary-adjacent dense form: u8 feature bytes)
        xb = rng.integers(0, 256, size=(300, 3), dtype=np.uint8)
        assert _roundtrip_bytes(xb) == xb.tobytes()

    def test_bf16_column(self, tiny_chunks, rng):
        import ml_dtypes

        x = rng.normal(size=(33, 9)).astype(np.float32).astype(
            ml_dtypes.bfloat16
        )
        assert _roundtrip_bytes(x) == x.tobytes()

    def test_zero_and_one_row(self, tiny_chunks):
        for n in (0, 1):
            x = np.arange(n * 4, dtype=np.float32).reshape(n, 4)
            assert _roundtrip_bytes(x) == x.tobytes()

    def test_scalar_roundtrip(self, tiny_chunks):
        # 0-d arrays cross whole in both directions (h2d/d2h symmetry)
        x = np.array(3.25, dtype=np.float32)
        assert _roundtrip_bytes(x) == x.tobytes()

    def test_single_chunk_when_it_fits(self, rng):
        # default 64 MiB chunk: small columns pay nothing for chunking
        x = rng.normal(size=(16, 4)).astype(np.float32)
        su = transfer.StreamingUpload(x)
        assert su.num_chunks == 1
        assert np.asarray(su.assembled()).tobytes() == x.tobytes()

    def test_chunk_count_is_capped(self):
        old = get_config().transfer_chunk_bytes
        set_config(transfer_chunk_bytes=1)
        try:
            bounds = transfer._chunk_bounds(100_000, 4)
            assert len(bounds) <= transfer._MAX_CHUNKS
            assert bounds[0][0] == 0 and bounds[-1][1] == 100_000
        finally:
            set_config(transfer_chunk_bytes=old)

    def test_chunking_disabled_is_monolithic(self, rng):
        old = get_config().transfer_chunk_bytes
        set_config(transfer_chunk_bytes=0)
        try:
            x = rng.normal(size=(1000, 8)).astype(np.float32)
            su = transfer.StreamingUpload(x)
            assert su.num_chunks == 1
            assert np.asarray(su.assembled()).tobytes() == x.tobytes()
        finally:
            set_config(transfer_chunk_bytes=old)


class TestStreamSlices:
    def test_slices_across_chunk_boundaries(self, tiny_chunks, rng):
        x = rng.normal(size=(50, 7)).astype(np.float32)
        cd = tft.TensorFrame.from_columns({"x": x}).column_data("x")
        su = cd.device_stream()
        assert su.num_chunks > 3
        for lo, hi in [(0, 3), (2, 9), (4, 8), (0, 50), (49, 50), (7, 43)]:
            got = np.asarray(su.slice(lo, hi))
            assert got.tobytes() == x[lo:hi].tobytes(), (lo, hi)

    def test_device_memoizes_assembled(self, tiny_chunks, rng):
        x = rng.normal(size=(40, 4)).astype(np.float32)
        cd = tft.TensorFrame.from_columns({"x": x}).column_data("x")
        before = _counter("frame.h2d_bytes_total")
        d1 = cd.device()
        assert _counter("frame.h2d_bytes_total") - before == x.nbytes
        d2 = cd.device()
        assert d2 is d1  # memoized: the column crossed once
        assert _counter("frame.h2d_bytes_total") - before == x.nbytes
        assert cd._stream is None

    def test_unpersist_releases_the_stream(self, tiny_chunks, rng):
        x = rng.normal(size=(40, 4)).astype(np.float32)
        df = tft.TensorFrame.from_columns({"x": x})
        df.column_data("x").device_stream()
        df.unpersist_device()
        assert df.column_data("x")._stream is None


class TestD2HIdentity:
    def test_chunked_fetch_matches_monolithic(self, tiny_chunks, rng):
        import jax

        x = rng.normal(size=(61, 5)).astype(np.float32)
        dev = jax.device_put(x)
        got = transfer.d2h(dev)
        assert got.tobytes() == np.asarray(dev).tobytes() == x.tobytes()

    def test_column_host_roundtrip(self, tiny_chunks, rng):
        import jax

        x = rng.normal(size=(45, 6)).astype(np.float32)
        df = tft.TensorFrame.from_columns({"x": jax.device_put(x)})
        assert df.column_data("x").host().tobytes() == x.tobytes()

    def test_d2h_async_overlaps(self, tiny_chunks, rng):
        import jax

        xs = [
            jax.device_put(rng.normal(size=(40, 4)).astype(np.float32))
            for _ in range(3)
        ]
        pending = [transfer.d2h_async(d) for d in xs]
        outs = [p.result() for p in pending]
        for d, o in zip(xs, outs):
            assert o.tobytes() == np.asarray(d).tobytes()


class TestWireCast:
    def test_bf16_wire_rounds_values_keeps_dtype(self, tiny_chunks, rng):
        import ml_dtypes

        x = rng.normal(size=(37, 5)).astype(np.float32)
        old = get_config().transfer_dtype
        set_config(transfer_dtype="bf16")
        try:
            before = _counter("frame.h2d_bytes_total")
            cd = tft.TensorFrame.from_columns({"x": x}).column_data("x")
            dev = cd.device()
            assert np.dtype(dev.dtype) == np.float32  # device dtype intact
            exp = x.astype(ml_dtypes.bfloat16).astype(np.float32)
            assert np.array_equal(np.asarray(dev), exp)
            # half the bytes ever crossed the wire
            assert _counter("frame.h2d_bytes_total") - before == x.nbytes // 2
        finally:
            set_config(transfer_dtype=old)

    def test_non_f32_payloads_are_untouched(self, tiny_chunks, rng):
        xi = rng.integers(0, 100, size=(29, 3), dtype=np.int32)
        old = get_config().transfer_dtype
        set_config(transfer_dtype="bf16")
        try:
            assert _roundtrip_bytes(xi) == xi.tobytes()
        finally:
            set_config(transfer_dtype=old)

    def test_unknown_wire_dtype_fails_loudly(self):
        old = get_config().transfer_dtype
        set_config(transfer_dtype="fp8")
        try:
            with pytest.raises(ValueError, match="transfer_dtype"):
                transfer.h2d(np.zeros((4, 4), np.float32))
        finally:
            set_config(transfer_dtype=old)


@pytest.mark.chaos
class TestTransferChaos:
    """Transient link faults during chunked transfers retry per chunk
    and the landed bytes stay identical — the no-retry ingest kill of
    the monolithic era is gone."""

    def test_h2d_transient_faults_retry_byte_identical(
        self, tiny_chunks, fast_retries, rng
    ):
        x = rng.normal(size=(53, 7)).astype(np.float32)
        i0 = _counter("chaos.injections_total", site="frame.h2d",
                      kind="transient")
        r0 = _counter("failures.retries_total", op="frame.h2d",
                      reason="UNAVAILABLE")
        with chaos.scoped("seed=3;frame.h2d=transient:every=3"):
            dev = transfer.h2d(x)
        assert np.asarray(dev).tobytes() == x.tobytes()
        assert _counter("chaos.injections_total", site="frame.h2d",
                        kind="transient") > i0
        assert _counter("failures.retries_total", op="frame.h2d",
                        reason="UNAVAILABLE") > r0

    def test_d2h_transient_faults_retry_byte_identical(
        self, tiny_chunks, fast_retries, rng
    ):
        import jax

        x = rng.normal(size=(53, 7)).astype(np.float32)
        dev = jax.device_put(x)
        with chaos.scoped("seed=5;frame.d2h=transient:every=3"):
            got = transfer.d2h(dev)
        assert got.tobytes() == x.tobytes()

    def test_exhausted_retries_surface_the_error(
        self, tiny_chunks, fast_retries, rng
    ):
        x = rng.normal(size=(40, 4)).astype(np.float32)
        with chaos.scoped("frame.h2d=transient"):  # fires on EVERY call
            with pytest.raises(RuntimeError, match="UNAVAILABLE"):
                transfer.h2d(x)

    def test_engine_pass_survives_transfer_faults(
        self, tiny_chunks, fast_retries, rng
    ):
        x = rng.normal(size=(64, 6)).astype(np.float32)
        df = tft.TensorFrame.from_columns({"x": x}, num_partitions=3)
        df = df.analyze()
        with chaos.scoped("seed=11;frame.h2d=transient:every=4"):
            out = map_blocks(lambda x: {"y": x * 2.0}, df)
            got = out.column_data("y").host()
        assert np.array_equal(got, x * 2.0)


class TestEngineStreaming:
    """The engine's block loops consume chunks as they land; results
    must be identical to the monolithic-upload era."""

    def test_map_blocks_chunked_feed_identity(self, tiny_chunks, rng):
        x = rng.normal(size=(101, 7)).astype(np.float32)
        df = tft.TensorFrame.from_columns({"x": x}, num_partitions=4)
        df = df.analyze()
        got = map_blocks(lambda x: {"y": x + 1.0}, df).column_data("y")
        assert np.array_equal(got.host(), x + 1.0)

    def test_map_blocks_overbudget_upload_prefetch(self, tiny_chunks, rng):
        """Over-budget columns stream host blocks through the prefetching
        uploader (block i+1 crosses while i computes)."""
        old = get_config().device_cache_bytes
        set_config(device_cache_bytes=256)  # force host streaming
        try:
            x = rng.normal(size=(90, 5)).astype(np.float32)
            df = tft.TensorFrame.from_columns(
                {"x": x}, num_partitions=6
            ).analyze()
            before = _counter("frame.h2d_bytes_total")
            got = map_blocks(lambda x: {"y": x * 3.0}, df).column_data("y")
            assert np.array_equal(got.host(), x * 3.0)
            # every streamed block crossed through the transfer layer
            assert _counter("frame.h2d_bytes_total") - before >= x.nbytes
        finally:
            set_config(device_cache_bytes=old)

    def test_map_rows_chunked_identity(self, tiny_chunks, rng):
        x = rng.normal(size=(77, 4)).astype(np.float32)
        df = tft.TensorFrame.from_columns({"x": x}).analyze()
        got = map_rows(lambda x: {"y": x * 2.0 + 1.0}, df).column_data("y")
        assert np.array_equal(got.host(), x * 2.0 + 1.0)

    def test_map_rows_sync_path_counts_feed_uploads(self, tiny_chunks, rng):
        """The synchronous chunked path (device-residency off) uploads
        its feeds explicitly: counted, retried, chaos-injectable."""
        old = get_config().max_rows_per_device_call
        set_config(max_rows_per_device_call=16)
        try:
            x = rng.normal(size=(64, 4)).astype(np.float32)
            # ragged second column forces the bucketed (non-fast) path
            cells = [
                rng.normal(size=(2 + (i % 2),)).astype(np.float32)
                for i in range(64)
            ]
            df = tft.TensorFrame.from_columns(
                {"x": x, "r": cells}
            ).analyze()
            before = _counter("frame.h2d_bytes_total")
            got = map_rows(
                lambda x: {"y": x.sum()}, df, feed_dict={"x": "x"}
            ).column_data("y")
            assert np.allclose(got.host(), x.sum(axis=1), rtol=1e-6)
            assert _counter("frame.h2d_bytes_total") - before >= x.nbytes
        finally:
            set_config(max_rows_per_device_call=old)

    def test_reduce_blocks_chunked_identity(self, tiny_chunks, rng):
        x = rng.normal(size=(66, 3)).astype(np.float32)
        df = tft.TensorFrame.from_columns(
            {"x": x}, num_partitions=3
        ).analyze()
        got = reduce_blocks(
            lambda x_input: {"x": x_input.sum(axis=0)}, df
        )
        assert np.allclose(np.asarray(got), x.sum(axis=0), rtol=1e-5)

    def test_unanalyzed_map_rows_uploads_bound_columns_once(self, rng):
        """The ROADMAP item-2 double-upload regression (fixed in ISSUE
        12): ``map_rows`` on an UN-analyzed frame has unknown out-spec
        dims, so the device-resident fast path must bail — and it must
        bail BEFORE probing ``_block_feeder``, which starts the
        column's chunked upload. The old order started that upload,
        bailed, and then the ``run_chunk`` fallback re-uploaded every
        chunk via explicit h2d: the column crossed the link TWICE. The
        exact-equality assert pins single-crossing."""
        x = rng.normal(size=(50_000, 8)).astype(np.float32)
        df = tft.TensorFrame.from_columns({"x": x})  # NOT analyzed
        before = _counter("frame.h2d_bytes_total")
        got = map_rows(lambda x: {"y": x + 1.0}, df).column_data("y")
        assert np.array_equal(got.host(), x + 1.0)
        assert _counter("frame.h2d_bytes_total") - before == x.nbytes

    def test_analyzed_map_rows_also_uploads_once(self, rng):
        """The fast path itself (analyzed frame, known out specs) has
        always uploaded once via the streaming feeder; pin it so the
        bail-out reorder cannot regress the happy path either."""
        x = rng.normal(size=(50_000, 8)).astype(np.float32)
        df = tft.TensorFrame.from_columns({"x": x}).analyze()
        before = _counter("frame.h2d_bytes_total")
        got = map_rows(lambda x: {"y": x + 1.0}, df).column_data("y")
        assert np.array_equal(got.host(), x + 1.0)
        assert _counter("frame.h2d_bytes_total") - before == x.nbytes


class TestTelemetry:
    def test_histograms_and_gauge(self, tiny_chunks, rng):
        import jax

        x = rng.normal(size=(40, 4)).astype(np.float32)
        h0, d0 = _hist_count("frame.h2d_seconds"), _hist_count(
            "frame.d2h_seconds"
        )
        dev = transfer.h2d(x)
        transfer.d2h(jax.device_put(x))
        assert _hist_count("frame.h2d_seconds") > h0
        assert _hist_count("frame.d2h_seconds") > d0
        # gauge is back to zero once nothing is in flight
        assert _counter("ingest.inflight_chunks") == 0
        del dev
