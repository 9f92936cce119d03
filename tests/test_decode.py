"""Host decode stage: ``TensorFrame.decode_column`` + ``map_rows(decoders=)``.

The TPU-native replacement for the reference's decode-inside-the-graph
binary scoring (``read_image.py:147-167``): decode bytes on the host,
batch the numeric program on device — instead of one Session.run per row
(``DebugRowOps.scala:819-857``).
"""

import numpy as np
import pytest

import tensorframes_tpu as tft
from tensorframes_tpu import parallel
from tensorframes_tpu.frame import TensorFrame


def _bytes_frame(n=20, dim=8, parts=3, seed=0):
    rng = np.random.default_rng(seed)
    arrays = [rng.normal(size=dim).astype(np.float32) for _ in range(n)]
    raws = [a.tobytes() for a in arrays]
    df = TensorFrame.from_columns({"data": raws}, num_partitions=parts)
    return df, arrays


def _decode(b):
    return np.frombuffer(b, dtype=np.float32)


class TestDecodeColumn:
    def test_uniform_decode_is_dense(self):
        df, arrays = _bytes_frame()
        dec = df.decode_column("data", _decode)
        assert dec.is_lazy
        block = dec.cache().column_block("data")  # dense => MXU-ready
        np.testing.assert_array_equal(np.asarray(block), np.stack(arrays))
        assert dec.num_partitions == df.num_partitions

    def test_dense_decode_feeds_map_blocks(self):
        df, arrays = _bytes_frame()
        dec = df.decode_column("data", _decode)
        out = tft.map_blocks(lambda data: {"s": data.sum(axis=1)}, dec)
        np.testing.assert_allclose(
            np.asarray(out.cache().column_block("s")),
            np.stack(arrays).sum(axis=1),
            rtol=1e-6,
        )

    def test_varying_shapes_stay_ragged(self):
        raws = [np.arange(k, dtype=np.float64).tobytes() for k in (3, 5, 3)]
        df = TensorFrame.from_columns({"d": raws})
        dec = df.decode_column("d", lambda b: np.frombuffer(b, dtype=np.float64))
        out = tft.map_rows(lambda d: {"s": d.sum()}, dec).collect()
        assert [r.s for r in out] == [3.0, 10.0, 3.0]

    def test_dst_keeps_binary_column(self):
        df, arrays = _bytes_frame(n=5)
        dec = df.decode_column("data", _decode, dst="x").cache()
        assert set(dec.columns) == {"data", "x"}
        assert isinstance(dec.column_data("data").cell(0), bytes)
        np.testing.assert_array_equal(dec.column_data("x").cell(1), arrays[1])

    def test_dst_collision_rejected(self):
        df, _ = _bytes_frame(n=5)
        df = df.decode_column("data", _decode, dst="x").cache()
        with pytest.raises(ValueError, match="already exists"):
            df.decode_column("data", _decode, dst="x")

    def test_later_cells_cast_to_probe_dtype(self):
        # row 0 decodes f32; a decoder that returns f64 for later rows gets
        # cast so the declared schema holds
        df, _ = _bytes_frame(n=4)

        def promoting(b):
            a = np.frombuffer(b, dtype=np.float32)
            return a.astype(np.float64) if b != df.column_data("data").cell(0) else a

        dec = df.decode_column("data", promoting, num_threads=0).cache()
        assert dec.column_data("data").dense.dtype == np.float32

    def test_schema_declares_decoded_type(self):
        df, _ = _bytes_frame(n=5, dim=4)
        dec = df.decode_column("data", _decode)
        info = dec.schema["data"]
        assert info.scalar_type.name == "float32"
        assert info.nesting == 1

    def test_threaded_matches_serial(self):
        df, arrays = _bytes_frame(n=200)
        a = df.decode_column("data", _decode, num_threads=0).cache()
        b = df.decode_column("data", _decode, num_threads=4).cache()
        np.testing.assert_array_equal(
            np.asarray(a.column_block("data")), np.asarray(b.column_block("data"))
        )

    def test_missing_column(self):
        df, _ = _bytes_frame(n=5)
        with pytest.raises(KeyError):
            df.decode_column("nope", _decode)


class TestMapRowsDecoders:
    def test_matches_host_path(self):
        df, arrays = _bytes_frame(n=30, dim=6)
        w = np.arange(6, dtype=np.float32)

        # host per-row path (round-1 behavior)
        host = tft.map_rows(
            lambda data: {"y": np.frombuffer(data, dtype=np.float32) @ w}, df
        ).collect()
        # decoded + batched device path
        dev = tft.map_rows(
            lambda data: {"y": data @ w}, df, decoders={"data": _decode}
        ).collect()
        np.testing.assert_allclose(
            [r.y for r in dev], [r.y for r in host], rtol=1e-5
        )

    def test_feed_dict_placeholder_key(self):
        df, arrays = _bytes_frame(n=10, dim=4)
        out = tft.map_rows(
            lambda x: {"s": x.sum()},
            df,
            feed_dict={"x": "data"},
            decoders={"x": _decode},
        ).collect()
        np.testing.assert_allclose(
            [r.s for r in out], [a.sum() for a in arrays], rtol=1e-5
        )

    def test_feed_dict_wins_over_column_name_collision(self):
        # placeholder 'x' collides with an unrelated numeric column; the
        # explicit feed_dict routing must decode 'data', not column 'x'
        rng = np.random.default_rng(0)
        arrays = [rng.normal(size=4).astype(np.float32) for _ in range(6)]
        df = TensorFrame.from_columns(
            {"x": np.arange(6.0), "data": [a.tobytes() for a in arrays]}
        )
        out = tft.map_rows(
            lambda x: {"s": x.sum()},
            df,
            feed_dict={"x": "data"},
            decoders={"x": _decode},
        ).collect()
        np.testing.assert_allclose(
            [r.s for r in out], [a.sum() for a in arrays], rtol=1e-5
        )

    def test_unresolvable_decoder_key(self):
        df, _ = _bytes_frame(n=5)
        with pytest.raises(Exception, match="nope"):
            tft.map_rows(
                lambda data: {"s": data.sum()}, df, decoders={"nope": _decode}
            )

    def test_distributed_decoders(self):
        df, arrays = _bytes_frame(n=64, dim=8, parts=8)
        out = parallel.map_rows(
            lambda data: {"s": data.sum()}, df, decoders={"data": _decode}
        ).collect()
        np.testing.assert_allclose(
            [r.s for r in out], [a.sum() for a in arrays], rtol=1e-5
        )
