"""CNN image scoring: the reference's frozen-VGG-over-binary-rows workload
(``read_image.py:147-167``) done TPU-first (host decode -> batched device
convs)."""

import numpy as np
import pytest

import tensorframes_tpu as tft
from tensorframes_tpu.frame import TensorFrame
from tensorframes_tpu.models import CNNScorer, cnn_embed, cnn_logits, init_cnn
from tensorframes_tpu.utils import get_config, set_config


def _image_frame(scorer, n=12, parts=3, seed=0):
    rng = np.random.default_rng(seed)
    h, w = scorer.input_hw
    imgs = rng.integers(0, 256, size=(n, h, w, scorer.channels), dtype=np.uint8)
    raws = [im.tobytes() for im in imgs]
    df = TensorFrame.from_columns({"image_data": raws}, num_partitions=parts)
    return df, imgs


class TestCNN:
    def test_embed_shapes(self):
        p = init_cnn(0, input_hw=(16, 16), block_widths=(8, 16), embed_dim=32)
        x = np.zeros((4, 16, 16, 3), dtype=np.uint8)
        emb = np.asarray(cnn_embed(p, x))
        assert emb.shape == (4, 32)
        assert emb.dtype == np.float32

    def test_logits_head(self):
        p = init_cnn(
            0, input_hw=(16, 16), block_widths=(8,), embed_dim=16, num_classes=5
        )
        x = np.random.default_rng(0).normal(size=(3, 16, 16, 3)).astype(np.float32)
        assert np.asarray(cnn_logits(p, x)).shape == (3, 5)
        with pytest.raises(ValueError, match="num_classes"):
            cnn_logits(init_cnn(0, input_hw=(16, 16), block_widths=(8,)), x)

    def test_uint8_normalized_on_device(self):
        p = init_cnn(0, input_hw=(8, 8), block_widths=(4,), embed_dim=8)
        img = np.random.default_rng(1).integers(
            0, 256, size=(2, 8, 8, 3), dtype=np.uint8
        )
        a = np.asarray(cnn_embed(p, img))
        b = np.asarray(cnn_embed(p, img.astype(np.float32) / 255.0))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)

    def test_score_frame_matches_direct(self):
        scorer = CNNScorer.init(
            0, input_hw=(16, 16), block_widths=(8, 16), embed_dim=32
        )
        df, imgs = _image_frame(scorer)
        out = scorer.score_frame(df, "image_data", compute_dtype=None)
        got = np.asarray(out.cache().column_block("embedding"))
        want = np.asarray(cnn_embed(scorer.params, imgs))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_score_frame_distributed(self):
        from tensorframes_tpu import parallel

        scorer = CNNScorer.init(
            0, input_hw=(16, 16), block_widths=(8,), embed_dim=16
        )
        df, imgs = _image_frame(scorer, n=32, parts=8)
        out = scorer.score_frame(
            df, "image_data", engine=parallel, compute_dtype=None
        )
        got = np.asarray(out.cache().column_block("embedding"))
        want = np.asarray(cnn_embed(scorer.params, imgs))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_bfloat16_close_to_f32(self):
        scorer = CNNScorer.init(
            0, input_hw=(16, 16), block_widths=(8,), embed_dim=16
        )
        df, imgs = _image_frame(scorer, n=6, parts=1)
        bf = np.asarray(
            scorer.score_frame(df, "image_data").cache().column_block("embedding")
        )
        f32 = np.asarray(cnn_embed(scorer.params, imgs))
        # bf16 matmul precision: loose tolerance, but must correlate tightly
        assert np.corrcoef(bf.ravel(), f32.ravel())[0, 1] > 0.999


class TestMapRowsChunking:
    def test_large_bucket_chunks_match_unchunked(self):
        old = get_config().max_rows_per_device_call
        try:
            df = TensorFrame.from_columns(
                {"x": np.arange(100, dtype=np.float64)}
            )
            fn = lambda x: {"y": x * 2.0}
            set_config(max_rows_per_device_call=7)  # forces 15 chunks
            chunked = [r.y for r in tft.map_rows(fn, df).collect()]
            set_config(max_rows_per_device_call=old)
            whole = [r.y for r in tft.map_rows(fn, df).collect()]
            assert chunked == whole
        finally:
            set_config(max_rows_per_device_call=old)
