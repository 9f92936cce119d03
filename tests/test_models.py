"""Model-zoo tests: k-means (reference snippet parity) and MLP scoring."""

import numpy as np
import pytest

import tensorframes_tpu as tft
import tensorframes_tpu.parallel as par
from tensorframes_tpu.models import (
    MLPClassifier,
    assign_clusters,
    kmeans,
)


def blob_data(n=300, d=5, k=3, seed=7):
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, 20, (k, d))
    labels = rng.integers(0, k, n)
    data = centers[labels] + rng.normal(0, 0.5, (n, d))
    return data.astype(np.float32), centers, labels


class TestKMeans:
    def test_recovers_blobs(self):
        data, centers, _ = blob_data()
        df = tft.TensorFrame.from_columns(
            {"features": data}, num_partitions=3
        ).analyze()
        centroids, history = kmeans(df, "features", k=3, num_iters=8, seed=1)
        assert centroids.shape == (3, 5)
        # every true center has a recovered centroid nearby
        for c in centers:
            assert np.min(np.linalg.norm(centroids - c, axis=1)) < 1.0
        assert history[-1] <= history[0]

    def test_assign_clusters(self):
        data, _, _ = blob_data(n=50)
        df = tft.TensorFrame.from_columns({"features": data}).analyze()
        centroids, _ = kmeans(df, "features", k=3, num_iters=5, seed=1)
        out = assign_clusters(df, "features", centroids)
        rows = out.collect()
        assert set(out.columns) >= {"closest_centroid", "distance", "features"}
        assert all(0 <= r.closest_centroid < 3 for r in rows)
        assert all(r.distance >= 0 for r in rows)

    def test_distributed_matches_local(self):
        data, _, _ = blob_data(n=160)
        df = tft.TensorFrame.from_columns({"features": data}).analyze()
        local_c, _ = kmeans(df, "features", k=3, num_iters=4, seed=2)
        dist_c, _ = kmeans(
            df,
            "features",
            k=3,
            num_iters=4,
            seed=2,
            distributed=True,
            mesh=par.make_mesh(),
        )
        np.testing.assert_allclose(local_c, dist_c, rtol=1e-4, atol=1e-4)


class TestMLPScoring:
    def test_probabilities_column(self):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(10, 6)).astype(np.float32)
        df = tft.TensorFrame.from_columns({"f": x}).analyze()
        clf = MLPClassifier.init(0, [6, 4, 3])
        out = clf.score_frame(df, "f", probabilities_col="probs")
        rows = out.collect()
        np.testing.assert_allclose(
            [float(np.sum(r.probs)) for r in rows], np.ones(10), rtol=1e-5
        )

    def test_scoring_reuses_graph(self):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(4, 6)).astype(np.float32)
        df = tft.TensorFrame.from_columns({"f": x}).analyze()
        clf = MLPClassifier.init(0, [6, 2])
        clf.score_frame(df, "f").cache()
        g1 = clf._graph_cache
        clf.score_frame(df, "f").cache()
        assert clf._graph_cache is g1 and len(g1) == 1
