"""Distributed-engine tests over the virtual 8-device CPU mesh (conftest
forces xla_force_host_platform_device_count=8), mirroring how the reference
exercises distribution through partitioning on a local master (SURVEY §4)."""

import numpy as np
import pytest

import tensorframes_tpu as tft
import tensorframes_tpu.parallel as par


@pytest.fixture(scope="module")
def mesh():
    import jax

    assert len(jax.devices()) == 8, "conftest must provide 8 virtual devices"
    return par.make_mesh()


def test_mesh_shapes():
    m = par.make_mesh({"dp": 4, "tp": 2})
    assert m.shape["dp"] == 4 and m.shape["tp"] == 2
    with pytest.raises(ValueError, match="devices"):
        par.make_mesh({"dp": 64})


class TestDistributedMapBlocks:
    def test_divisible(self, mesh):
        df = tft.TensorFrame.from_columns({"x": np.arange(16.0)})
        df2 = par.map_blocks(lambda x: {"z": x * 2.0}, df, mesh=mesh)
        assert [r.z for r in df2.collect()] == [2.0 * i for i in range(16)]

    def test_remainder_tail(self, mesh):
        df = tft.TensorFrame.from_columns({"x": np.arange(19.0)})
        df2 = par.map_blocks(lambda x: {"z": x + 1.0}, df, mesh=mesh)
        assert [r.z for r in df2.collect()] == [float(i + 1) for i in range(19)]

    def test_small_frame_all_tail(self, mesh):
        df = tft.TensorFrame.from_columns({"x": np.arange(3.0)})
        df2 = par.map_blocks(lambda x: {"z": -x}, df, mesh=mesh)
        assert [r.z for r in df2.collect()] == [0.0, -1.0, -2.0]

    def test_trim(self, mesh):
        df = tft.TensorFrame.from_columns({"x": np.arange(16.0)})
        df2 = par.map_blocks(
            lambda x: {"z": x[:1]}, df, mesh=mesh, trim=True
        )
        rows = df2.collect()
        # one row per shard
        assert len(rows) == 8

    def test_vector_columns(self, mesh):
        df = tft.TensorFrame.from_columns(
            {"y": [[float(i), float(-i)] for i in range(8)]}
        ).analyze()
        df2 = par.map_blocks(lambda y: {"s": y.sum(axis=1)}, df, mesh=mesh)
        assert [r.s for r in df2.collect()] == [0.0] * 8


class TestDistributedReduce:
    def test_reduce_blocks_sum(self, mesh):
        df = tft.TensorFrame.from_columns({"x": np.arange(16.0)})
        out = par.reduce_blocks(
            lambda x_input: {"x": x_input.sum()}, df, mesh=mesh
        )
        assert float(out) == sum(range(16))

    def test_reduce_blocks_vector_with_tail(self, mesh):
        df = tft.TensorFrame.from_columns(
            {"y": [[float(i), 1.0] for i in range(21)]}
        ).analyze()
        out = par.reduce_blocks(
            lambda y_input: {"y": y_input.sum(axis=0)}, df, mesh=mesh
        )
        np.testing.assert_allclose(out, [sum(range(21)), 21.0])

    def test_reduce_blocks_min(self, mesh):
        df = tft.TensorFrame.from_columns(
            {"x": np.array([5.0, -2.0, 9.0, 0.5] * 4)}
        )
        out = par.reduce_blocks(
            lambda x_input: {"x": x_input.min()}, df, mesh=mesh
        )
        assert float(out) == -2.0

    def test_reduce_rows(self, mesh):
        df = tft.TensorFrame.from_columns({"x": np.arange(17.0)})
        out = par.reduce_rows(
            lambda x_1, x_2: {"x": x_1 + x_2}, df, mesh=mesh
        )
        assert float(out) == sum(range(17))

    def test_matches_local_engine(self, mesh):
        rng = np.random.default_rng(3)
        data = rng.normal(size=(40, 3))
        df = tft.TensorFrame.from_columns({"y": data}).analyze()
        local = tft.reduce_blocks(
            lambda y_input: {"y": y_input.sum(axis=0)}, df
        )
        dist = par.reduce_blocks(
            lambda y_input: {"y": y_input.sum(axis=0)}, df, mesh=mesh
        )
        np.testing.assert_allclose(local, dist, rtol=1e-12)


def test_distributed_scalar_output_guard(mesh):
    df = tft.TensorFrame.from_columns({"x": np.arange(16.0)})
    with pytest.raises(tft.InvalidDimensionError, match="scalar"):
        par.map_blocks(lambda x: {"s": x.sum()}, df, mesh=mesh)


def test_mlp_params_update_invalidates_scoring_cache():
    from tensorframes_tpu.models import MLPClassifier, init_mlp

    df = tft.TensorFrame.from_columns(
        {"f": np.eye(4, dtype=np.float32)}
    ).analyze()
    clf = MLPClassifier.init(0, [4, 2])
    first = [r.prediction for r in clf.score_frame(df, "f").collect()]
    # swap in weights that force class 1 everywhere
    new = init_mlp(0, [4, 2])
    new[0]["w"][:] = 0.0
    new[0]["b"][:] = np.array([0.0, 100.0], dtype=np.float32)
    clf.params = new
    second = [r.prediction for r in clf.score_frame(df, "f").collect()]
    assert second == [1, 1, 1, 1]
    assert first != second or first == [1, 1, 1, 1]


class TestDistributedAggregate:
    def test_two_phase_matches_local(self, mesh):
        rng = np.random.default_rng(0)
        n = 50
        df = tft.TensorFrame.from_columns(
            {
                "k": rng.integers(0, 7, n).astype(np.int64),
                "v": rng.normal(size=n),
            }
        )
        local = tft.aggregate(
            lambda v_input: {"v": v_input.sum(axis=0)}, df.group_by("k")
        )
        dist = par.aggregate(
            lambda v_input: {"v": v_input.sum(axis=0)},
            df.group_by("k"),
            mesh=mesh,
        )
        lrows = {r.k: r.v for r in local.collect()}
        drows = {r.k: r.v for r in dist.collect()}
        assert set(lrows) == set(drows)
        for k in lrows:
            np.testing.assert_allclose(lrows[k], drows[k], rtol=1e-12)


class TestShardedTraining:
    def test_sgd_loss_decreases(self):
        m = par.make_mesh({"dp": 4, "tp": 2})
        trainer = par.ShardedSGDTrainer([8, 16, 3], mesh=m, lr=0.5)
        rng = np.random.default_rng(0)
        x = rng.normal(size=(32, 8)).astype(np.float32)
        y = (rng.integers(0, 3, 32)).astype(np.int32)
        params, losses = trainer.fit(x, y, steps=20)
        assert losses[-1] < losses[0]

    def test_param_shardings_alternate(self):
        m = par.make_mesh({"dp": 4, "tp": 2})
        trainer = par.ShardedSGDTrainer([8, 16, 3], mesh=m)
        sh = trainer.param_shardings()
        specs = [s["w"].spec for s in sh]
        assert specs[0] == (None, "tp")
        assert specs[1] == ("tp", None)

    def test_trained_model_scores_frame(self):
        m = par.make_mesh({"dp": 4, "tp": 2})
        trainer = par.ShardedSGDTrainer([4, 3], mesh=m, lr=0.3)
        rng = np.random.default_rng(1)
        x = rng.normal(size=(16, 4)).astype(np.float32)
        y = (x[:, 0] > 0).astype(np.int32)
        params, _ = trainer.fit(x, y, steps=30)
        from tensorframes_tpu.models import MLPClassifier
        import jax

        clf = MLPClassifier(jax.device_get(params))
        df = tft.TensorFrame.from_columns({"features": x}).analyze()
        scored = clf.score_frame(df, "features")
        preds = [r.prediction for r in scored.collect()]
        assert len(preds) == 16
        assert set(preds) <= {0, 1, 2}


class TestDistributedMapRows:
    """Distributed row ops (VERDICT r01 gap: the reference runs every op
    through its distributed plane, ``DebugRowOps.scala:396-477``)."""

    def test_dense_matches_local(self, mesh):
        x = np.random.default_rng(0).normal(size=(37, 3))
        df = tft.TensorFrame.from_columns({"v": x}).analyze()
        dist = par.map_rows(lambda v: {"s": v.sum()}, df, mesh=mesh)
        local = tft.map_rows(lambda v: {"s": v.sum()}, df)
        np.testing.assert_allclose(
            [r.s for r in dist.collect()], [r.s for r in local.collect()]
        )

    def test_scalar_cells_with_tail(self, mesh):
        # 19 rows over 8 devices: main=16 sharded, tail=3 local
        df = tft.TensorFrame.from_columns({"x": np.arange(19.0)})
        out = par.map_rows(lambda x: {"y": x * 10.0}, df, mesh=mesh)
        assert [r.y for r in out.collect()] == [10.0 * i for i in range(19)]

    def test_ragged_column(self, mesh):
        cells = [[1.0], [2.0, 3.0], [4.0, 5.0, 6.0]] * 6  # 18 rows, 3 buckets
        df = tft.TensorFrame.from_rows([{"v": c} for c in cells]).analyze()
        out = par.map_rows(lambda v: {"s": v.sum()}, df, mesh=mesh)
        expect = [float(np.sum(c)) for c in cells]
        assert [r.s for r in out.collect()] == expect

    def test_multi_fetch_and_passthrough(self, mesh):
        df = tft.TensorFrame.from_columns(
            {"a": np.arange(16.0), "b": np.arange(16.0) * 2}
        )
        out = par.map_rows(
            lambda a, b: {"lo": a - b, "hi": a + b}, df, mesh=mesh
        )
        rows = out.collect()
        assert set(out.columns) == {"lo", "hi", "a", "b"}
        assert rows[3].lo == -3.0 and rows[3].hi == 9.0

    def test_feed_dict_binding(self, mesh):
        df = tft.TensorFrame.from_columns({"col": np.arange(16.0)})
        out = par.map_rows(
            lambda x: {"y": x + 1.0}, df, mesh=mesh, feed_dict={"x": "col"}
        )
        assert out.collect()[5].y == 6.0

    def test_binary_delegates_to_host_path(self, mesh):
        df = tft.TensorFrame.from_rows(
            [{"blob": bytes([i] * (i + 1))} for i in range(10)]
        )
        out = par.map_rows(
            lambda blob: {"n": np.float64(len(blob))}, df, mesh=mesh
        )
        assert [r.n for r in out.collect()] == [float(i + 1) for i in range(10)]


class TestDistributedAggregateGeneralKeys:
    def test_binary_key_matches_local(self, mesh):
        rng = np.random.default_rng(3)
        names = [b"a", b"bb", b"ccc", b"dddd"]
        rows = [
            {"name": names[int(i)], "x": float(v)}
            for i, v in zip(rng.integers(0, 4, 50), rng.normal(size=50))
        ]
        df = tft.TensorFrame.from_rows(rows)
        dist = par.aggregate(
            lambda x_input: {"x": x_input.sum(axis=0)},
            df.group_by("name"),
            mesh=mesh,
        )
        local = tft.aggregate(
            lambda x_input: {"x": x_input.sum(axis=0)}, df.group_by("name")
        )
        d = sorted((r.name, round(r.x, 6)) for r in dist.collect())
        l = sorted((r.name, round(r.x, 6)) for r in local.collect())
        assert d == l

    def test_mixed_multi_key(self, mesh):
        rows = [
            {"s": [b"x", b"y"][i % 2], "k": np.int64(i % 3), "v": float(i)}
            for i in range(40)
        ]
        df = tft.TensorFrame.from_rows(rows)
        dist = par.aggregate(
            lambda v_input: {"v": v_input.sum(axis=0)},
            df.group_by("s", "k"),
            mesh=mesh,
        )
        local = tft.aggregate(
            lambda v_input: {"v": v_input.sum(axis=0)}, df.group_by("s", "k")
        )
        assert sorted((r.s, int(r.k), r.v) for r in dist.collect()) == sorted(
            (r.s, int(r.k), r.v) for r in local.collect()
        )
