"""Checkpoint/resume subsystem (Orbax-backed).

The reference has no trainable-state checkpointing at all (SURVEY §5 —
model state ships as frozen graph constants); on TPU this is a first-class
subsystem, so it gets first-class tests: pytree round-trips, sharded-params
round-trips over the 8-device mesh with shardings preserved, manager
retention, and trainer resume.
"""

import numpy as np
import pytest

pytest.importorskip(
    "orbax.checkpoint", reason="checkpoint subsystem is an optional extra"
)

import tensorframes_tpu.parallel as par
from tensorframes_tpu.utils.checkpoint import (
    CheckpointManager,
    restore_checkpoint,
    save_checkpoint,
)


def test_pytree_round_trip(tmp_path):
    tree = {
        "w": np.arange(12, dtype=np.float32).reshape(3, 4),
        "meta": {"b": np.ones(4, dtype=np.float64)},
    }
    save_checkpoint(str(tmp_path / "ck"), tree)
    out = restore_checkpoint(str(tmp_path / "ck"))
    np.testing.assert_array_equal(out["w"], tree["w"])
    np.testing.assert_array_equal(out["meta"]["b"], tree["meta"]["b"])


def test_sharded_params_round_trip_preserves_sharding(tmp_path):
    import jax

    trainer = par.ShardedSGDTrainer([8, 4, 2])
    params = trainer.init_params(0)
    save_checkpoint(str(tmp_path / "ck"), params)
    restored = restore_checkpoint(str(tmp_path / "ck"), template=params)
    for orig, back in zip(
        jax.tree.leaves(params), jax.tree.leaves(restored)
    ):
        np.testing.assert_allclose(np.asarray(orig), np.asarray(back))
        assert back.sharding.is_equivalent_to(orig.sharding, orig.ndim), (
            orig.sharding,
            back.sharding,
        )


def test_manager_retention_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "mgr"), max_to_keep=2)
    for step in (1, 2, 3):
        mgr.save(step, {"v": np.full(2, float(step))})
    assert mgr.latest_step() == 3
    step, tree = mgr.restore_latest()
    assert step == 3
    np.testing.assert_array_equal(tree["v"], [3.0, 3.0])
    mgr.close()
    # retention: only the last two steps remain on disk
    kept = sorted(
        int(p.name) for p in (tmp_path / "mgr").iterdir() if p.name.isdigit()
    )
    assert kept == [2, 3]


def test_trainer_fit_resume(tmp_path):
    import jax

    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 8)).astype(np.float32)
    y = rng.integers(0, 2, 32).astype(np.int32)
    ckdir = str(tmp_path / "train")

    trainer = par.ShardedSGDTrainer([8, 4, 2], lr=0.1)
    params_a, losses_a = trainer.fit(x, y, steps=4, resume=ckdir)
    assert len(losses_a) == 4

    # a fresh trainer resuming from the same dir starts at step 4: no new
    # steps to run, and it returns the checkpointed params
    trainer_b = par.ShardedSGDTrainer([8, 4, 2], lr=0.1)
    params_b, losses_b = trainer_b.fit(x, y, steps=4, resume=ckdir)
    assert losses_b == []
    for a, b in zip(jax.tree.leaves(params_a), jax.tree.leaves(params_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))

    # asking for more steps continues from the restored state
    params_c, losses_c = trainer_b.fit(x, y, steps=6, resume=ckdir)
    assert len(losses_c) == 2
    assert all(np.isfinite(l) for l in losses_c)


def _tiny_lm(seed):
    from tensorframes_tpu.models.transformer import TransformerLM

    return TransformerLM.init(
        seed, vocab=50, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_len=32,
    )


def _toks():
    return (
        np.random.default_rng(2)
        .integers(0, 50, size=(8, 16))
        .astype(np.int32)
    )


def test_transformer_fit_resume_matches_uninterrupted(tmp_path):
    """Interrupted-then-resumed transformer SGD reproduces the
    uninterrupted loss trajectory exactly (same compiled step, restored
    params) — covers the resume path through ``_sgd_loop``."""
    toks = _toks()
    full = _tiny_lm(0).fit(toks, steps=6, lr=0.05)
    ckdir = str(tmp_path / "lm")
    first = _tiny_lm(0).fit(
        toks, steps=3, lr=0.05, resume=ckdir, checkpoint_every=1
    )
    # a FRESH model object resuming = a restarted process
    rest = _tiny_lm(0).fit(
        toks, steps=6, lr=0.05, resume=ckdir, checkpoint_every=1
    )
    np.testing.assert_allclose(first + rest, full, rtol=1e-5, atol=1e-6)


def test_fit_pipelined_resume_matches_uninterrupted(tmp_path):
    """Resume through the PIPELINE layout: the restored stacked slab must
    be re-pinned to the pp axis (restored leaves come back committed to
    one device) and the trajectory must match the uninterrupted run."""
    from tensorframes_tpu.parallel import make_mesh

    toks = _toks()
    mesh = make_mesh({"pp": 2})
    full = _tiny_lm(1).fit_pipelined(toks, mesh, steps=4, lr=0.05, n_micro=2)
    ckdir = str(tmp_path / "pipe")
    first = _tiny_lm(1).fit_pipelined(
        toks, mesh, steps=2, lr=0.05, n_micro=2,
        resume=ckdir, checkpoint_every=1,
    )
    rest = _tiny_lm(1).fit_pipelined(
        toks, mesh, steps=4, lr=0.05, n_micro=2,
        resume=ckdir, checkpoint_every=1,
    )
    np.testing.assert_allclose(first + rest, full, rtol=1e-5, atol=1e-6)


def test_checkpoint_every_requires_resume_dir():
    from tensorframes_tpu.utils.checkpoint import run_checkpointed_loop

    with pytest.raises(ValueError, match="checkpoint_every"):
        run_checkpointed_loop(
            lambda s: (s, 0.0), {}, 2, checkpoint_every=1
        )


def test_fit_tp_resume_matches_uninterrupted(tmp_path):
    """Resume through the Megatron plan: restored committed leaves must be
    re-pinned to the dp x tp shardings before the jitted step."""
    from tensorframes_tpu.parallel import make_mesh

    toks = _toks()
    mesh = make_mesh({"dp": 4, "tp": 2})
    full = _tiny_lm(0).fit_tp(toks, mesh, steps=4, lr=0.05)
    ckdir = str(tmp_path / "tp")
    first = _tiny_lm(0).fit_tp(
        toks, mesh, steps=2, lr=0.05, resume=ckdir, checkpoint_every=1
    )
    rest = _tiny_lm(0).fit_tp(
        toks, mesh, steps=4, lr=0.05, resume=ckdir, checkpoint_every=1
    )
    np.testing.assert_allclose(first + rest, full, rtol=1e-5, atol=1e-6)


def test_fit_sharded_resume_matches_uninterrupted(tmp_path):
    """Resume through the sequence-parallel (ring) plan."""
    from tensorframes_tpu.parallel import make_mesh

    toks = (
        np.random.default_rng(3)
        .integers(0, 50, size=(4, 17))
        .astype(np.int32)
    )
    mesh = make_mesh({"dp": 2, "sp": 4})
    full = _tiny_lm(1).fit_sharded(toks, mesh, steps=4, lr=0.05)
    ckdir = str(tmp_path / "sp")
    first = _tiny_lm(1).fit_sharded(
        toks, mesh, steps=2, lr=0.05, resume=ckdir, checkpoint_every=1
    )
    rest = _tiny_lm(1).fit_sharded(
        toks, mesh, steps=4, lr=0.05, resume=ckdir, checkpoint_every=1
    )
    np.testing.assert_allclose(first + rest, full, rtol=1e-5, atol=1e-6)
