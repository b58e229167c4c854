"""Training loop: convergence, checkpoint/restart determinism, elasticity,
optimizer behaviour."""

import dataclasses
import itertools
import os
import tempfile
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_smoke
from repro.data.pipeline import SyntheticCorpus
from repro.dist.mesh import make_local_mesh
from repro.models import model as M
from repro.train import checkpoint as ckpt
from repro.train.elastic import elastic_restore
from repro.train.loop import TrainConfig, check_state_fits, train
from repro.train.optimizer import (OptConfig, adamw_update, global_norm,
                                   init_opt_state, schedule)
from repro.train.train_step import make_train_step


def test_loss_decreases():
    cfg = get_smoke("granite-3-2b")
    r = train(cfg, TrainConfig(steps=25, batch=4, seq=32))
    assert r.steps_done == 25
    assert r.losses[-1] < r.losses[0] * 0.9


def test_failure_restart_reaches_same_final_loss():
    """Restart replays the same batches: final loss must match no-failure."""
    cfg = get_smoke("minitron-8b")
    with tempfile.TemporaryDirectory() as d1:
        base = train(cfg, TrainConfig(steps=20, batch=4, seq=32,
                                      ckpt_every=10, ckpt_dir=d1))
    with tempfile.TemporaryDirectory() as d2:
        failed = train(cfg, TrainConfig(steps=20, batch=4, seq=32,
                                        ckpt_every=10, ckpt_dir=d2,
                                        simulate_failure_at=15))
    assert failed.restarts == 1
    np.testing.assert_allclose(base.losses[-1], failed.losses[-1],
                               rtol=2e-2)


class TestCheckpoint:
    def test_roundtrip_bf16(self):
        tree = {"a": jnp.ones((4, 4), jnp.bfloat16) * 1.5,
                "b": {"c": jnp.arange(6, dtype=jnp.int32)}}
        with tempfile.TemporaryDirectory() as d:
            ckpt.save(tree, d, 3)
            assert ckpt.latest_step(d) == 3
            tgt = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
            out = ckpt.restore(d, target=tgt)
        np.testing.assert_array_equal(
            np.asarray(out["a"], np.float32), np.asarray(tree["a"], np.float32))
        np.testing.assert_array_equal(out["b"]["c"], tree["b"]["c"])

    def test_latest_pointer_tracks_newest(self):
        with tempfile.TemporaryDirectory() as d:
            ckpt.save({"x": jnp.zeros(2)}, d, 1)
            ckpt.save({"x": jnp.ones(2)}, d, 2)
            assert ckpt.latest_step(d) == 2

    def test_async_checkpointer(self):
        with tempfile.TemporaryDirectory() as d:
            ac = ckpt.AsyncCheckpointer(d)
            ac.save_async({"x": jnp.ones((128, 128))}, 5)
            ac.wait()
            assert ckpt.latest_step(d) == 5

    def test_atomicity_no_tmp_left(self):
        with tempfile.TemporaryDirectory() as d:
            ckpt.save({"x": jnp.zeros(3)}, d, 7)
            assert not any(p.endswith(".tmp") for p in os.listdir(d))


def test_elastic_restore_new_mesh():
    """Checkpoint written ungridded restores onto a (1,1) production-style
    mesh with rule-derived shardings (full reshard path)."""
    cfg = get_smoke("granite-3-2b")
    oc = OptConfig()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(oc, params)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save({"p": params, "o": opt}, d, 11)
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2,
                             devices=jax.devices()[:1])
        p2, o2, step = elastic_restore(cfg, oc, d, mesh)
    assert step == 11
    a = jax.tree.leaves(params)[0]
    b = jax.tree.leaves(p2)[0]
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))


class TestOptimizer:
    def test_schedule_warmup_and_decay(self):
        oc = OptConfig(lr=1.0, warmup_steps=10, total_steps=100)
        assert float(schedule(oc, jnp.asarray(0))) == 0.0
        assert float(schedule(oc, jnp.asarray(10))) == pytest.approx(1.0)
        assert float(schedule(oc, jnp.asarray(100))) == pytest.approx(0.1)

    def test_clipping_bounds_update(self):
        oc = OptConfig(lr=1e-2, clip_norm=1.0, weight_decay=0.0)
        params = {"w": jnp.zeros((4, 4))}
        st = init_opt_state(oc, params)
        huge = {"w": jnp.full((4, 4), 1e6)}
        new_p, st, m = adamw_update(oc, huge, st, params)
        assert float(m["grad_norm"]) > 1e5
        assert float(jnp.abs(new_p["w"]).max()) < 1.0

    def test_no_decay_on_vectors(self):
        oc = OptConfig(lr=1e-1, weight_decay=1.0)
        params = {"w": jnp.ones((4, 4)), "g": jnp.ones((4,))}
        st = init_opt_state(oc, params)
        zeros = jax.tree.map(jnp.zeros_like, params)
        new_p, _, _ = adamw_update(oc, zeros, st, params)
        # matrix decayed, vector untouched (zero grad, no wd on 1-D)
        assert float(new_p["w"][0, 0]) < 1.0
        assert float(new_p["g"][0]) == pytest.approx(1.0)

    def test_moment_dtype_bf16(self):
        oc = OptConfig(moment_dtype="bfloat16")
        st = init_opt_state(oc, {"w": jnp.zeros((2, 2), jnp.bfloat16)})
        assert st["m"]["w"].dtype == jnp.bfloat16

    def test_global_norm(self):
        t = {"a": jnp.ones((3,)), "b": jnp.ones((4,))}
        assert float(global_norm(t)) == pytest.approx(np.sqrt(7.0))


def test_mesh_train_matches_unsharded_across_restart():
    """``train`` (sharded step, sharded init, elastic restore onto the host
    mesh) reproduces a plain jitted step over the same batches, through a
    failure and a checkpoint restore."""
    cfg = dataclasses.replace(get_smoke("granite-3-2b"), dtype="float32")
    oc = OptConfig(warmup_steps=10, total_steps=8)
    step = jax.jit(make_train_step(cfg, oc, microbatches=2))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(oc, params)
    want = []
    for b in itertools.islice(
            SyntheticCorpus(cfg.vocab, seed=0).batches(2, 32), 8):
        params, opt, m = step(params, opt, b)
        want.append(float(m["loss"]))
    with tempfile.TemporaryDirectory() as d:
        r = train(cfg, TrainConfig(steps=8, batch=2, seq=32, ckpt_every=4,
                                   ckpt_dir=d, simulate_failure_at=6,
                                   microbatches=2), oc)
    assert r.restarts == 1 and r.steps_done == 8
    # steps 1-6, then steps 5-8 again from the step-4 checkpoint
    np.testing.assert_allclose(r.losses, want[:6] + want[4:], rtol=1e-5)


@pytest.mark.parametrize("rows,fits", [(8, True), (16, False)])
def test_check_state_fits_against_device_limit(rows, fits):
    """The state's per-device shard bytes are held against the device's
    ``bytes_limit`` before anything is allocated."""
    class Dev:
        def memory_stats(self):
            return {"bytes_limit": 1000}

    mesh = make_local_mesh(1, 1)
    shapes = {"w": jax.ShapeDtypeStruct((rows, 16), jnp.float32)}
    shardings = {"w": NamedSharding(mesh, P())}
    stub = types.SimpleNamespace(devices=np.array([Dev()]), shape=mesh.shape)
    if fits:
        check_state_fits(shapes, shardings, stub)
    else:
        with pytest.raises(MemoryError, match="per device"):
            check_state_fits(shapes, shardings, stub)


def test_failure_before_first_checkpoint_cold_restarts():
    """A failure BEFORE any checkpoint exists must cold-restart (fresh init,
    deterministic data replay), not crash on a missing manifest."""
    cfg = get_smoke("granite-3-2b")
    with tempfile.TemporaryDirectory() as d:
        r = train(cfg, TrainConfig(steps=12, batch=2, seq=32, ckpt_every=50,
                                   ckpt_dir=d, simulate_failure_at=5))
    assert r.restarts == 1 and r.steps_done == 12
