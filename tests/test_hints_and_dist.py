"""dist.hints role semantics + shard_map MoE parity with the global oracle."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.dist.hints import hint, sharding_rules, tp_divides
from repro.dist.mesh import make_local_mesh
from repro.models.moe import _moe_ffn_global, init_moe, moe_ffn


def test_hint_noop_without_rules():
    x = jnp.ones((4, 8))
    y = hint(x, "dp", "tp")
    assert y is x                      # identity, not even a constraint


def test_make_local_mesh_axes_are_auto():
    """``with_sharding_constraint`` (every ``hint``) refuses Explicit axes,
    which ``jax.make_mesh`` now builds by default."""
    mesh = make_local_mesh(1, 1)
    assert mesh.axis_names == ("data", "model")
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,) * 2


def test_hint_applies_under_rules():
    mesh = make_local_mesh(1, 1)
    with mesh, sharding_rules(mesh):
        def f(x):
            return hint(x, "dp", "tp") * 2
        out = jax.jit(f)(jnp.ones((4, 8)))
    np.testing.assert_array_equal(np.asarray(out), 2.0)


def test_hint_wrong_rank_asserts():
    mesh = make_local_mesh(1, 1)
    with mesh, sharding_rules(mesh):
        with pytest.raises(AssertionError):
            hint(jnp.ones((4, 8)), "dp")


def test_tp_divides_semantics():
    assert tp_divides(56)              # vacuous without rules
    mesh = make_local_mesh(1, 1)
    with sharding_rules(mesh):
        assert tp_divides(56)          # tp_size == 1 divides everything


def test_hint_degrades_on_indivisible():
    """Roles on indivisible dims must silently replicate, never fail."""
    mesh = make_local_mesh(1, 1)
    with mesh, sharding_rules(mesh):
        out = jax.jit(lambda x: hint(x, "dp", "tp", "seq"))(
            jnp.ones((3, 7, 5)))
    assert out.shape == (3, 7, 5)


class TestShardMapMoEParity:
    """The shard_map expert path must match the global-capacity oracle on a
    trivial (1,1) mesh (same local capacity == same drops == same numerics)."""

    @pytest.mark.parametrize("arch", ["arctic-480b", "deepseek-v3-671b"])
    def test_matches_global(self, arch):
        cfg = dataclasses.replace(get_smoke(arch), dtype="float32")
        p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(2, 16, cfg.d_model)), jnp.float32)
        ref, aux_ref = _moe_ffn_global(cfg, p, x)
        mesh = make_local_mesh(1, 1)
        with mesh, sharding_rules(mesh):
            out, aux = jax.jit(lambda p, x: moe_ffn(cfg, p, x))(p, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-5)

    def test_grads_flow_through_shard_map(self):
        cfg = dataclasses.replace(get_smoke("arctic-480b"), dtype="float32")
        p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
        x = jnp.asarray(np.random.default_rng(1).normal(
            size=(1, 8, cfg.d_model)), jnp.float32)
        mesh = make_local_mesh(1, 1)
        with mesh, sharding_rules(mesh):
            g = jax.jit(jax.grad(
                lambda w: moe_ffn(cfg, w, x)[0].sum()))(p)
        leaves = jax.tree.leaves(g)
        assert all(np.isfinite(np.asarray(l)).all() for l in leaves)
        assert any(float(jnp.abs(l).max()) > 0 for l in leaves)


def test_full_train_step_under_mesh_rules():
    """Whole train step (microbatched) lowers and runs under a mesh with
    sharding rules — the dry-run path at toy scale, actually executed."""
    from repro.train.optimizer import OptConfig, init_opt_state
    from repro.train.train_step import make_train_step
    from repro.models import init_params
    cfg = get_smoke("deepseek-v3-671b")
    oc = OptConfig()
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(oc, params)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)),
                                   jnp.int32)}
    batch["labels"] = jnp.roll(batch["tokens"], -1, 1)
    mesh = make_local_mesh(1, 1)
    with mesh, sharding_rules(mesh):
        step = jax.jit(make_train_step(cfg, oc, microbatches=2))
        p2, o2, m = step(params, opt, batch)
    assert np.isfinite(float(m["loss"]))
    assert int(o2["step"]) == 1
