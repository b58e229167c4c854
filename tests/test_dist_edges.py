"""Edge cases for the dist layer: indivisible-dim fallback, hints outside a
rules context, and compressed collectives on degenerate gradients."""

import jax.numpy as jnp
import numpy as np
from jax.sharding import AbstractMesh

from repro.dist import sharding as shd
from repro.dist.compression import quantize_int8
from repro.dist.hints import get_rules, hint, sharding_rules
from repro.dist.mesh import make_local_mesh


def mesh1():
    return AbstractMesh((16, 16), ("data", "model"))


class TestCheckFallback:
    def test_indivisible_dim_drops_axis(self):
        spec = shd._check(mesh1(), (10, 48), ("data", "model"))
        assert tuple(spec) == (None, "model")

    def test_both_indivisible_fully_replicates(self):
        spec = shd._check(mesh1(), (3, 7), ("data", "model"))
        assert tuple(spec) == (None, None)

    def test_tuple_axis_partial_fit(self):
        """(pod, data) on a batch divisible by pod (2) but not pod*data (32)
        keeps the divisible prefix instead of dropping everything."""
        mesh = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
        spec = shd._check(mesh, (2, 64), (("pod", "data"), None))
        assert tuple(spec)[0] == "pod"

    def test_axis_never_used_twice(self):
        spec = shd._check(mesh1(), (32, 32), ("model", "model"))
        assert tuple(spec) == ("model", None)

    def test_unknown_axis_degrades_not_raises(self):
        """Rules naming an axis the mesh doesn't have must replicate."""
        mesh = AbstractMesh((4,), ("data",))
        spec = shd._check(mesh, (64, 64), ("data", "model"))
        assert tuple(spec) == ("data", None)

    def test_short_spec_padded_with_none(self):
        spec = shd._check(mesh1(), (32, 32, 32), ("data",))
        assert tuple(spec) == ("data", None, None)


class TestHintOutsideRules:
    def test_identity_object(self):
        x = jnp.ones((4, 8))
        assert hint(x, "dp", "tp") is x

    def test_no_rank_check_without_rules(self):
        """Outside a rules context hint must not even look at the roles."""
        x = jnp.ones((4, 8))
        assert hint(x, "dp") is x

    def test_rules_context_restored_after_exit(self):
        assert get_rules() is None
        with sharding_rules(make_local_mesh(1, 1)):
            assert get_rules() is not None
        assert get_rules() is None

    def test_nested_rules_restore_outer(self):
        m = make_local_mesh(1, 1)
        with sharding_rules(m) as outer:
            with sharding_rules(m):
                pass
            assert get_rules() is outer


# reuse the 1-device shard_map harness from the main compression tests
from test_compression import _PSUM  # noqa: E402


class TestCompressedPsumDegenerate:
    def test_zero_gradients(self):
        """All-zero gradients: scale 0 must not produce NaNs/Infs."""
        x = jnp.zeros((32,), jnp.float32)
        mean, err = _PSUM(x, jnp.zeros_like(x))
        assert np.all(np.asarray(mean) == 0.0)
        assert np.all(np.asarray(err) == 0.0)

    def test_constant_gradients(self):
        """A constant tensor maps to q = +/-127; the residual is at most one
        float rounding step and the EF invariant mean + err == x is exact."""
        x = jnp.full((16,), -3.5, jnp.float32)
        mean, err = _PSUM(x, jnp.zeros_like(x))
        s = 3.5 / 127.0
        assert np.abs(np.asarray(err)).max() <= s / 2
        np.testing.assert_array_equal(np.asarray(mean + err), np.asarray(x))

    def test_quantize_zero_tensor(self):
        q, s = quantize_int8(jnp.zeros((8,)))
        assert float(s) == 0.0
        assert np.all(np.asarray(q) == 0)
