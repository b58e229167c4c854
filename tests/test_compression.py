"""int8 error-feedback gradient compression: exactness bounds + convergence."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.dist.compression import (compressed_psum, compression_ratio,
                                    dequantize_int8, quantize_int8, wrap_grads)


def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s) - x))
    assert err.max() <= float(s) / 2 + 1e-7     # half-ULP of the int8 grid


def test_compression_ratio_near_4x():
    t = {"w": jnp.zeros((1024, 1024))}
    assert 3.9 < compression_ratio(t) <= 4.0


_MESH = Mesh(np.array(jax.devices()[:1]), ("d",))
_PSUM = jax.jit(jax.shard_map(
    lambda a, e: compressed_psum(a, "d", e),
    mesh=_MESH, in_specs=jax.sharding.PartitionSpec(),
    out_specs=jax.sharding.PartitionSpec(), check_vma=False))


def _psum_1dev(x, err):
    """Run compressed_psum under a 1-device shard_map (API-level check)."""
    return _PSUM(x, err)


def test_compressed_psum_single_device_identity_up_to_quantization():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
    mean, err = _psum_1dev(x, jnp.zeros_like(x))
    # value+err must reconstruct x exactly (error feedback invariant)
    np.testing.assert_allclose(np.asarray(mean + err), np.asarray(x),
                               rtol=1e-6, atol=1e-6)


def test_error_feedback_unbiased_over_steps():
    """Accumulated compressed means -> true mean (EF eliminates bias)."""
    rng = np.random.default_rng(2)
    g = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    err = jnp.zeros_like(g)
    acc = np.zeros(128, np.float32)
    T = 80
    for _ in range(T):
        mean, err = _psum_1dev(g, err)
        acc += np.asarray(mean)
    np.testing.assert_allclose(acc / T, np.asarray(g), rtol=5e-3, atol=5e-3)


def test_wrap_grads_pytree():
    mesh = Mesh(np.array(jax.devices()[:1]), ("d",))
    grads = {"a": jnp.ones((8,)), "b": {"c": jnp.full((4,), -2.0)}}

    def f(g):
        return wrap_grads(g, "d", None)

    sm = jax.shard_map(f, mesh=mesh, in_specs=jax.sharding.PartitionSpec(),
                       out_specs=jax.sharding.PartitionSpec(), check_vma=False)
    out, err = sm(grads)
    np.testing.assert_allclose(np.asarray(out["a"]), 1.0, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(out["b"]["c"]), -2.0, rtol=1e-2)
    assert jax.tree.structure(err) == jax.tree.structure(grads)


def test_ef_sgd_converges_on_quadratic():
    """EF-compressed gradients still optimize f(w) = ||w - w*||^2."""
    w_star = jnp.asarray(np.random.default_rng(3).normal(size=(32,)),
                         jnp.float32)
    w = jnp.zeros((32,), jnp.float32)
    err = jnp.zeros_like(w)
    for _ in range(200):
        g = 2 * (w - w_star)
        g_c, err = _psum_1dev(g, err)
        w = w - 0.05 * g_c
    assert float(jnp.linalg.norm(w - w_star)) < 1e-2
