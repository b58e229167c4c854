"""Compile the chip paths for a described TPU v5e, with no chip attached.

The TPU compiler ships with the installed libtpu and compiles for a topology
that is only described, so these tests catch what interpret mode cannot: a
kernel block layout the chip's tiling refuses, a program that does not fit
the chip's 16 GB of HBM. Nothing runs; only shapes are compiled.

The topology is described inside a module-scoped fixture (never at import,
in ``conftest.py`` or in a ``parametrize``/``skipif`` argument): only one
process at a time may load libtpu, and every test worker imports this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.models import model as M

V5E_HBM_BYTES = 16e9

# granite-3-2b attention geometry (configs/granite_3_2b.py)
HQ, HKV, HD = 32, 8, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu / compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but can
    never be read back without a chip: keep it out of the cache."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(one_chip, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_flash_attention_compiles_for_v5e(one_chip, no_persistent_cache,
                                          dtype):
    S = 2048
    args = (_shape(one_chip, (1, S, HQ, HD), dtype),
            _shape(one_chip, (1, S, HKV, HD), dtype),
            _shape(one_chip, (1, S, HKV, HD), dtype))
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_decode_attention_compiles_for_v5e(one_chip, no_persistent_cache,
                                           dtype):
    B, S = 8, 4096
    args = (_shape(one_chip, (B, HQ, HD), dtype),
            _shape(one_chip, (B, S, HKV, HD), dtype),
            _shape(one_chip, (B, S, HKV, HD), dtype),
            _shape(one_chip, (B,), jnp.int32))
    compiled = jax.jit(
        lambda q, k, v, n: decode_attention(q, k, v, n, interpret=False)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_granite_decode_step_fits_one_v5e(one_chip, no_persistent_cache):
    """Full-width, full-depth granite-3-2b decode over an 8 x 4096 KV pool —
    the serving path ``chip_smoke.py`` drives — fits one chip's HBM."""
    cfg = get_config("granite-3-2b")

    def on_chip(tree):
        return jax.tree.map(lambda x: _shape(one_chip, x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
    state = on_chip(jax.eval_shape(lambda: M.init_decode_state(cfg, 8, 4096)))
    tokens = _shape(one_chip, (8, 1), jnp.int32)
    compiled = jax.jit(lambda p, s, t: M.decode_step(cfg, p, s, t)).lower(
        params, state, tokens).compile()
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert ma.argument_size_in_bytes > 7e9        # 5.3 GB params + 2.7 GB KV
    assert used < V5E_HBM_BYTES


def test_granite_train_step_shards_over_v5e_2x2(topo, no_persistent_cache):
    """The four-chip path of ``chip_smoke.py --chips 4`` at full width and
    2 layers: the sharded step compiles for a 2x2 (data, model) mesh, splits
    the parameters and moments four ways, and needs collectives."""
    import dataclasses

    import numpy as np
    from jax.sharding import AxisType, Mesh

    from repro.configs import TRAIN_4K
    from repro.dist.hints import sharding_rules
    from repro.train.optimizer import OptConfig, init_opt_state
    from repro.train.train_step import microbatches_for, sharded_train_step

    cfg = dataclasses.replace(get_config("granite-3-2b"), n_layers=2)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    oc = OptConfig()
    batch = {k: jax.ShapeDtypeStruct((8, 2048), jnp.int32)
             for k in ("tokens", "labels")}
    mb, acc = microbatches_for(cfg, TRAIN_4K)
    step, (p_sh, _, _) = sharded_train_step(cfg, oc, mesh, batch,
                                            microbatches=mb, accum_dtype=acc)
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    opt = jax.eval_shape(lambda: init_opt_state(oc, params))
    with sharding_rules(mesh):
        compiled = step.lower(params, opt, batch).compile()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < V5E_HBM_BYTES
    # the embedding and every matrix are split, so a device holds well under
    # a whole copy of the parameters (2 layers: 0.6 GB in bf16)
    split = [s for s in jax.tree.leaves(p_sh) if not s.is_fully_replicated]
    assert len(split) >= len(jax.tree.leaves(p_sh)) // 2
    txt = compiled.as_text()
    assert "all-reduce" in txt and "all-gather" in txt
