"""Compile the chip paths for a described TPU v5e, with no chip attached.

The TPU compiler ships with the installed libtpu and compiles for a topology
that is only described, so these tests catch what interpret mode cannot: a
kernel block layout the chip's tiling refuses, a program that does not fit
the chip's 16 GB of HBM. Nothing runs; only shapes are compiled.

The topology is described inside a module-scoped fixture (never at import,
in ``conftest.py`` or in a ``parametrize``/``skipif`` argument): only one
process at a time may load libtpu, and every test worker imports this file.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mla_decode import mla_decode_attention
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
    """The kernel on granite-3-2b's pool, (L, B, S, Hkv*hd), with the layer
    and window traced as the model's scan traces them."""
    L, B, S = 40, 8, 4096
    args = (_shape(one_chip, (B, HQ, HD), dtype),
            _shape(one_chip, (L, B, S, HKV * HD), dtype),
            _shape(one_chip, (L, B, S, HKV * HD), dtype),
            _shape(one_chip, (B, HKV * HD), dtype),
            _shape(one_chip, (B, HKV * HD), dtype),
            _shape(one_chip, (B,), jnp.int32),
            _shape(one_chip, (), jnp.int32),
            _shape(one_chip, (), jnp.int32))
    compiled = jax.jit(
        lambda *a: decode_attention(*a, interpret=False)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_mla_decode_attention_compiles_for_v5e(one_chip, no_persistent_cache,
                                               dtype):
    """The MLA kernel on the deepseek-v3 cell's latent pools, 9 layers of
    (32 slots, 8192 positions, 512 + 64), 32 query heads, with the layer
    traced as the model's scan traces it."""
    L, B, S, H, C, R = 9, 32, 8192, 32, 512, 64
    args = (_shape(one_chip, (B, H, C), dtype),
            _shape(one_chip, (B, H, R), dtype),
            _shape(one_chip, (L, B, S, C), dtype),
            _shape(one_chip, (L, B, S, R), dtype),
            _shape(one_chip, (B, C), dtype),
            _shape(one_chip, (B, R), dtype),
            _shape(one_chip, (B,), jnp.int32),
            _shape(one_chip, (), jnp.int32))
    compiled = jax.jit(
        lambda *a: mla_decode_attention(*a, softmax_scale=0.135,
                                        interpret=False)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_granite_decode_step_fits_one_v5e(one_chip, no_persistent_cache,
                                          monkeypatch):
    """Full-width, full-depth granite-3-2b decode over an 8 x 4096 KV pool —
    the serving path ``chip_smoke.py`` drives, as the engine jits it (the
    Pallas kernel, the state donated) — fits one chip's HBM. The pool is
    read and written where it lies: its device bytes are its logical bytes,
    the output pool aliases the input, and no op copies the whole pool."""
    monkeypatch.setattr(M, "_pooled_attention_impl", lambda: "pallas")
    cfg = get_config("granite-3-2b")

    def on_chip(tree):
        return jax.tree.map(lambda x: _shape(one_chip, x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
    state = on_chip(jax.eval_shape(lambda: M.init_decode_state(cfg, 8, 4096)))
    tokens = _shape(one_chip, (8, 1), jnp.int32)
    compiled = jax.jit(lambda p, s, t: M.decode_step(cfg, p, s, t),
                       donate_argnums=(1,)).lower(
        params, state, tokens).compile()
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    logical = sum(x.size * x.dtype.itemsize
                  for x in jax.tree.leaves((params, state)))
    pool = state["k"].size * state["k"].dtype.itemsize       # 1.34 GB
    assert ma.argument_size_in_bytes > 7e9        # 5.3 GB params + 2.7 GB KV
    assert abs(ma.argument_size_in_bytes - logical) < 1 << 20  # no padding
    assert ma.alias_size_in_bytes >= 2 * pool
    assert ma.temp_size_in_bytes < pool // 100
    assert used < V5E_HBM_BYTES
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt
    assert not re.search(r"= bf16\[40,8,4096,512\]\S* copy\(", txt)


def test_deepseek_decode_step_fits_one_v5e(one_chip, no_persistent_cache,
                                           monkeypatch):
    """The benchmark's deepseek-v3-671b share (1 dense + 8 MoE layers at
    published widths, 32 heads and 8 of 256 experts held, 32 x 8192 latent
    pool) decodes on one chip, as the engine jits it (the MLA kernel, the
    state donated): the latent pools are read where they lie and written in
    place, aliased to the donated state, no op copies a whole pool, and no
    f32 score tensor spans the pool's positions."""
    monkeypatch.setattr(M, "_pooled_attention_impl", lambda: "pallas")
    import json
    import sys
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root))
    from bench import harness
    path = root / "bench" / "configs" / "deepseek-v3-671b.json"
    bcfg = json.loads(path.read_text())
    cfg = harness.load_module(path.with_suffix(".py")).model_config(bcfg)
    B, S = bcfg["serving"]["max_batch"], bcfg["serving"]["max_seq"]

    def on_chip(tree):
        return jax.tree.map(lambda x: _shape(one_chip, x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
    state = on_chip(jax.eval_shape(lambda: M.init_decode_state(cfg, B, S)))
    compiled = jax.jit(lambda p, s, t: M.decode_step(cfg, p, s, t),
                       donate_argnums=(1,)).lower(
        params, state, _shape(one_chip, (B, 1), jnp.int32)).compile()
    ma = compiled.memory_analysis()
    pools = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(
        (state["dense_cache"], state["moe_cache"])))          # 2.72 GB
    assert ma.alias_size_in_bytes >= pools
    assert ma.temp_size_in_bytes < pools // 50
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < V5E_HBM_BYTES
    txt = compiled.as_text()
    assert not re.search(r"= bf16\[\d+,32,8192,(512|64)\]\S* copy\(", txt)
    assert "tpu_custom_call" in txt
    assert not re.search(r"f32\[32,32,8192\]", txt)


def test_granite_decode_step_shards_over_v5e_2x2(topo, no_persistent_cache,
                                                monkeypatch):
    """The pooled decode step with the Pallas kernel on a 2x2 (data, model)
    mesh, at full width and 2 layers: the kernel runs on each chip's shard
    of the pool (slots on data, kv heads on model), so no collective moves
    the pool (a Mosaic kernel left to the partitioner does not compile),
    and the donated pool stays in place. ``max_seq`` 3072 is a length that
    no weight has, so a collective with that dimension would be the pool's."""
    import collections

    import numpy as np
    from jax.sharding import AxisType, Mesh, PartitionSpec as P

    from repro.dist import sharding as shd
    from repro.dist.hints import sharding_rules

    monkeypatch.setattr(M, "_pooled_attention_impl", lambda: "pallas")
    cfg = dataclasses.replace(get_config("granite-3-2b"), n_layers=2)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    B, S = 8, 3072
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda: M.init_decode_state(cfg, B, S))
    tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    st_specs = shd.decode_state_specs(cfg, state, mesh)
    assert st_specs["k"] == P(None, "data", None, "model")
    st_sh = shd.named(mesh, st_specs)
    step = jax.jit(lambda p, s, t: M.decode_step(cfg, p, s, t),
                   in_shardings=(shd.named(mesh, shd.param_specs(cfg, params,
                                                                 mesh)),
                                 st_sh, shd.named(mesh, shd.batch_specs(
                                     cfg, {"t": tokens}, mesh))["t"]),
                   out_shardings=(None, st_sh), donate_argnums=(1,))
    with sharding_rules(mesh):
        compiled = step.lower(params, state, tokens).compile()
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt
    moved = collections.Counter(
        m.group(2) for m in re.finditer(
            r"= (.*?) (all-gather|all-to-all|collective-permute-start|"
            r"all-reduce|reduce-scatter)\(", txt)
        if re.search(rf"\[[0-9,]*\b{S}\b", m.group(1)))
    assert not moved, moved
    shard = state["k"].size * state["k"].dtype.itemsize // 4
    assert compiled.memory_analysis().alias_size_in_bytes >= 2 * shard


def test_granite_train_step_shards_over_v5e_2x2(topo, no_persistent_cache):
    """The four-chip path of ``chip_smoke.py --chips 4`` at full width and
    2 layers: the sharded step compiles for a 2x2 (data, model) mesh, splits
    the parameters and moments four ways, and needs collectives."""
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
