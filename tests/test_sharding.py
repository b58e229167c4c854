"""Sharding rules: divisibility-aware specs on the production mesh shapes.

Uses AbstractMesh — axis sizes without devices — so the 16×16 and 2×16×16
rules are testable on a 1-CPU container.
"""

import jax
import pytest
from jax.sharding import AbstractMesh

from repro.configs import get_config
from repro.dist import sharding as shd
from repro.launch.specs import (batch_specs_for, decode_specs_for,
                                params_specs_for)
from repro.configs.base import SHAPES


def mesh1():
    return AbstractMesh((16, 16), ("data", "model"))


def mesh2():
    return AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def flat_with_paths(tree):
    return jax.tree_util.tree_flatten_with_path(tree)[0]


@pytest.mark.parametrize("mesh_fn", [mesh1, mesh2])
@pytest.mark.parametrize("arch", ["granite-3-2b", "deepseek-v3-671b",
                                  "zamba2-7b", "rwkv6-1.6b",
                                  "llama-3.2-vision-90b"])
def test_param_specs_divide(arch, mesh_fn):
    """Every assigned axis must divide its dim (else XLA errors at lower)."""
    mesh = mesh_fn()
    cfg = get_config(arch)
    shapes = params_specs_for(cfg)
    specs = shd.param_specs(cfg, shapes, mesh)
    for (path, leaf), (_, spec) in zip(flat_with_paths(shapes),
                                       flat_with_paths(specs)):
        for dim, ax in zip(leaf.shape, tuple(spec)):
            if ax is None:
                continue
            assert dim % shd.axis_size(mesh, ax) == 0, (path, leaf.shape, spec)


def test_embed_sharded_vocab_model():
    cfg = get_config("granite-3-2b")
    shapes = params_specs_for(cfg)
    specs = shd.param_specs(cfg, shapes, mesh1())
    assert tuple(specs["embed"]["tok"]) == ("model", "data")


def test_expert_weights_get_ep():
    cfg = get_config("deepseek-v3-671b")
    shapes = params_specs_for(cfg)
    specs = shd.param_specs(cfg, shapes, mesh1())
    # stacked moe blocks: (n_layers, E, d, ff) -> FSDP on ff (d is the first
    # einsum's contraction dim; see dist.sharding._EXPERT_RULES)
    assert tuple(specs["moe_blocks"]["moe"]["w1"]) == \
        (None, "model", None, "data")
    # shared expert is a normal mlp
    assert tuple(specs["moe_blocks"]["moe"]["shared"]["w1"]) == \
        (None, "data", "model")


def test_batch_specs_shard_dp_when_divisible():
    cfg = get_config("granite-3-2b")
    b = batch_specs_for(cfg, SHAPES["train_4k"])
    spec = shd.batch_specs(cfg, b, mesh2())
    assert tuple(spec["tokens"])[0] == ("pod", "data")
    # long_500k batch=1 cannot shard
    b1 = batch_specs_for(cfg, SHAPES["long_500k"])
    spec1 = shd.batch_specs(cfg, b1, mesh2())
    assert tuple(spec1["tokens"])[0] is None


class TestDecodeStateSpecs:
    def test_gqa_kv8_falls_back_to_seq_sharding(self):
        cfg = get_config("granite-3-2b")     # kv=8 < model=16
        state, _ = decode_specs_for(cfg, SHAPES["decode_32k"])
        specs = shd.decode_state_specs(cfg, state, mesh1())
        k = tuple(specs["k"])                # (L, B, S, kv*hd)
        assert k[1] == "data" and k[2] == "model" and k[3] is None

    def test_gqa_kv16_shards_heads(self):
        cfg = get_config("gemma3-27b")       # kv=16 == model
        state, _ = decode_specs_for(cfg, SHAPES["decode_32k"])
        specs = shd.decode_state_specs(cfg, state, mesh1())
        k = tuple(specs["k"])                # 16 heads of 128 lanes
        assert k[3] == "model" and k[1] == "data" and k[2] is None

    def test_long_500k_batch1_seq_takes_dp(self):
        cfg = get_config("gemma3-27b")
        state, _ = decode_specs_for(cfg, SHAPES["long_500k"])
        specs = shd.decode_state_specs(cfg, state, mesh1())
        k = tuple(specs["k"])                # B=1: seq gets data axes
        assert k[1] is None
        assert k[2] == "data" or k[2] == ("data",)

    def test_mla_latent_cache(self):
        cfg = get_config("deepseek-v3-671b")
        state, _ = decode_specs_for(cfg, SHAPES["decode_32k"])
        specs = shd.decode_state_specs(cfg, state, mesh1())
        c_kv = tuple(specs["moe_cache"][0])  # (L, B, S, c)
        assert c_kv[1] == "data" and c_kv[2] == "model"

    def test_rwkv_state_heads_sharded(self):
        cfg = get_config("rwkv6-1.6b")
        state, _ = decode_specs_for(cfg, SHAPES["decode_32k"])
        specs = shd.decode_state_specs(cfg, state, mesh1())
        assert tuple(specs["wkv"])[2] == "model"   # (L,B,H,K,K)


def test_check_never_assigns_indivisible():
    mesh = mesh1()
    spec = shd._check(mesh, (10, 48), ("data", "model"))
    assert tuple(spec) == (None, "model")   # 10 % 16 != 0 -> dropped
