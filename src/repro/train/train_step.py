"""The jitted train/serve steps, with sharding attached.

``make_train_step(cfg, opt_cfg)`` returns ``step(params, opt_state, batch)``;
:func:`sharded_train_step` jits it onto a mesh with the shardings of
:mod:`repro.dist.sharding`. The same function is what the dry-run lowers for
every (arch × train shape) cell, what the training loop runs on a mesh and
what ``chip_smoke.py --chips 4`` runs on four chips, so there is exactly one
train-step definition in the framework.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.dist import sharding as shd
from repro.models import model as M
from repro.train.optimizer import OptConfig, adamw_update

Pytree = Any


def opt_config_for(cfg: ModelConfig) -> OptConfig:
    """Giant models keep their AdamW moments in bf16."""
    big = M.param_count(cfg) > 80e9
    return OptConfig(moment_dtype="bfloat16" if big else "float32")


def microbatches_for(cfg: ModelConfig, shape: InputShape) -> tuple[int, Any]:
    """Gradient-accumulation depth per train cell (memory-term control):
    activations scale with tokens-per-pass. Giant models also accumulate in
    bf16 (an f32 accumulator alone would be 2.7 TB for deepseek-v3)."""
    n = M.param_count(cfg)
    if shape.kind != "train":
        return 1, None
    if n > 80e9:
        return 8, jnp.bfloat16
    if n > 20e9 or cfg.family == "hybrid":
        return 8, None
    if n > 8e9:
        return 4, None
    return 2, None


def make_train_step(cfg: ModelConfig, opt_cfg: OptConfig, *,
                    microbatches: int = 1, accum_dtype=None,
                    grad_specs: Pytree | None = None):
    """Jitted train step; ``microbatches > 1`` scans the global batch in
    micro-slices, accumulating gradients (gradient accumulation) — the
    memory-term lever for the ≥100B dry-run cells (activations scale with
    tokens-per-pass, not tokens-per-step). ``accum_dtype`` defaults to f32;
    the giant configs pass bf16 (a f32 grad accumulator alone would be 2.7 TB
    for deepseek-v3).

    ``grad_specs`` (a sharding tree matching params) constrains each
    microbatch's gradients to the accumulator's sharding BEFORE the add —
    without it XLA all-reduces the full gradient then slices (measured 948 GiB
    × L × mb of f32 all-reduce on arctic train_4k); with it the batch-axis
    reduction lowers to a reduce-scatter at 1/tp the bytes."""

    def grads_of(params: Pytree, batch: Pytree):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: M.loss_fn(cfg, p, batch), has_aux=True)(params)
        if grad_specs is not None:
            grads = jax.tree.map(
                lambda g, sp: jax.lax.with_sharding_constraint(g, sp),
                grads, grad_specs)
        return (loss, metrics), grads

    def train_step(params: Pytree, opt_state: Pytree, batch: Pytree):
        if microbatches == 1:
            (loss, metrics), grads = grads_of(params, batch)
            loss = metrics["loss"]
        else:
            mb = microbatches
            resh = jax.tree.map(
                lambda x: x.reshape(mb, x.shape[0] // mb, *x.shape[1:]),
                batch)
            acc_dt = accum_dtype or jax.numpy.float32

            def body(acc, micro):
                (loss_i, metrics_i), g = grads_of(params, micro)
                acc_g = jax.tree.map(
                    lambda a, b: a + (b / mb).astype(a.dtype), acc[0], g)
                return (acc_g, acc[1] + loss_i / mb), metrics_i

            zeros = jax.tree.map(
                lambda p: jax.numpy.zeros(p.shape, acc_dt), params)
            (grads, loss), metrics_all = jax.lax.scan(
                body, (zeros, jax.numpy.zeros((), jax.numpy.float32)), resh)
            grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)
            metrics = jax.tree.map(lambda m: m.mean(), metrics_all)
        params, opt_state, opt_metrics = adamw_update(
            opt_cfg, grads, opt_state, params)
        metrics = dict(metrics, loss=loss, **opt_metrics)
        return params, opt_state, metrics

    return train_step


def state_shardings(cfg: ModelConfig, mesh) -> tuple[Pytree, Pytree]:
    """NamedShardings on ``mesh`` for the training state (params,
    opt_state): the moments take their parameter's sharding, the step count
    is replicated. The train step's shardings and a restore's targets
    (:func:`repro.train.elastic.shard_targets`) both come from here."""
    p_shapes = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    p_specs = shd.param_specs(cfg, p_shapes, mesh)
    return (shd.named(mesh, p_specs),
            shd.named(mesh, {"m": p_specs, "v": p_specs, "step": P()}))


def sharded_train_step(cfg: ModelConfig, opt_cfg: OptConfig, mesh,
                       batch: Pytree, *, microbatches: int = 1,
                       accum_dtype=None):
    """The train step jitted onto ``mesh``: params and moments stay in their
    rule-derived shardings across steps (donated), the batch (arrays or
    ShapeDtypeStructs) shards over the DP axes. Returns
    ``(jitted_step, (param_sh, opt_sh, batch_sh))``. Trace (call or
    ``.lower``) it inside ``dist.hints.sharding_rules(mesh)`` so the model's
    activation hints bind to the mesh."""
    p_sh, o_sh = state_shardings(cfg, mesh)
    b_sh = shd.named(mesh, shd.batch_specs(cfg, batch, mesh))
    step = make_train_step(cfg, opt_cfg, microbatches=microbatches,
                           accum_dtype=accum_dtype, grad_specs=p_sh)
    jitted = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, None), donate_argnums=(0, 1))
    return jitted, (p_sh, o_sh, b_sh)


def make_serve_step(cfg: ModelConfig):
    def serve_step(params: Pytree, state: Pytree, tokens):
        return M.decode_step(cfg, params, state, tokens)

    return serve_step


def make_prefill_step(cfg: ModelConfig, max_seq: int):
    def prefill_step(params: Pytree, batch: Pytree):
        return M.prefill(cfg, params, batch, max_seq)

    return prefill_step
