"""Elastic scaling: restore a checkpoint onto a DIFFERENT mesh.

The scenario at 1000+ nodes: a pod loses hosts mid-run; the job restarts on
the surviving N-k hosts with a reshaped mesh. Nothing about the checkpoint
format depends on the writing mesh (leaves are saved whole per key), so
elasticity is purely a restore-time policy:

    new_mesh  = make_mesh((new_dp, new_tp), ("data", "model"))
    params    = elastic_restore(cfg, opt_cfg, ckpt_dir, new_mesh)

Each leaf is device_put against the sharding rules evaluated on the NEW mesh
(divisibility-aware: rules degrade to replication for axes that no longer
divide). The data pipeline is deterministic-by-step, so training resumes at
the checkpoint step with the exact next batch.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.train import checkpoint as ckpt
from repro.train.optimizer import OptConfig, init_opt_state
from repro.train.train_step import state_shardings

Pytree = Any


def shard_targets(cfg: ModelConfig, opt_cfg: OptConfig, mesh: Mesh
                  ) -> dict[str, Pytree]:
    """ShapeDtypeStructs with NEW-mesh shardings for {params, opt_state}."""
    p_shapes = jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    o_shapes = jax.eval_shape(lambda: init_opt_state(opt_cfg, p_shapes))
    p_sh, o_sh = state_shardings(cfg, mesh)

    def attach(shapes, shardings):
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, shardings)

    return {"p": attach(p_shapes, p_sh), "o": attach(o_shapes, o_sh)}


def elastic_restore(cfg: ModelConfig, opt_cfg: OptConfig, ckpt_dir: str,
                    mesh: Mesh, step: int | None = None
                    ) -> tuple[Pytree, Pytree, int]:
    """(params, opt_state, step) resharded onto ``mesh``."""
    step = step if step is not None else (ckpt.latest_step(ckpt_dir) or 0)
    tgt = shard_targets(cfg, opt_cfg, mesh)
    with mesh:
        state = ckpt.restore(ckpt_dir, step, target=tgt)
    return state["p"], state["o"], step
