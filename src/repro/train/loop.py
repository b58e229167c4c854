"""The training loop: data prefetch + jitted step + async checkpoint +
elastic restart. This is the end-to-end driver examples/train_lm.py uses.

Fault-tolerance contract:
  * checkpoint every ``ckpt_every`` steps, asynchronously (one in flight);
  * ``simulate_failure_at`` kills the in-memory state at that step — the loop
    then restores from the latest checkpoint (possibly onto a different mesh:
    elastic restart) and continues; steps since the last checkpoint re-run;
  * the data pipeline is deterministic-by-step, so restarts replay the exact
    batches (no data loss / duplication beyond the rolled-back steps).

Training always runs on a mesh (one device gives a 1×1 mesh): params and
moments are created in the rule-derived shardings of
:mod:`repro.dist.sharding`, each batch shards over the DP axes, and a restart
restores onto the mesh (:func:`repro.train.elastic.elastic_restore`).
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.data.pipeline import PrefetchingLoader, SyntheticCorpus
from repro.dist.hints import sharding_rules
from repro.dist.mesh import make_host_mesh
from repro.models import model as M
from repro.train import checkpoint as ckpt
from repro.train.elastic import elastic_restore
from repro.train.optimizer import OptConfig, init_opt_state
from repro.train.train_step import sharded_train_step

Pytree = Any


@dataclasses.dataclass
class TrainConfig:
    steps: int = 50
    batch: int = 8
    seq: int = 64
    ckpt_every: int = 20
    ckpt_dir: str | None = None
    prefetch_depth: int = 2
    log_every: int = 10
    simulate_failure_at: int | None = None
    seed: int = 0
    microbatches: int = 1
    accum_dtype: Any = None          # gradient accumulator; None = float32


@dataclasses.dataclass
class TrainResult:
    losses: list[float]
    steps_done: int
    restarts: int
    wall_seconds: float
    data_waits: int


def check_state_fits(shapes: Pytree, shardings: Pytree, mesh) -> None:
    """Raise MemoryError before any allocation when one device's shards of
    the training state (params and moments, not yet gradients or
    activations) exceed that device's memory. Backends that report no
    ``bytes_limit`` (the CPU) are not checked."""
    limit = (mesh.devices.flat[0].memory_stats() or {}).get("bytes_limit")
    if not limit:
        return
    per_device = sum(
        math.prod(sh.shard_shape(s.shape)) * s.dtype.itemsize
        for s, sh in zip(jax.tree.leaves(shapes), jax.tree.leaves(shardings)))
    if per_device > limit:
        raise MemoryError(
            f"training state needs {per_device / 2**30:.1f} GiB per device on "
            f"mesh {dict(mesh.shape)}, over the {limit / 2**30:.1f} GiB a "
            "device holds: use more devices")


def extras_fn(cfg: ModelConfig, batch_np: dict, rng: np.random.Generator
              ) -> dict:
    """Attach stub modality inputs (frames/patches) where the family needs."""
    out = dict(batch_np)
    B = batch_np["tokens"].shape[0]
    if cfg.family == "encdec":
        out["frames"] = rng.standard_normal(
            (B, cfg.n_frames, cfg.d_model), np.float32).astype(np.float32)
    if cfg.family == "vlm":
        out["patches"] = rng.standard_normal(
            (B, cfg.n_patches, cfg.d_model), np.float32).astype(np.float32)
    return out


def train(cfg: ModelConfig, tc: TrainConfig,
          opt_cfg: OptConfig | None = None,
          on_step: Callable[[int, dict], None] | None = None,
          mesh=None) -> TrainResult:
    """Train ``cfg`` for ``tc.steps`` steps on ``mesh`` (default: every local
    device, :func:`repro.dist.mesh.make_host_mesh`)."""
    opt_cfg = opt_cfg or OptConfig(warmup_steps=10, total_steps=tc.steps)
    cfg.validate()
    mesh = make_host_mesh() if mesh is None else mesh
    corpus = SyntheticCorpus(cfg.vocab, seed=tc.seed)
    sample = extras_fn(cfg, next(corpus.batches(tc.batch, tc.seq)),
                       np.random.default_rng(0))
    step_fn, (p_sh, o_sh, _) = sharded_train_step(
        cfg, opt_cfg, mesh, sample, microbatches=tc.microbatches,
        accum_dtype=tc.accum_dtype)

    def fresh_state():
        params = M.init_params(cfg, jax.random.PRNGKey(tc.seed))
        return params, init_opt_state(opt_cfg, params)

    check_state_fits(jax.eval_shape(fresh_state), (p_sh, o_sh), mesh)
    init = jax.jit(fresh_state, out_shardings=(p_sh, o_sh))
    params, opt_state = init()

    checkpointer = (ckpt.AsyncCheckpointer(tc.ckpt_dir)
                    if tc.ckpt_dir else None)

    losses: list[float] = []
    restarts = 0
    failed_once = False
    step = 0
    data_waits = 0
    t0 = time.perf_counter()

    def make_loader(start: int) -> PrefetchingLoader:
        it = corpus.batches(tc.batch, tc.seq, start_step=start)
        return PrefetchingLoader(
            (extras_fn(cfg, b, np.random.default_rng((tc.seed, i + start)))
             for i, b in enumerate(it)),
            depth=tc.prefetch_depth)

    loader = make_loader(0)
    try:
        while step < tc.steps:
            if (tc.simulate_failure_at is not None and not failed_once
                    and step == tc.simulate_failure_at):
                # ---- simulated node failure: lose in-memory state ---------
                failed_once = True
                del params, opt_state
                if checkpointer:
                    checkpointer.wait()
                restore_step = ckpt.latest_step(tc.ckpt_dir)
                if restore_step is None:
                    # failed before the first checkpoint: cold restart —
                    # deterministic init + data pipeline replay from step 0
                    params, opt_state = init()
                    restore_step = 0
                else:
                    params, opt_state, _ = elastic_restore(
                        cfg, opt_cfg, tc.ckpt_dir, mesh, restore_step)
                step = restore_step
                restarts += 1
                loader.close()
                loader = make_loader(step)
                continue

            batch = next(loader)
            with sharding_rules(mesh):
                params, opt_state, metrics = step_fn(params, opt_state, batch)
            step += 1
            loss = float(metrics["loss"])
            losses.append(loss)
            if not np.isfinite(loss):
                raise FloatingPointError(f"loss diverged at step {step}")
            if on_step:
                on_step(step, metrics)
            if checkpointer and step % tc.ckpt_every == 0:
                checkpointer.save_async({"p": params, "o": opt_state}, step)
        if checkpointer:
            checkpointer.wait()
    finally:
        data_waits = loader.waits
        loader.close()

    return TrainResult(losses=losses, steps_done=step, restarts=restarts,
                       wall_seconds=time.perf_counter() - t0,
                       data_waits=data_waits)
