"""Elastic restart: checkpoint on one mesh, resume on another.

Simulates the 1000-node scenario at laptop scale: a run checkpoints, "loses
half its pod", and resumes from the same checkpoint on a reshaped mesh —
parameters are resharded by the divisibility-aware rules, and the
deterministic data pipeline replays the exact next batch.

    PYTHONPATH=src python examples/elastic_restart.py
"""

import tempfile

import jax

from repro.configs import get_smoke
from repro.launch.compile_cache import use_compile_cache
from repro.dist.mesh import make_local_mesh
from repro.models import init_params
from repro.train import checkpoint as ckpt
from repro.train.elastic import elastic_restore, shard_targets
from repro.train.optimizer import OptConfig, init_opt_state


def main() -> None:
    use_compile_cache()
    cfg = get_smoke("granite-3-2b")
    oc = OptConfig()
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(oc, params)

    with tempfile.TemporaryDirectory() as d:
        print("training 'pod A' saves step 40 ...")
        ckpt.save({"p": params, "o": opt}, d, 40)

        # --- pod shrinks: new mesh shape -------------------------------------
        new_mesh = make_local_mesh(1, 1)   # stand-in for (8, 16) after losing hosts
        print(f"restarting on mesh {dict(new_mesh.shape)} ...")
        p2, o2, step = elastic_restore(cfg, oc, d, new_mesh)
        print(f"restored step {step}; resharded "
              f"{len(jax.tree.leaves(p2))} param leaves onto the new mesh")

        # verify bit-identical content
        ok = all(
            (jax.numpy.abs(a.astype(jax.numpy.float32)
                           - b.astype(jax.numpy.float32)).max() == 0)
            for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
        print("content identical after reshard:", bool(ok))

        # the targets the restore used (what a production launcher passes)
        tgt = shard_targets(cfg, oc, new_mesh)
        some = jax.tree.leaves(tgt["p"])[0]
        print("example target sharding:", some.sharding)


if __name__ == "__main__":
    main()
