"""End-to-end driver: train a ~100M-param LM for a few hundred steps.

Uses the full production stack at laptop scale: synthetic corpus shards,
prefetching loader (the paper's pipelining), jitted train step with AdamW,
async checkpointing, and a mid-run simulated node failure with restart from
checkpoint — all the fault-tolerance machinery, observable in one run.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""

import argparse
import tempfile

from repro.configs.base import ModelConfig
from repro.launch.compile_cache import use_compile_cache
from repro.train.loop import TrainConfig, train
from repro.train.optimizer import OptConfig

# ~100M params: a granite-family dense GQA decoder
CFG_100M = ModelConfig(
    name="granite-100m", family="dense",
    n_layers=8, d_model=768, n_heads=12, n_kv_heads=4,
    d_ff=2048, vocab=32768,
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--fail-at", type=int, default=150,
                    help="simulate a node failure at this step (0 = off)")
    args = ap.parse_args()
    use_compile_cache()

    n_params = sum(x.size for x in __import__("jax").tree.leaves(
        __import__("jax").eval_shape(
            lambda: __import__("repro.models", fromlist=["init_params"])
            .init_params(CFG_100M, __import__("jax").random.PRNGKey(0)))))
    print(f"model: {CFG_100M.name}  params={n_params/1e6:.1f}M  "
          f"steps={args.steps}  tokens/step={args.batch * args.seq}")

    with tempfile.TemporaryDirectory() as ckpt_dir:
        tc = TrainConfig(steps=args.steps, batch=args.batch, seq=args.seq,
                         ckpt_dir=ckpt_dir, ckpt_every=50,
                         simulate_failure_at=args.fail_at or None)
        oc = OptConfig(lr=6e-4, warmup_steps=30, total_steps=args.steps)

        def log(step, metrics):
            if step % 20 == 0:
                print(f"  step {step:4d}  loss={float(metrics['loss']):.4f}  "
                      f"lr={float(metrics['lr']):.2e}  "
                      f"gnorm={float(metrics['grad_norm']):.2f}", flush=True)

        r = train(CFG_100M, tc, oc, on_step=log)

    print(f"\nloss {r.losses[0]:.3f} -> {r.losses[-1]:.3f} over "
          f"{r.steps_done} steps ({r.wall_seconds:.0f}s, "
          f"{r.restarts} failure-restart(s), "
          f"{args.batch * args.seq * r.steps_done / r.wall_seconds:,.0f} tok/s)")
    assert r.losses[-1] < r.losses[0], "training must reduce loss"


if __name__ == "__main__":
    main()
