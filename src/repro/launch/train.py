"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

By default it trains the smoke-scale variant of the chosen arch end to end
(real data pipeline, prefetch, checkpointing, optional simulated failure).
Either way it trains on a (data, model) mesh over every local device (1×1 on
one chip, 2×2 on a four-chip host) with the sharded step of
:func:`repro.train.train_step.sharded_train_step`. ``--full`` trains the full
published config, with the gradient accumulation the dry-run uses for the
arch; it stops before allocating when the state does not fit the mesh
(full-width granite-3-2b needs four v5e chips).
"""

from __future__ import annotations

import argparse

from repro.configs import ARCH_NAMES, get_config, get_smoke
from repro.configs.base import InputShape
from repro.launch.compile_cache import use_compile_cache
from repro.train.loop import TrainConfig, train
from repro.train.optimizer import OptConfig
from repro.train.train_step import microbatches_for


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="granite-3-2b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="simulate a node failure at this step")
    ap.add_argument("--full", action="store_true",
                    help="full published config (in place of the smoke "
                         "variant)")
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch) if args.full else get_smoke(args.arch)
    mb, acc = (microbatches_for(
        cfg, InputShape("train", args.seq, args.batch, "train"))
        if args.full else (1, None))
    tc = TrainConfig(steps=args.steps, batch=args.batch, seq=args.seq,
                     ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                     simulate_failure_at=args.fail_at, microbatches=mb,
                     accum_dtype=acc)
    oc = OptConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                   total_steps=args.steps)

    def log(step, metrics):
        if step % 10 == 0 or step == 1:
            extra = " ".join(f"{k}={float(v):.4f}" for k, v in metrics.items()
                             if k != "loss")
            print(f"step {step:5d} loss={float(metrics['loss']):.4f} {extra}",
                  flush=True)

    r = train(cfg, tc, oc, on_step=log)
    print(f"\ndone: {r.steps_done} steps, {r.restarts} restarts, "
          f"{r.wall_seconds:.1f}s, loss {r.losses[0]:.3f} -> {r.losses[-1]:.3f}")


if __name__ == "__main__":
    main()
