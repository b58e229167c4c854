"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this script
  1. builds the production mesh (16×16 single-pod / 2×16×16 multi-pod),
  2. constructs ShapeDtypeStruct inputs (no allocation) and the sharding
     rules from repro.dist.sharding,
  3. ``jax.jit(step, in_shardings=…).lower(...).compile()`` — a failure here
     (sharding mismatch, OOM at compile, unsupported collective) is a bug,
  4. records ``compiled.memory_analysis()`` (proves it fits),
     ``cost_analysis()`` (FLOPs/bytes for §Roofline) and the collective
     traffic parsed from the optimized HLO, into a JSONL file consumed by
     benchmarks/bench_roofline.py and EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k --mesh pod1
  python -m repro.launch.dryrun --all --out results/dryrun.jsonl

It is a host-only lowering: run as a program, it pins JAX to the CPU and
forces 512 host devices before JAX is imported, so it never holds a chip.
"""

import os

if __name__ == "__main__":
    # MUST precede the jax import: jax fixes its platform and host device
    # count on first use
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=512")

import argparse
import json
import time
import traceback
from typing import Any

import jax
import numpy as np

from repro.configs import ARCH_NAMES, SHAPES, get_config, shapes_for
from repro.dist import sharding as shd
from repro.dist.hints import sharding_rules
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs
from repro.train.optimizer import init_opt_state
from repro.train.train_step import (make_prefill_step, make_serve_step,
                                    microbatches_for, opt_config_for,
                                    sharded_train_step)


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             collect_hlo: bool = True) -> dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec: dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_kind, "kind": shape.kind, "ok": False}
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "pod2"))
        specs = input_specs(cfg, shape)
        with mesh:
            p_specs = specs["params"]
            if shape.kind == "train":
                mb, acc_dt = microbatches_for(cfg, shape)
                rec["microbatches"] = mb
                oc = opt_config_for(cfg)
                jitted, _ = sharded_train_step(cfg, oc, mesh, specs["batch"],
                                               microbatches=mb,
                                               accum_dtype=acc_dt)
                o_specs = jax.eval_shape(lambda: init_opt_state(oc, p_specs))
                args = (p_specs, o_specs, specs["batch"])
            elif shape.kind == "prefill":
                in_sh = (shd.named(mesh, shd.param_specs(cfg, p_specs, mesh)),
                         shd.named(mesh, shd.batch_specs(
                             cfg, specs["batch"], mesh)))
                jitted = jax.jit(make_prefill_step(cfg, shape.seq_len),
                                 in_shardings=in_sh)
                args = (p_specs, specs["batch"])
            else:  # decode
                in_sh = (shd.named(mesh, shd.param_specs(cfg, p_specs, mesh)),
                         shd.named(mesh, shd.decode_state_specs(
                             cfg, specs["state"], mesh)),
                         shd.named(mesh, shd.batch_specs(
                             cfg, {"t": specs["tokens"]}, mesh))["t"])
                jitted = jax.jit(make_serve_step(cfg), in_shardings=in_sh)
                args = (p_specs, specs["state"], specs["tokens"])

            with sharding_rules(mesh):
                lowered = jitted.lower(*args)
            compiled = lowered.compile()

            ca = compiled.cost_analysis() or {}
            if isinstance(ca, (list, tuple)):   # jax<=0.4 returns [dict]
                ca = ca[0] if ca else {}
            ma = compiled.memory_analysis()
            rec["flops_per_device"] = float(ca.get("flops", 0.0))
            rec["bytes_per_device"] = float(ca.get("bytes accessed", 0.0))
            if ma is not None:
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes",
                          "alias_size_in_bytes"):
                    v = getattr(ma, k, None)
                    if v is not None:
                        rec[k] = int(v)
            if collect_hlo:
                hlo = hlo_analysis.analyze(compiled.as_text())
                rec["collectives"] = hlo["collective_bytes"]
                rec["collective_total"] = hlo["collective_total"]
                rec["collective_count"] = hlo["collective_count"]
                rec["dot_flops_per_device"] = hlo["dot_flops"]
                rec["result_bytes_per_device"] = hlo["result_bytes"]
                rec["n_while"] = hlo["n_while"]
            rec["n_devices"] = int(np.prod(list(mesh.shape.values())))
            rec["ok"] = True
    except Exception as e:  # noqa: BLE001 — recorded, cell marked failed
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["compile_seconds"] = round(time.time() - t0, 1)
    return rec


def cells(arch_filter=None, shape_filter=None, mesh_filter=None):
    for arch in ARCH_NAMES:
        if arch_filter and arch != arch_filter:
            continue
        cfg = get_config(arch)
        for shape in shapes_for(cfg):
            if shape_filter and shape.name != shape_filter:
                continue
            for mesh_kind in ("pod1", "pod2"):
                if mesh_filter and mesh_kind != mesh_filter:
                    continue
                yield arch, shape.name, mesh_kind


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("pod1", "pod2"))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--skip-done", action="store_true",
                    help="skip cells already present & ok in --out")
    ap.add_argument("--no-hlo", action="store_true",
                    help="skip collective parsing (faster)")
    args = ap.parse_args()

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    done: set[tuple] = set()
    if args.skip_done and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                r = json.loads(line)
                if r.get("ok"):
                    done.add((r["arch"], r["shape"], r["mesh"]))

    todo = list(cells(args.arch, args.shape, args.mesh))
    print(f"dry-run: {len(todo)} cells -> {args.out}", flush=True)
    n_ok = n_fail = 0
    with open(args.out, "a") as f:
        for arch, shape, mesh_kind in todo:
            if (arch, shape, mesh_kind) in done:
                continue
            rec = run_cell(arch, shape, mesh_kind,
                           collect_hlo=not args.no_hlo)
            f.write(json.dumps(rec) + "\n")
            f.flush()
            status = "OK " if rec["ok"] else "FAIL"
            n_ok += rec["ok"]
            n_fail += not rec["ok"]
            print(f"[{status}] {arch:22s} {shape:12s} {mesh_kind} "
                  f"({rec['compile_seconds']}s) "
                  f"{rec.get('error', '')}", flush=True)
    print(f"done: {n_ok} ok, {n_fail} failed", flush=True)


if __name__ == "__main__":
    main()
