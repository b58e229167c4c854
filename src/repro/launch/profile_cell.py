"""Per-cell dry-run profiler — the §Perf loop's microscope.

Compiles ONE (arch × shape × mesh) cell exactly as launch/dryrun.py does and
prints the top collectives and top dot instructions (with while-loop
multiplicities), so a hillclimb iteration can see exactly which op its last
change moved.

  python -m repro.launch.profile_cell --arch arctic-480b --shape train_4k \
      --mesh pod1 [--save results/cell.hlo]

Like the dry-run it is host-only: ``main`` pins JAX to the CPU and forces
512 host devices before anything imports JAX.
"""

import argparse
import os

from repro.launch import hlo_analysis as H


def profile(arch: str, shape_name: str, mesh_kind: str,
            save: str | None = None, top: int = 14,
            seq_parallel: bool = False) -> None:
    import jax
    from repro.configs import SHAPES, get_config
    from repro.dist import sharding as shd
    from repro.dist.hints import sharding_rules
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import input_specs
    from repro.train.optimizer import init_opt_state
    from repro.train.train_step import (make_prefill_step, make_serve_step,
                                        microbatches_for, opt_config_for,
                                        sharded_train_step)

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "pod2"))
    specs = input_specs(cfg, shape)
    with mesh:
        p = specs["params"]
        if shape.kind == "train":
            mb, acc = microbatches_for(cfg, shape)
            oc = opt_config_for(cfg)
            jitted, _ = sharded_train_step(cfg, oc, mesh, specs["batch"],
                                           microbatches=mb, accum_dtype=acc)
            o = jax.eval_shape(lambda: init_opt_state(oc, p))
            args = (p, o, specs["batch"])
        elif shape.kind == "prefill":
            in_sh = (shd.named(mesh, shd.param_specs(cfg, p, mesh)),
                     shd.named(mesh, shd.batch_specs(cfg, specs["batch"],
                                                     mesh)))
            jitted = jax.jit(make_prefill_step(cfg, shape.seq_len),
                             in_shardings=in_sh)
            args = (p, specs["batch"])
        else:
            in_sh = (shd.named(mesh, shd.param_specs(cfg, p, mesh)),
                     shd.named(mesh, shd.decode_state_specs(
                         cfg, specs["state"], mesh)),
                     shd.named(mesh, shd.batch_specs(
                         cfg, {"t": specs["tokens"]}, mesh))["t"])
            jitted = jax.jit(make_serve_step(cfg), in_shardings=in_sh)
            args = (p, specs["state"], specs["tokens"])
        with sharding_rules(mesh, seq_parallel=seq_parallel):
            compiled = jitted.lower(*args).compile()
    txt = compiled.as_text()
    if save:
        with open(save, "w") as f:
            f.write(txt)
    a = H.analyze(txt)
    print(f"== {arch} {shape_name} {mesh_kind} ==")
    print(f"dot_flops/dev: {a['dot_flops']/1e12:.1f} TF   "
          f"collective: {a['collective_total']/1e12:.2f} TB   "
          f"result_bytes: {a['result_bytes']/1e12:.2f} TB")
    ma = compiled.memory_analysis()
    if ma:
        print(f"temp: {ma.temp_size_in_bytes/1e9:.1f} GB   "
              f"args: {ma.argument_size_in_bytes/1e9:.1f} GB")
    print("\ntop collectives (bytes x mult):")
    for row in H.top_collectives(txt, top):
        print("  " + row)
    print("\ntop dots:")
    comps = H.parse_computations(txt)
    entry = H._entry_name(comps, txt)
    mult = H.multiplicities(comps, entry)
    rows = []
    for cname, m in mult.items():
        for ins in comps[cname].instrs:
            if ins.op == "dot":
                rows.append((m * H._dot_flops(comps[cname], ins), m,
                             ins.name, cname))
    rows.sort(reverse=True)
    for fl, m, name, cname in rows[:top]:
        print(f"  {fl/1e12:8.2f}TF x{int(m):5d}  {name:20s} @{cname[:50]}")


def main() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=512")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="pod1", choices=("pod1", "pod2"))
    ap.add_argument("--save")
    ap.add_argument("--top", type=int, default=14)
    ap.add_argument("--sp", action="store_true", help="Megatron seq-parallel")
    args = ap.parse_args()
    profile(args.arch, args.shape, args.mesh, args.save, args.top,
            seq_parallel=args.sp)


if __name__ == "__main__":
    main()
