"""Quickstart: the paper's cross-layer stack in 60 lines.

Build a hinted workflow (compiler layer), run it through the location-aware
store + proactive scheduler (storage + runtime layers), and compare against
the FCFS baseline — the paper's Figure-2 scenario, executable on a laptop.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import (FCFSScheduler, HPC_CLUSTER, LocalityScheduler,
                        ProactiveScheduler, TaskGraph, WorkflowExecutor,
                        compile_workflow, simulate, size_hint, task)
from repro.core.workloads import fig2_workflow
from repro.launch.compile_cache import use_compile_cache

use_compile_cache()

# --- 1. the compiler layer: a hinted DAG (the paper's @ annotations) --------
g = TaskGraph()
g.add_data("raw", size_bytes=size_hint(64 * 1024 * 1024))        # @size
g.add_task("split", inputs=("raw",), outputs=("a", "b"),
           hints=task(compute="linear", io_ratio=0.5))           # @ratios
g.add_task("fft_a", inputs=("a",), outputs=("fa",),
           hints=task(compute="nlogn", io_ratio=1.0))            # @complexity
g.add_task("fft_b", inputs=("b",), outputs=("fb",),
           hints=task(compute="nlogn", io_ratio=1.0))
g.add_task("merge", inputs=("fa", "fb"), outputs=("out",),
           hints=task(compute="linear", io_ratio=0.5))

wf = compile_workflow(g)                     # sizes/costs/ranks propagate
print("critical path:", " -> ".join(wf.critical_path))
print("dataset sizes:", {k: f"{v/2**20:.0f}MiB" for k, v in wf.sizes.items()})

# --- 2. REAL execution with numpy bodies on the executor --------------------
bodies = {
    "split": lambda raw: {"a": raw[: len(raw) // 2], "b": raw[len(raw) // 2:]},
    "fft_a": lambda a: {"fa": np.fft.rfft(a).real.astype(np.float32)},
    "fft_b": lambda b: {"fb": np.fft.rfft(b).real.astype(np.float32)},
    "merge": lambda fa, fb: {"out": float(np.abs(fa).sum() + np.abs(fb).sum())},
}
for tid, fn in bodies.items():
    wf.graph.tasks[tid].fn = fn

ex = WorkflowExecutor(wf, ProactiveScheduler(wf), n_nodes=2,
                      inject_inputs={"raw": np.random.default_rng(0)
                                     .standard_normal(1 << 16)
                                     .astype(np.float32)})
res = ex.run()
print(f"\nexecuted: out={res.outputs['out']:.1f}  wall={res.wall_seconds:.3f}s"
      f"  locality hit rate={res.locality_hit_rate:.0%}")

# --- 3. the paper's comparison, at cluster scale in the simulator ----------
wf_big = compile_workflow(fig2_workflow(flops_per_byte=20_000), HPC_CLUSTER)
print("\n16-node simulation (paper's comparison):")
for name, factory in [("fcfs      ", FCFSScheduler),
                      ("locality  ", LocalityScheduler),
                      ("proactive ", ProactiveScheduler)]:
    r = simulate(wf_big, factory, n_nodes=16, hw=HPC_CLUSTER)
    print(f"  {name} makespan={r.makespan:7.1f}s  "
          f"moved={r.bytes_moved/2**30:5.2f}GiB  "
          f"hit={r.locality_hit_rate:5.1%}  io_wait={r.io_wait_total:6.1f}s")
