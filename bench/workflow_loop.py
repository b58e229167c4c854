"""Workflow cells: one workflow after another through ``compile_workflow``
-> ``ProactiveScheduler`` -> ``WorkflowExecutor`` on one chip.

Every workflow gets a fresh executor (and with it a fresh ``LocStore`` and
``PrefetchEngine``) over ``n_nodes`` logical nodes that all map to the chip,
with the tiered hierarchy. Its input images are host numpy arrays, made in
set-up from the seed (``input_sets`` distinct sets, used in turn) and
injected, so each workflow stages its inputs into device memory anew. A
workflow ends when its sink output is ready on the device.

* ``makespan_s``: the seconds from the window's start to the end of the last
  workflow started in it, over the workflows completed.

Set-up builds the graph and its jitted bodies, makes the inputs and runs one
whole workflow through the executor, which compiles every body. After the
window every input set is run once more by the configuration's serial
reference, and every workflow's output is compared with its set's: the
widest absolute difference is held to the configuration's limit.
"""

from __future__ import annotations

import threading
import time
from typing import Callable

import jax
import numpy as np

from bench import traffic
from bench.harness import CellRun, Readings, Spans, TraceWindow, peak_bytes


def _scheduler_class(spans: Spans):
    """``ProactiveScheduler`` whose ``select`` and ``preplace`` are spans."""
    from repro.core.scheduler import ProactiveScheduler

    class TimedScheduler(ProactiveScheduler):
        def select(self, ready, cluster):
            with spans.span("schedule"):
                return super().select(ready, cluster)

        def preplace(self, candidates, cluster, running_at=None):
            with spans.span("schedule"):
                return super().preplace(candidates, cluster, running_at)

    return TimedScheduler if spans.on else ProactiveScheduler


class _InputBytes:
    """Task-input bytes, and those of them handed over as the prefetch
    engine's device copy, counted in wrappers around the task bodies."""

    def __init__(self, n_nodes: int) -> None:
        self.n_nodes = n_nodes
        self.executor = None
        self.total = 0.0
        self.prefetched = 0.0
        self._lock = threading.Lock()

    def wrap(self, tg) -> None:
        for t in tg.tasks.values():
            t.fn = self._counted(t.fn)

    def _counted(self, fn):
        def body(**kw):
            pf = self.executor.prefetch
            total = pre = 0.0
            for name, v in kw.items():
                nbytes = float(getattr(v, "nbytes", 0))
                total += nbytes
                if any(v is pf.device_copy(name, n) for n in range(self.n_nodes)):
                    pre += nbytes
            with self._lock:
                self.total += total
                self.prefetched += pre
            return fn(**kw)

        return body


def run(cfg: dict, cfg_mod, mix: dict, *, cell: str, seed: int,
        seconds: float, trace: bool, devices, peaks: dict, clock,
        t_start: float, log: Callable[[str], None],
        hook: Callable | None = None) -> CellRun:
    from repro.core import WorkflowExecutor, compile_workflow
    from repro.core.locstore import tiered_hierarchy

    chip = devices[0]
    n_nodes = int(cfg["n_nodes"])
    bodies = cfg_mod.make_bodies(cfg)
    tg = cfg_mod.build_graph(cfg, bodies)
    ref_graph = cfg_mod.build_graph(cfg, bodies)
    if hook is not None:
        hook(tg)
    spans = Spans(trace)
    counted = _InputBytes(n_nodes)
    if trace:
        counted.wrap(tg)
    wf = compile_workflow(tg)
    sched_cls = _scheduler_class(spans)
    sets = [cfg_mod.make_inputs(cfg, traffic.rng_for(seed, 3, k))
            for k in range(int(mix["input_sets"]))]
    sink = [n for t in tg.sinks() for n in tg.tasks[t].outputs]

    def one(inputs):
        ex = WorkflowExecutor(wf, sched_cls(wf), n_nodes=n_nodes,
                              hierarchy=tiered_hierarchy(),
                              device_of=lambda node: chip,
                              inject_inputs=inputs)
        counted.executor = ex
        try:
            res = ex.run()
            out = jax.block_until_ready(res.outputs)
        finally:
            ex.prefetch.shutdown()
        return res, out

    one(sets[0])                                  # compiles every body
    log(f"[exec] set-up: {len(tg.tasks)} tasks per workflow, "
        f"{sum(x.nbytes for x in sets[0].values())} input bytes, "
        f"{clock.since(0)[0]} compiles {clock.since(0)[1]:.2f} s")
    counted.total = counted.prefetched = 0.0
    tw = TraceWindow(cell, seconds) if trace else None
    c_mark = clock.mark()
    setup_s = time.perf_counter() - t_start

    outputs: list[tuple[int, dict]] = []
    io_wait = run_s = 0.0
    n_tasks = 0
    failed = 0
    t0 = time.perf_counter()
    w = 0
    while time.perf_counter() - t0 < seconds:
        if tw is not None:
            tw.tick(time.perf_counter() - t0)
        k = w % len(sets)
        try:
            res, out = one(sets[k])
        except (RuntimeError, ValueError, MemoryError) as e:
            failed += 1
            log(f"[exec] workflow {w} failed: {e!r}")
        else:
            outputs.append((k, {n: np.asarray(out[n]) for n in sink}))
            io_wait += res.io_wait_total
            run_s += sum(r["run"] for r in res.task_records.values())
            n_tasks += len(res.task_records)
        w += 1
    wall = time.perf_counter() - t0
    if tw is not None:
        tw.close()
    n_compiles, c_s = clock.since(c_mark)
    memory = peak_bytes(devices)
    trace_red = tw.reduce() if tw is not None else None

    t_ref = time.perf_counter()
    want = [serial_outputs(cfg_mod, ref_graph, s, sink) for s in sets]
    diff = 0.0
    for k, got in outputs:
        for n in sink:
            if got[n].shape != want[k][n].shape:
                diff = float("inf")
                continue
            diff = max(diff, float(np.abs(got[n].astype(np.float64)
                                          - want[k][n]).max()))
    if not outputs:
        diff = float("inf")
    limit = float(cfg_mod.LIMITS["mosaic_max_abs_diff"])
    control = None
    if mix.get("control"):
        # the control in the program's place: the serial reference with its
        # arithmetic in bfloat16 gives the mosaics that are compared
        import jax.numpy as jnp

        low = cfg_mod.build_graph(cfg, cfg_mod.make_bodies(cfg, jnp.bfloat16,
                                                           chip))
        control = max(float(np.abs(want[k][n] - serial_outputs(
            cfg_mod, low, sets[k], sink)[n]).max())
            for k in range(len(sets)) for n in sink)
    counts = {"workflows": w, "workflows_completed": len(outputs),
              "tasks": n_tasks, "input_sets": len(sets)}
    notes = [f"[exec] window {seconds} s, wall {wall:.2f} s, counts {counts}",
             f"[exec] compiles in the window: {n_compiles} ({c_s:.2f} s)",
             f"[exec] makespan s {wall / max(len(outputs), 1):.4f}; io_wait "
             f"{io_wait:.3f} s, run {run_s:.3f} s summed over tasks",
             f"[exec] reference: {len(sets)} serial runs, "
             f"{time.perf_counter() - t_ref:.2f} s",
             f"[exec] memory_peak_bytes {memory}"]
    if control is not None:
        notes.append(f"[exec] sound mosaic_max_abs_diff {diff!r}, control "
                     f"{control!r}")
    judged = diff if control is None else control
    readings = Readings(
        cell=cell, cfg=cfg, mix=mix, peaks=peaks, spans=dict(spans.seconds),
        trace=trace_red,
        extra={"counts": counts, "io_wait_s": io_wait, "run_s": run_s,
               "tasks": n_tasks, "input_bytes": counted.total,
               "prefetched_bytes": counted.prefetched, "wall_s": wall,
               "sound_diff": diff, "control_diff": control})
    e2e = {"setup_s": setup_s,
           "makespan_s": wall / len(outputs) if outputs else float("inf")}
    return CellRun(attempted=w, failed=failed, e2e=e2e,
                   checks={"mosaic_max_abs_diff": (judged, limit)},
                   correct=bool(judged <= limit), memory_peak_bytes=memory,
                   readings=readings, notes=notes)


def serial_outputs(cfg_mod, ref_graph, inputs: dict, sink: list[str]) -> dict:
    values = cfg_mod.serial_run(ref_graph, inputs)
    return {n: np.asarray(values[n], np.float64) for n in sink}
