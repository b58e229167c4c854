"""Run one benchmark cell on the chips of this machine and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json``; its configuration, traffic mix
and per-layer metrics are found by name under ``bench/``. With ``--trace 0``
the result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, the device's busy time and a breakdown from a profiler
trace of a steady stretch of the window. The last line of standard output is
the result, one JSON object; the numbers the correctness check compared, each
with its limit, are the last lines of standard error and the result's last
key. Without a TPU, or with fewer chips than the cell asks for, it exits
with code 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, devices,
             *, t_start: float, hook=None, cfg_override: dict | None = None,
             mix_override: dict | None = None):
    """Run one cell on ``devices`` and return (result line, CellRun).

    ``cfg_override`` and ``mix_override`` replace entries of the
    configuration file and the mix (the tests run cells at a small size);
    ``hook`` reaches the system under test before the window (the tests plant
    faults through it)."""
    from bench import harness, traffic

    bench = harness.load_benchmark()
    wl, entry = harness.find_workload(bench, workload)
    cfg, cfg_mod = harness.load_config(entry)
    cfg.update(cfg_override or {})
    mix = traffic.load_mix(wl["traffic"])
    mix.update(mix_override or {})
    peaks = harness.peaks_for(devices[0].device_kind) \
        if devices[0].platform == "tpu" else {}
    driver = harness.load_driver(mix["driver"])
    clock = harness.CompileClock()
    cell_run = driver.run(cfg, cfg_mod, mix, cell=workload, seed=seed,
                          seconds=seconds, trace=trace, devices=devices,
                          peaks=peaks, clock=clock, t_start=t_start, log=log,
                          hook=hook)
    line = harness.result_line(bench, workload, cell_run, devices, trace,
                               log=log)
    return line, cell_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    from repro.launch.compile_cache import use_compile_cache

    wl, _ = harness.find_workload(harness.load_benchmark(), args.workload)
    cache = use_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"no TPU: JAX found {len(devs)} {devs[0].platform} device(s)")
        return 2
    if len(devs) < int(wl["chips"]):
        log(f"{args.workload} needs {wl['chips']} chips, JAX found {len(devs)}")
        return 2
    harness.peaks_for(devs[0].device_kind)
    log(f"{args.workload}: {len(devs)} x {devs[0].device_kind}, jax "
        f"{jax.__version__}, compile cache {cache}")
    line, _ = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), devs[:int(wl["chips"])],
                       t_start=T_START)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
