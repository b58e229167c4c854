"""Readings that set the limits of the correctness check, and the sweep
that finds a serving cell's knee. Run on the chip; the benchmark's own runs
never run this.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20
    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --control
    python3 bench/calibrate.py --workload <cell> --seeds 7 --rates 0.5,1,1.5

Every run is one cell run in this process, at the cell's own load (or at
each ``--rates`` session rate). One JSON line per run goes to standard
output: the sound run's reading, the control's with ``--control`` (the
reference in the next precision down in the program's place, which is then
what ``correct`` judges), the counts and the end-to-end metrics.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)

    from bench import harness
    from bench.run import log, run_cell
    from repro.launch.compile_cache import use_compile_cache

    wl, _ = harness.find_workload(harness.load_benchmark(), args.workload)
    use_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < int(wl["chips"]):
        log(f"needs {wl['chips']} TPU chips, found {devs}")
        return 2
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    for seed in (int(s) for s in args.seeds.split(",")):
        for rate in rates:
            mix = {"control": True} if args.control else {}
            if rate is not None:
                mix.update(rate_per_s=rate, check_tokens=1)
            line, run = run_cell(args.workload, seed, args.seconds, False,
                                 devs[:int(wl["chips"])],
                                 t_start=time.perf_counter(),
                                 mix_override=mix)
            extra = run.readings.extra
            print(json.dumps({
                "seed": seed, "rate": rate, "correct": line["correct"],
                "sound": extra.get("sound_gap", extra.get("sound_diff")),
                "control": extra.get("control_gap", extra.get("control_diff")),
                "counts": extra.get("counts"), "metrics": line["metrics"],
                "failed": line["failed"], "attempted": line["attempted"],
                "memory_peak_bytes": line["device"]["memory_peak_bytes"]}),
                flush=True)
            del line, run
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
