"""What every cell shares: finding a cell's files by the names in
``BENCHMARK.json``, the device, compile counting, host spans, the profiler
window and the result line.

A cell is a configuration under a traffic mix. Its files are found by name:

* ``bench/configs/<config>.json``: the configuration as it is run, and
  ``bench/configs/<config>.py`` beside it: the plain reference, the limits of
  the comparison and what the driver needs to build the system;
* ``bench/traffic/<mix>.json``: the mix's parameters; its ``driver`` names
  the loop that runs it (``bench/<driver>_loop.py``);
* ``bench/metrics/<metric>.py``: a reader with ``read(readings)`` that
  returns the metric's value, or None where it finds nothing to read.

Adding a cell, a mix or a metric adds files and entries; nothing here
changes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Callable

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
OUT = ROOT / ".bench_out"
TRACE_SECONDS = 3.0      # length of the traced stretch of a --trace 1 run


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find_workload(bench: dict, name: str) -> tuple[dict, dict]:
    """The workload entry and its configuration entry."""
    for w in bench["workloads"]:
        if w["name"] == name:
            for c in bench["configs"]:
                if c["name"] == w["config"]:
                    return w, c
            raise KeyError(f"workload {name!r}: no configuration "
                           f"{w['config']!r}")
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def load_module(path: Path):
    """Import a file of the benchmark by path (names may hold '-' and '.')."""
    mod_name = "bench_" + "".join(ch if ch.isalnum() else "_"
                                  for ch in str(path.relative_to(HERE)))
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_config(entry: dict) -> tuple[dict, Any]:
    """The configuration file and its module (reference, limits, builders)."""
    path = ROOT / entry["file"]
    cfg = json.loads(path.read_text())
    return cfg, load_module(path.with_suffix(".py"))


def load_driver(name: str):
    return importlib.import_module(f"bench.{name}_loop")


def peaks_for(device_kind: str) -> dict:
    """The published peaks of a device kind; an unknown kind is an error."""
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in bench/peaks.json (have {sorted(table)})")
    return table[device_kind]


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


# ------------------------------------------------------------- statistics
def percentile(values, q: float) -> float:
    """The q-th percentile by linear interpolation between order statistics
    (numpy's default)."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


# ---------------------------------------------------------------- compiles
class CompileClock:
    """Backend compiles and their seconds, from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax

        self.seconds: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds.append(duration)

    def mark(self) -> int:
        return len(self.seconds)

    def since(self, mark: int) -> tuple[int, float]:
        new = self.seconds[mark:]
        return len(new), sum(new)


# ------------------------------------------------------------------ spans
class Spans:
    """Host spans around the calls into each layer, kept in memory.

    Off, ``span`` is a bare context and nothing is recorded, so
    the timed path of an untraced run carries no instrumentation. On, each
    span is timed by the host clock and also written into the profiler's
    trace with ``jax.profiler.TraceAnnotation`` as ``bench.<name>``, on the
    device trace's clock."""

    def __init__(self, on: bool) -> None:
        self.on = on
        self.seconds: dict[str, list[float]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.on:
            yield
            return
        import jax

        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        self.seconds.setdefault(name, []).append(time.perf_counter() - t)


class TraceWindow:
    """The profiler on for one steady stretch of the window, driven by the
    loop's clock through :meth:`tick`.

    The traced stretch (the ``bench.window`` span that the reduction reads)
    lasts ``length_s`` and ends 1.5 s before the window does; the profiler
    starts ``settle_s`` before it, so that the first work under the profiler
    falls outside it. The trace is written to a directory of the checkout
    that is removed once it has been reduced."""

    def __init__(self, name: str, window_s: float,
                 length_s: float = TRACE_SECONDS,
                 settle_s: float = 0.5) -> None:
        self.dir = OUT / "trace" / name
        self.start_s = max(0.0, window_s - length_s - 1.5)
        self.length_s, self.settle_s = length_s, settle_s
        self.state = "idle"
        self._t = 0.0
        self._ann = None

    @property
    def open(self) -> bool:
        return self.state == "open"

    def tick(self, t: float) -> None:
        import jax

        if self.state == "idle" and t >= self.start_s:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir.mkdir(parents=True, exist_ok=True)
            jax.profiler.start_trace(str(self.dir))
            self.state, self._t = "settling", t
        elif self.state == "settling" and t >= self._t + self.settle_s:
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
            self.state, self._t = "open", t
        elif self.state == "open" and t >= self._t + self.length_s:
            self.close()

    def close(self) -> None:
        """End the traced stretch and the profiler, wherever they are."""
        import jax

        if self.state == "open":
            self._ann.__exit__(None, None, None)
        if self.state in ("settling", "open"):
            jax.profiler.stop_trace()
            self.state = "done"

    def reduce(self) -> dict | None:
        """The reduced trace of the stretch; None when it holds no TPU
        operations (a CPU run) or the stretch never opened."""
        from bench.trace_reduce import load_xplane, reduce_trace

        if self.state != "done":
            return None
        devices, spans = load_xplane(str(self.dir))
        shutil.rmtree(self.dir, ignore_errors=True)
        win = [s for s in spans if s.name == "bench.window"]
        if not devices or not win:
            return None
        lo, hi = win[0].start_ns, win[0].end_ns
        inner = [s for s in spans if s.name != "bench.window"]
        return reduce_trace(devices, inner, (lo, hi))


# -------------------------------------------------------------- results
@dataclasses.dataclass
class Readings:
    """What a per-layer metric's reader gets: the configuration, the mix and
    the peaks; the host spans (seconds per span name, over the window); the
    reduced trace (None when the run was not traced); and the driver's own
    counts and counters (``extra``)."""

    cell: str
    cfg: dict
    mix: dict
    peaks: dict
    spans: dict[str, list[float]]
    trace: dict | None
    extra: dict


@dataclasses.dataclass
class CellRun:
    """One run of a cell, as its driver returns it."""

    attempted: int
    failed: int
    e2e: dict[str, float]               # end-to-end metric name -> value
    checks: dict[str, tuple[float, float]]   # compared number -> (value, limit)
    correct: bool
    memory_peak_bytes: int
    readings: Readings | None = None
    notes: list[str] = dataclasses.field(default_factory=list)


def device_info(devices, memory_peak_bytes: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(memory_peak_bytes)}


def peak_bytes(devices) -> int:
    """Peak device memory in use on the fullest of ``devices``."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks) if peaks else 0


def result_line(bench: dict, workload: str, run: CellRun, devices,
                trace: bool, *, log: Callable[[str], None]) -> dict:
    """The contract's last line: end-to-end metrics untraced, per-layer
    metrics traced, and the compared numbers last."""
    metrics: dict[str, dict] = {}
    if trace:
        r = run.readings
        for m in bench["per_layer"]:
            if not applies(m, workload):
                continue
            reader = load_module(HERE / "metrics" / f"{m['name']}.py")
            value = reader.read(r)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in bench["end_to_end"]:
            if not applies(m, workload):
                continue
            if m["name"] not in run.e2e:
                raise RuntimeError(f"{workload}: no value for {m['name']}")
            metrics[m["name"]] = {"value": float(run.e2e[m["name"]]),
                                  "unit": m["unit"]}
    device = device_info(devices, run.memory_peak_bytes)
    line: dict[str, Any] = {"correct": bool(run.correct),
                            "attempted": int(run.attempted),
                            "failed": int(run.failed),
                            "metrics": metrics, "device": device}
    if trace and run.readings is not None and run.readings.trace is not None:
        t = run.readings.trace
        device["busy_s"] = t["busy_s"]
        device["window_s"] = t["window_s"]
        line["breakdown"] = {"device_ops": t["device_ops"],
                             "idle_gaps": t["idle_gaps"]}
    for note in run.notes:
        log(note)
    for name, (value, limit) in run.checks.items():
        log(f"check {name} {value!r} limit {limit!r}")
    line["checks"] = {name: {"value": _finite(value), "limit": limit}
                      for name, (value, limit) in run.checks.items()}
    return line


def _finite(x: float) -> float | None:
    """JSON has no NaN or infinity: a number that is neither stays, else
    None."""
    return float(x) if math.isfinite(x) else None
