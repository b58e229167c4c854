"""Reduce a profiler trace to device busy time, per-span device time and
named idle gaps.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
:func:`load_xplane` reads it with JAX alone into plain :class:`Event` lists:
the device operations of each chip (the ``XLA Ops`` line of every
``/device:TPU:<n>`` plane) and the benchmark's host spans (events whose name
starts with ``bench.``, written with ``jax.profiler.TraceAnnotation`` so that
they share the device trace's clock). :func:`reduce_trace` does the
arithmetic on those lists and is what the tests check on a trace they write
themselves.

* busy time: the union of a chip's operation intervals inside the window,
  averaged over the chips;
* device time per host span: busy time that overlaps each span, summed by
  span name (the program's jitted prefill and decode are both anonymous
  lambdas, so their device operations cannot be told apart by name);
* idle gaps: stretches of the window with no operation on the chip, named by
  the innermost host span around the gap's midpoint (``host:none`` when the
  host was in no span);
* collective time: operations whose name marks a collective.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Iterable, Sequence

SPAN_PREFIX = "bench."
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "allreduce", "allgather")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def merge(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """Union of half-open intervals, sorted and non-overlapping."""
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def _overlap(merged: Sequence[tuple[float, float]], lo: float,
             hi: float) -> float:
    """Length of ``[lo, hi)`` covered by the merged intervals."""
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merged
               if b > lo and a < hi)


def _gaps(merged: Sequence[tuple[float, float]], lo: float,
          hi: float) -> list[tuple[float, float]]:
    out, t = [], lo
    for a, b in merged:
        if b <= lo or a >= hi:
            continue
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def _span_at(spans: Sequence[Event], t: float) -> str:
    """The innermost (shortest) host span that contains ``t``."""
    around = [s for s in spans if s.start_ns <= t < s.end_ns]
    if not around:
        return "host:none"
    return min(around, key=lambda s: s.dur_ns).name


def reduce_trace(device_ops: Sequence[Sequence[Event]],
                 host_spans: Sequence[Event], window: tuple[float, float],
                 *, top: int = 10) -> dict:
    """Busy time, per-span device time, top operations and longest idle gaps
    inside ``window`` (ns), from each chip's operation events.

    Returns seconds: ``busy_s`` (mean over chips), ``window_s``,
    ``span_device_s`` {span name: device seconds}, ``span_count`` {span name:
    spans in the window}, ``collective_s`` (mean over chips), ``device_ops``
    [[name, seconds], ...] and ``idle_gaps`` [[span name, seconds], ...],
    each at most ``top`` long."""
    lo, hi = window
    if not device_ops or hi <= lo:
        raise ValueError("no device operations or an empty window")
    spans = [s for s in host_spans if s.end_ns > lo and s.start_ns < hi]
    busy, coll = 0.0, 0.0
    per_span: dict[str, float] = defaultdict(float)
    op_time: dict[str, float] = defaultdict(float)
    gaps: list[tuple[float, str]] = []
    for ops in device_ops:
        inside = [e for e in ops if e.end_ns > lo and e.start_ns < hi]
        merged = merge((max(e.start_ns, lo), min(e.end_ns, hi))
                       for e in inside)
        busy += _overlap(merged, lo, hi)
        coll += _overlap(merge((max(e.start_ns, lo), min(e.end_ns, hi))
                               for e in inside
                               if any(k in e.name.lower()
                                      for k in COLLECTIVES)), lo, hi)
        for s in spans:
            per_span[s.name] += _overlap(merged, max(s.start_ns, lo),
                                         min(s.end_ns, hi))
        for e in inside:
            op_time[e.name] += min(e.end_ns, hi) - max(e.start_ns, lo)
        for a, b in _gaps(merged, lo, hi):
            gaps.append((b - a, _span_at(spans, (a + b) / 2)))
    n = len(device_ops)
    count: dict[str, int] = defaultdict(int)
    for s in spans:
        count[s.name] += 1
    ops_sorted = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: -g[0])
    return {
        "busy_s": busy / n * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "span_device_s": {k: v / n * 1e-9 for k, v in per_span.items()},
        "span_count": dict(count),
        "collective_s": coll / n * 1e-9,
        "device_ops": [[k, v / n * 1e-9] for k, v in ops_sorted],
        "idle_gaps": [[name, dur * 1e-9] for dur, name in gaps[:top]],
    }


def load_xplane(trace_dir: str) -> tuple[list[list[Event]], list[Event]]:
    """Each TPU chip's ``XLA Ops`` events and the ``bench.`` host spans from
    the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices: list[list[Event]] = []
    spans: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.append([Event(e.name, e.start_ns, e.duration_ns)
                                    for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return devices, spans

