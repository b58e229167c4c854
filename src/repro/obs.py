"""Spans, counters and a compile counter, owned by the program.

``span(name, key=None)`` marks a stretch of host work inside one layer of the
program (``engine.step``, ``store.get``, ``task.body``, ...). It records only
while a JAX profiler session collects (``jax.profiler.trace(dir)`` or
``jax.profiler.start_trace``). Then it enters a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``, so that the span
lands in the profile on the device trace's clock with ``key`` (a session or
task id) as metadata, and it appends a :class:`Record` to a bounded buffer
in memory. With no profiler collecting, a span costs one check and is a
shared null context.

``count(name, n)`` adds ``n`` to a named counter (``engine.kv_blocks_read``,
...). Like a span, it records only while a profiler collects, which
``collecting()`` tells a caller whose count costs work of its own.

:func:`summary` reduces the buffer per span name and gives the counters;
:func:`reset` empties both. The profile is the only exporter of spans.

The compile counter listens for JAX's backend-compile event (a compile, or a
load from the persistent compile cache) from import on, whether or not a
profiler collects, and keeps each event's function name, seconds and end
time: :func:`compiles`.
"""

from __future__ import annotations

import collections
import functools
import threading
import time
from typing import Any, Callable, NamedTuple

import jax

__all__ = ["Record", "span", "traced", "count", "collecting", "summary",
           "reset", "records", "compiles"]

PREFIX = "repro."
MAX_RECORDS = 1 << 16
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_Annotation = jax.profiler.TraceAnnotation
_collecting = _Annotation.is_enabled


class Record(NamedTuple):
    """One ended span. Times are ``time.perf_counter()`` seconds."""

    name: str
    start: float
    end: float
    parent: str | None      # the innermost open span on the same thread
    key: Any
    thread: int
    self_s: float           # end - start, less the child spans' durations
    outer: bool             # inside no other span of its layer on the thread


# plain tuples in Record's field order: a Record is built only when read
_records: collections.deque[tuple] = collections.deque(maxlen=MAX_RECORDS)
_compiles: collections.deque[tuple[float, str, float]] = \
    collections.deque(maxlen=MAX_RECORDS)
_counters: collections.Counter[str] = collections.Counter()
_local = threading.local()
_names: dict[str, tuple[str, str]] = {}   # name -> (annotation name, layer)


def _layer(name: str) -> str:
    """The layer a span name belongs to: the part before its first dot."""
    return name.partition(".")[0]


class _Off:
    """The span while no profiler collects: does nothing."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def ready(self, x: Any) -> Any:
        return x


_OFF = _Off()


class _Span:
    __slots__ = ("name", "key", "layer", "ann", "start", "child_s", "outer")

    def __init__(self, name: str, key: Any) -> None:
        self.name, self.key, self.child_s = name, key, 0.0

    def __enter__(self) -> "_Span":
        try:
            ann_name, layer = _names[self.name]
        except KeyError:
            ann_name, layer = _names.setdefault(
                self.name, (PREFIX + self.name, _layer(self.name)))
        try:
            stack = _local.stack
        except AttributeError:
            stack = _local.stack = []
        self.layer, self.outer = layer, True
        for s in stack:
            if s.layer == layer:
                self.outer = False
                break
        self.ann = (_Annotation(ann_name) if self.key is None
                    else _Annotation(ann_name, key=self.key))
        self.ann.__enter__()
        stack.append(self)
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter()
        self.ann.__exit__(*exc)
        stack = _local.stack
        stack.pop()
        dur = end - self.start
        if stack:
            parent = stack[-1]
            parent.child_s += dur
            parent = parent.name
        else:
            parent = None
        _records.append((self.name, self.start, end, parent, self.key,
                         threading.get_ident(), dur - self.child_s,
                         self.outer))
        return None

    def ready(self, x: Any) -> Any:
        """Block until ``x`` is computed, so that the span ends with the
        device work it dispatched; returns ``x``."""
        return jax.block_until_ready(x)


def span(name: str, key: Any = None) -> _Span | _Off:
    """A context manager around one stretch of work named ``name``; its
    ``ready(x)`` blocks on ``x`` only while recording."""
    if not _collecting():
        return _OFF
    return _Span(name, key)


def traced(name: str) -> Callable:
    """Decorate a function so that each call is the span ``name``."""

    def wrap(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            if not _collecting():
                return fn(*args, **kwargs)
            with _Span(name, None):
                return fn(*args, **kwargs)

        return inner

    return wrap


def collecting() -> bool:
    """True while a profiler collects, that is while spans and counters
    record: a caller checks it before it computes what it would count."""
    return _collecting()


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name`` while a profiler collects."""
    if _collecting():
        _counters[name] += n


def records() -> list[Record]:
    """The recorded spans, oldest first (the last ``MAX_RECORDS``)."""
    return [Record(*r) for r in _records]


def reset() -> None:
    """Forget every recorded span and counter (the compile counter keeps
    counting)."""
    _records.clear()
    _counters.clear()


def summary() -> dict:
    """What the buffer holds, reduced:

    * ``spans``: per span name, ``count``, ``total_s`` (host seconds) and
      ``self_s`` (host seconds less those of child spans on the same
      thread);
    * ``layers``: per layer (the name up to its first dot), ``count`` and
      ``total_s`` of its outermost spans, those inside no other span of the
      same layer on their thread, so that nested calls count once;
    * ``start_s``: ``time.perf_counter()`` at the earliest recorded start,
      None when nothing is recorded;
    * ``counters``: per counter name, its total (:func:`count`);
    * ``compiles``: per function name, ``count`` and ``seconds`` of every
      compile event seen (:func:`compiles`).
    """
    spans: dict[str, dict] = {}
    layers: dict[str, dict] = {}
    recs = records()
    for r in recs:
        s = spans.setdefault(r.name, {"count": 0, "total_s": 0.0,
                                      "self_s": 0.0})
        s["count"] += 1
        s["total_s"] += r.end - r.start
        s["self_s"] += r.self_s
        if r.outer:
            lay = layers.setdefault(_layer(r.name),
                                    {"count": 0, "total_s": 0.0})
            lay["count"] += 1
            lay["total_s"] += r.end - r.start
    return {"spans": spans, "layers": layers,
            "start_s": min((r.start for r in recs), default=None),
            "counters": dict(_counters), "compiles": compiles()}


def compiles(before: float | None = None) -> dict[str, dict]:
    """Per function name, the ``count`` and ``seconds`` of the compile
    events (compiles and persistent-cache loads) that ended before
    ``before`` (a ``time.perf_counter()`` value), or of all of them."""
    out: dict[str, dict] = {}
    for t, fun, seconds in list(_compiles):
        if before is not None and t >= before:
            continue
        c = out.setdefault(fun, {"count": 0, "seconds": 0.0})
        c["count"] += 1
        c["seconds"] += seconds
    return out


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == COMPILE_EVENT:
        _compiles.append((time.perf_counter(),
                          str(kwargs.get("fun_name", "?")), duration))


jax.monitoring.register_event_duration_secs_listener(_on_duration)
