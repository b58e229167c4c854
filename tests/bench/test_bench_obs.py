"""The program's own spans in the benchmark: the trace reduction would name
idle gaps by them, but the trace's loader leaves them out, so that the
``bench.*`` numbers and the breakdown stay as they were; the five readers of
``repro.obs``; and a cell's window without a profiler leaves the program's
buffer empty."""

import collections
import time

import jax
import pytest
from test_bench_cells import montage, serve
from test_bench_trace_reduce import _trace

from bench import harness
from bench.trace_reduce import Event, load_xplane, reduce_trace
from repro import obs

PROGRAM_SPANS = [Event("repro.engine.submit", 54, 44),
                 Event("repro.engine.write_slot", 58, 7),
                 Event("repro.store.put", 81, 3)]


def test_gaps_go_to_the_innermost_program_span(tmp_path):
    """Given the program's spans, the reduction names each gap by the
    innermost span around it and leaves every ``bench.*`` number alone."""
    devices, spans = _trace(tmp_path)
    before = reduce_trace(devices, spans, (0, 100), top=3)
    after = reduce_trace(devices, spans + PROGRAM_SPANS, (0, 100), top=3)
    # chip 0's gap [70,95) has its midpoint in store.put (inside bench.park);
    # chip 1's [50,70) in write_slot (inside engine.submit, inside
    # bench.prefill); chip 0's [50,60) in engine.submit
    assert after["idle_gaps"] == [
        ["repro.store.put", pytest.approx(25e-9)],
        ["repro.engine.write_slot", pytest.approx(20e-9)],
        ["repro.engine.submit", pytest.approx(10e-9)]]
    for key in ("busy_s", "window_s", "collective_s", "device_ops"):
        assert after[key] == before[key]
    for key in ("span_device_s", "span_count"):
        bench_only = {k: v for k, v in after[key].items()
                      if k.startswith("bench.")}
        assert bench_only == before[key]
    # write_slot [58,65): chip 0 idle from 50 to 60, busy 60-65; chip 1 idle
    assert after["span_device_s"]["repro.engine.write_slot"] == \
        pytest.approx(2.5e-9)
    assert after["span_count"]["repro.engine.write_slot"] == 1


def test_load_xplane_leaves_out_the_program_spans(tmp_path):
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    obs.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.decode_step"):
            with obs.span("engine.step"):
                f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("other"):
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
        obs.reset()
    _, spans = load_xplane(str(tmp_path))
    assert [s.name for s in spans] == ["bench.decode_step"]


def _rec(name, start, dur, *, self_s=None, outer=True, parent=None):
    return obs.Record(name, start, start + dur, parent, None, 1,
                      dur if self_s is None else self_s, outer)


def _readings(trace=None):
    return harness.Readings(cell="c", cfg={}, mix={}, peaks={}, spans={},
                            trace=trace, extra={})


def _read(metric, readings):
    return harness.load_module(harness.HERE / "metrics"
                               / f"{metric}.py").read(readings)


@pytest.fixture
def buffers(monkeypatch):
    """Empty span and compile buffers in place of the program's."""
    recs = collections.deque()
    comp = collections.deque()
    monkeypatch.setattr(obs, "_records", recs)
    monkeypatch.setattr(obs, "_compiles", comp)
    return recs, comp


PROGRAM_METRICS = ["step_host_ms", "store_ms_per_task",
                   "prefetch_wait_ms_per_task", "setup_compile_s",
                   "slot_write_ms"]


@pytest.mark.parametrize("metric", PROGRAM_METRICS)
def test_reader_finds_nothing_to_read(metric, buffers):
    _, comp = buffers
    comp.append((1.0, "jit(f)", 2.0))      # compiles, but no span recorded
    assert _read(metric, _readings()) is None
    empty = {"span_count": {}, "span_device_s": {}}
    assert _read(metric, _readings(empty)) is None


def test_step_host_ms_is_self_time_per_step(buffers):
    recs, _ = buffers
    recs.extend([_rec("engine.step.sync", 0.004, 0.006, outer=False,
                      parent="engine.step"),
                 _rec("engine.step", 0.0, 0.010, self_s=0.004),
                 _rec("engine.step", 1.0, 0.012, self_s=0.002),
                 _rec("engine.park", 2.0, 0.5)])
    assert _read("step_host_ms", _readings()) == pytest.approx(3.0)


def test_store_and_prefetch_wait_per_task(buffers):
    recs, _ = buffers
    recs.extend([_rec("task", 10.0 + i, 0.5) for i in range(4)])
    recs.extend([_rec("store.get", 10.1, 0.001), _rec("store.get", 11.1, 0.001),
                 _rec("store.get", 12.1, 0.001), _rec("store.put", 13.1, 0.002),
                 # a store call inside another store call counts once
                 _rec("store.get", 13.1, 0.0005, outer=False,
                      parent="store.put"),
                 _rec("prefetch.wait", 10.0, 0.001),
                 _rec("prefetch.wait", 12.0, 0.003)])
    assert _read("store_ms_per_task", _readings()) == pytest.approx(1.25)
    assert _read("prefetch_wait_ms_per_task", _readings()) == \
        pytest.approx(1.0)


def test_prefetch_wait_is_zero_when_no_consumer_came_early(buffers):
    recs, _ = buffers
    recs.append(_rec("task", 1.0, 0.5))
    assert _read("prefetch_wait_ms_per_task", _readings()) == 0.0


def test_setup_compile_s_counts_compiles_before_the_first_span(buffers):
    recs, comp = buffers
    comp.extend([(1.0, "jit(prefill)", 2.0), (2.0, "jit(decode_step)", 3.0),
                 (50.0, "jit(head)", 7.0)])      # the reference, after
    recs.extend([_rec("engine.step", 20.0, 0.01), _rec("engine.step", 10.0,
                                                       0.01)])
    assert _read("setup_compile_s", _readings()) == pytest.approx(5.0)


def test_slot_write_ms_is_host_time_per_write(buffers):
    recs, _ = buffers
    recs.extend([_rec("engine.write_slot", 1.0, 0.011, outer=False,
                      parent="engine.submit"),
                 _rec("engine.submit", 0.9, 0.2),
                 _rec("engine.write_slot", 2.0, 0.009, outer=False,
                      parent="engine.resume"),
                 _rec("engine.read_slot", 3.0, 0.5, outer=False,
                      parent="engine.park")])
    assert _read("slot_write_ms", _readings()) == pytest.approx(10.0)


@pytest.mark.parametrize("cell", ["montage-2mass.exec", "granite-3-2b.short"])
def test_untraced_window_leaves_no_program_records(cell):
    obs.reset()
    t0 = time.perf_counter()
    line, _ = montage() if cell.startswith("montage") else serve(cell)
    assert line["correct"]
    assert obs.records() == []
    assert obs.summary()["start_s"] is None
    assert obs.compiles(before=t0) != obs.compiles()   # the counter counted


def test_traced_cells_report_the_program_span_metrics():
    obs.reset()
    line, _ = montage(trace=True)
    m = line["metrics"]
    assert {"store_ms_per_task", "prefetch_wait_ms_per_task",
            "setup_compile_s"} <= set(m)
    assert m["store_ms_per_task"]["value"] > 0
    assert m["setup_compile_s"]["value"] > 0
    obs.reset()
    line, _ = serve("granite-3-2b.sessions", trace=True, rate=2.0)
    m = line["metrics"]
    assert {"step_host_ms", "slot_write_ms", "setup_compile_s"} <= set(m)
    assert m["step_host_ms"]["value"] > 0
    assert m["slot_write_ms"]["value"] > 0
    obs.reset()
