"""bench/trace_reduce.py on traces the tests write themselves."""

import json

import pytest

from bench.trace_reduce import Event, load_xplane, merge, reduce_trace


def _trace(tmp_path):
    """Two chips and three host spans, in ns, written to and read back from
    a JSON file of events."""
    raw = {
        "devices": [
            [["fusion.1", 0, 30], ["fusion.2", 20, 30], ["all-reduce.3", 60, 10],
             ["fusion.4", 95, 20]],
            [["fusion.1", 0, 50], ["all-gather.2", 70, 20]],
        ],
        "spans": [["bench.decode_step", 0, 55], ["bench.prefill", 55, 45],
                  ["bench.park", 80, 10]],
    }
    path = tmp_path / "trace.json"
    path.write_text(json.dumps(raw))
    back = json.loads(path.read_text())
    devices = [[Event(*e) for e in chip] for chip in back["devices"]]
    return devices, [Event(*s) for s in back["spans"]]


def test_merge_unions_overlaps():
    assert merge([(5, 7), (0, 2), (1, 3), (7, 9), (10, 10)]) == [(0, 3), (5, 9)]


def test_busy_per_span_gaps_and_collectives(tmp_path):
    devices, spans = _trace(tmp_path)
    out = reduce_trace(devices, spans, (0, 100), top=3)
    # chip 0 busy [0,50) [60,70) [95,100) = 65; chip 1 [0,50) [70,90) = 70
    assert out["busy_s"] == pytest.approx(67.5e-9)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["collective_s"] == pytest.approx(15e-9)        # (10 + 20) / 2
    # decode_step [0,55): 50 + 50; prefill [55,100): 15 + 20; park [80,90)
    assert out["span_device_s"]["bench.decode_step"] == pytest.approx(50e-9)
    assert out["span_device_s"]["bench.prefill"] == pytest.approx(17.5e-9)
    assert out["span_device_s"]["bench.park"] == pytest.approx(5e-9)
    assert out["span_count"] == {"bench.decode_step": 1, "bench.prefill": 1,
                                 "bench.park": 1}
    # longest gaps: chip 0 [70,95), midpoint in park (innermost); chip 1
    # [50,70) in prefill; then the 10 ns gaps, in prefill
    gaps = out["idle_gaps"]
    assert gaps == [["bench.park", pytest.approx(25e-9)],
                    ["bench.prefill", pytest.approx(20e-9)],
                    ["bench.prefill", pytest.approx(10e-9)]]
    ops = dict(out["device_ops"])
    assert ops["fusion.1"] == pytest.approx(40e-9)             # (30 + 50) / 2
    assert len(out["device_ops"]) == 3


def test_gap_outside_every_span_is_named_host_none():
    out = reduce_trace([[Event("f", 0, 10)]], [], (0, 30))
    assert out["idle_gaps"] == [["host:none", pytest.approx(20e-9)]]
    assert out["busy_s"] == pytest.approx(10e-9)


def test_window_clips_events():
    out = reduce_trace([[Event("f", -10, 30), Event("g", 90, 30)]],
                       [Event("bench.x", -5, 200)], (0, 100))
    assert out["busy_s"] == pytest.approx(30e-9)
    assert out["span_device_s"]["bench.x"] == pytest.approx(30e-9)


def test_empty_window_or_no_device_raises():
    with pytest.raises(ValueError):
        reduce_trace([], [], (0, 10))
    with pytest.raises(ValueError):
        reduce_trace([[Event("f", 0, 1)]], [], (5, 5))


def test_load_xplane_reads_host_spans_of_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.decode_step"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("other"):
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    devices, spans = load_xplane(str(tmp_path))
    assert devices == []                      # no TPU plane on the CPU
    assert [s.name for s in spans] == ["bench.decode_step"]
    assert spans[0].dur_ns > 0
