"""repro.obs: spans that record only while a profiler collects, with their
parents, self times and keys; the spans of the serving and workflow paths;
and the compile counter."""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_smoke
from repro.core import (ProactiveScheduler, TaskGraph, WorkflowExecutor,
                        compile_workflow, size_hint)
from repro.core.locstore import LocStore, tiered_hierarchy
from repro.models import init_params
from repro.serve.engine import Router, ServingEngine


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(get_smoke("granite-3-2b"), dtype="float32")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def empty_buffer():
    obs.reset()
    yield
    obs.reset()


def serve_one_session(cfg, params) -> int:
    """Submit, step, park, resume through the router, step, finish."""
    store = LocStore(1, hierarchy=tiered_hierarchy())
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64, store=store)
    router = Router([eng], store)
    sid = eng.submit([1, 2, 3])
    eng.step()
    eng.park(sid)
    d = router.follow_up(sid, [])
    assert d.kind == "hit_parked" and d.resumed
    eng.step()
    eng.finish(sid)
    return sid


def run_three_tasks():
    """``a`` and a slow ``b`` from ``x``, then ``c`` from both: ``a``'s
    output exists while ``c`` is still pending, so the proactive scheduler
    pre-places it and the prefetch engine stages it onto the device."""
    g = TaskGraph()
    g.add_data("x", size_bytes=size_hint(4 * 64))

    def slow(x):
        time.sleep(0.2)
        return {"b": x * 2}

    g.add_task("a", inputs=("x",), outputs=("a",), fn=lambda x: {"a": x + 1})
    g.add_task("b", inputs=("x",), outputs=("b",), fn=slow)
    g.add_task("c", inputs=("a", "b"), outputs=("c",),
               fn=lambda a, b: {"c": a + b})
    wf = compile_workflow(g)
    dev = jax.devices()[0]
    ex = WorkflowExecutor(wf, ProactiveScheduler(wf), n_nodes=2,
                          hierarchy=tiered_hierarchy(),
                          device_of=lambda node: dev,
                          inject_inputs={"x": np.arange(64,
                                                        dtype=np.float32)})
    try:
        res = ex.run()
    finally:
        ex.prefetch.shutdown()
    x = np.arange(64, dtype=np.float32)
    np.testing.assert_array_equal(np.asarray(res.outputs["c"]),
                                  x + 1 + x * 2)
    return ex


def test_no_profiler_leaves_no_records(model):
    serve_one_session(*model)
    run_three_tasks()
    obs.count("engine.kv_blocks_read", 3)
    assert obs.records() == []
    s = obs.summary()
    assert s["spans"] == {} and s["layers"] == {} and s["start_s"] is None
    assert s["counters"] == {}


def test_counters_add_while_a_profiler_collects_and_reset(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        obs.count("a", 2)
        obs.count("a")
        obs.count("b", 0)
    obs.count("a", 100)                 # after the profiler: not recorded
    assert obs.summary()["counters"] == {"a": 3, "b": 0}
    obs.reset()
    assert obs.summary()["counters"] == {}


def test_engine_counts_the_kv_blocks_a_step_reads(model, tmp_path):
    """At max_seq 1024 a block is 512 positions: in each layer a slot of
    cached length 3 reads one block, one of 600 reads two, an idle slot one
    (its first index is fetched); the pool holds 3 x 2 blocks a layer. Each
    step also counts itself, its live sequences and their cached
    positions."""
    cfg, params = model
    eng = ServingEngine(cfg, params, max_batch=3, max_seq=1024)
    eng.submit([1, 2, 3])
    eng.submit(list(range(1, 601)))
    with jax.profiler.trace(str(tmp_path)):
        eng.step()                      # cached lengths 3, 600 and idle
        eng.step()                      # 4, 601
    L = cfg.n_layers
    assert obs.summary()["counters"] == {"engine.kv_blocks_read": 2 * 4 * L,
                                         "engine.kv_blocks_pool": 2 * 6 * L,
                                         "engine.decode_steps": 2,
                                         "engine.decode_tokens": 4,
                                         "engine.kv_tokens_live": 603 + 605}


def test_nested_spans_carry_parents_self_time_and_keys(tmp_path):
    x = jnp.ones((8, 8))
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("outer", key=5):
            time.sleep(0.02)
            with obs.span("outer.inner", key="k") as sp:
                time.sleep(0.03)
                assert sp.ready(x) is x
        t = threading.Thread(target=lambda: obs.span("other").__enter__()
                             .__exit__(None, None, None))
        t.start()
        t.join()
    rec = {r.name: r for r in obs.records()}
    assert set(rec) == {"outer", "outer.inner", "other"}
    inner, outer, other = rec["outer.inner"], rec["outer"], rec["other"]
    assert (inner.parent, inner.key, inner.outer) == ("outer", "k", False)
    assert (outer.parent, outer.key, outer.outer) == (None, 5, True)
    assert other.parent is None and other.thread != outer.thread
    assert outer.start <= inner.start < inner.end <= outer.end
    assert outer.self_s == pytest.approx(
        (outer.end - outer.start) - (inner.end - inner.start), abs=1e-9)
    assert 0.02 <= outer.self_s < 0.03 <= inner.self_s
    s = obs.summary()
    assert s["spans"]["outer"]["count"] == 1
    assert s["spans"]["outer"]["self_s"] == pytest.approx(outer.self_s)
    assert s["spans"]["outer"]["total_s"] == pytest.approx(
        outer.end - outer.start)
    # the layer's outermost spans only: "outer.inner" sits inside "outer"
    assert s["layers"]["outer"] == {"count": 1,
                                    "total_s": s["spans"]["outer"]["total_s"]}
    assert s["start_s"] == outer.start
    obs.reset()
    assert obs.records() == []


def test_off_span_is_shared_and_ready_does_not_block():
    a, b = obs.span("engine.step"), obs.span("store.put", key=1)
    assert a is b
    with a as sp:
        assert sp.ready("not an array") == "not an array"
    assert obs.records() == []


def test_serving_path_records_each_span(model, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        sid = serve_one_session(*model)
    recs = obs.records()
    parents = {}
    for r in recs:
        parents.setdefault(r.name, set()).add(r.parent)
    assert parents["router.follow_up"] == {None}
    assert parents["router.route"] == {"router.follow_up"}
    assert parents["engine.resume"] == {"router.follow_up"}
    assert parents["engine.submit"] == {None}
    assert parents["engine.prefill"] == {"engine.submit"}
    assert parents["engine.write_slot"] == {"engine.submit", "engine.resume"}
    assert parents["engine.park"] == {None}
    assert parents["engine.read_slot"] == {"engine.park"}
    assert parents["engine.step"] == {None}
    assert parents["engine.step.sync"] == {"engine.step"}
    assert parents["store.get"] == {"engine.resume"}
    assert parents["store.put"] == {"engine.submit", "engine.park",
                                    "engine.resume"}
    keyed = {r.name: r.key for r in recs if r.key is not None}
    assert {keyed[n] for n in ("engine.submit", "engine.park",
                               "engine.resume", "router.follow_up")} == {sid}
    s = obs.summary()
    assert s["spans"]["engine.step"]["count"] == 2
    assert s["spans"]["engine.write_slot"]["count"] == 2
    assert s["layers"]["store"]["count"] == s["spans"]["store.get"]["count"] \
        + s["spans"]["store.put"]["count"]


def test_workflow_records_executor_task_store_and_prefetch_spans(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        run_three_tasks()
    recs = obs.records()
    names = {r.name for r in recs}
    assert {"executor.run", "executor.dispatch", "executor.wait",
            "executor.drain", "task", "task.stage_in", "task.body",
            "task.put", "store.put", "store.get",
            "prefetch.stage"} <= names
    assert {r.key for r in recs if r.name == "task"} == {"a", "b", "c"}
    parent = {r.name: r.parent for r in recs}
    for child in ("task.stage_in", "task.body", "task.put"):
        assert parent[child] == "task"
    for child in ("executor.dispatch", "executor.wait", "executor.drain"):
        assert parent[child] == "executor.run"
    worker = {r.thread for r in recs if r.name == "task"}
    control = {r.thread for r in recs if r.name == "executor.run"}
    stage = {r.thread for r in recs if r.name == "prefetch.stage"}
    assert not (worker | stage) & control
    assert {r.parent for r in recs if r.name == "prefetch.wait"} <= \
        {"task.stage_in"}


def _fresh_for_the_compile_counter(x):
    return x * 3 + 1


def test_compile_listener_counts_a_fresh_jit_once():
    name = "jit(_fresh_for_the_compile_counter)"
    t0 = time.perf_counter()
    f = jax.jit(_fresh_for_the_compile_counter)
    f(jnp.ones(5)).block_until_ready()
    f(jnp.ones(5)).block_until_ready()
    c = obs.compiles()[name]
    assert c["count"] == 1 and c["seconds"] > 0
    assert name not in obs.compiles(before=t0)
    assert obs.summary()["compiles"][name] == c


def test_serving_programs_compile_under_their_names(model):
    before = obs.compiles()
    serve_one_session(*model)
    after = obs.compiles()
    for name in ("jit(prefill)", "jit(decode_step)"):
        assert after[name]["count"] > before.get(name, {"count": 0})["count"]
