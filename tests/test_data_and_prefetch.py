"""Data pipeline (determinism + prefetch) and the PrefetchEngine data plane."""

import time

import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.locstore import LocStore, SimObject
from repro.core.prefetch import PrefetchEngine
from repro.data.pipeline import (PrefetchingLoader, SyntheticCorpus,
                                 epoch_workflow)
from repro.core import compile_workflow, ProactiveScheduler, simulate, HPC_CLUSTER


class TestCorpus:
    def test_deterministic_across_instances(self):
        c1 = SyntheticCorpus(1000, seed=5)
        c2 = SyntheticCorpus(1000, seed=5)
        np.testing.assert_array_equal(c1.shard(3), c2.shard(3))

    def test_restart_resumes_exact_batches(self):
        c = SyntheticCorpus(1000, seed=1)
        full = [b for _, b in zip(range(8), c.batches(2, 16))]
        resumed = [b for _, b in zip(range(3), c.batches(2, 16, start_step=5))]
        for a, b in zip(full[5:], resumed):
            np.testing.assert_array_equal(a["tokens"], b["tokens"])

    def test_labels_are_next_tokens(self):
        c = SyntheticCorpus(1000)
        b = next(c.batches(2, 16))
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


class TestPrefetchingLoader:
    def test_yields_all_and_counts_waits(self):
        def slow_gen():
            for i in range(5):
                yield {"x": np.full((2,), i)}

        loader = PrefetchingLoader(slow_gen(), depth=2)
        got = [np.asarray(b["x"])[0] for b in loader]
        assert got == [0, 1, 2, 3, 4]

    def test_prefetch_hides_producer_latency(self):
        def gen(delay):
            for i in range(6):
                time.sleep(delay)
                yield {"x": np.zeros(1)}

        t0 = time.perf_counter()
        loader = PrefetchingLoader(gen(0.05), depth=3)
        for _ in loader:
            time.sleep(0.05)      # consumer work overlaps producer
        overlapped = time.perf_counter() - t0
        assert overlapped < 2 * 6 * 0.05 + 0.2   # far below serial 0.6s


class TestPrefetchEngine:
    def test_stage_creates_replica(self):
        store = LocStore(4)
        store.put("d", SimObject(100), loc=0)
        eng = PrefetchEngine(store)
        eng.submit("d", 3)
        eng.drain()
        assert store.stat("d").resident_on(3)
        _, t = store.get("d", at=3)
        assert t.local

    def test_idempotent_submit(self):
        store = LocStore(4)
        store.put("d", SimObject(10), loc=0)
        eng = PrefetchEngine(store)
        f1 = eng.submit("d", 2)
        f2 = eng.submit("d", 2)
        assert f1 is f2
        eng.drain()
        assert eng.submitted == 1

    def test_wait_returns_false_without_submit(self):
        store = LocStore(2)
        store.put("d", SimObject(1), loc=0)
        assert PrefetchEngine(store).wait("d", 1) is False

    def test_hbm_stage_copies_to_device(self):
        import jax
        store = LocStore(2)
        store.put("d", np.arange(8, dtype=np.float32), loc=0)
        dev = jax.devices()[0]
        eng = PrefetchEngine(store, device_of=lambda node: dev)
        eng.submit("d", 1, tier="hbm")
        eng.drain()
        copy = eng.device_copy("d", 1)
        assert isinstance(copy, jax.Array) and copy.devices() == {dev}
        assert eng.report()["device_puts"] == 1

    def test_failed_device_put_raises(self):
        """A device copy that fails must surface, not pass as a host replica."""
        store = LocStore(2)
        store.put("d", np.arange(8, dtype=np.float32), loc=0)
        eng = PrefetchEngine(store, device_of=lambda node: "no-such-device")
        eng.submit("d", 1, tier="hbm")
        with pytest.raises(ValueError, match="device_put"):
            eng.drain()
        assert eng.device_copy("d", 1) is None
        assert eng.report()["device_puts"] == 0
        assert not store.stat("d").resident_on(1)


def test_epoch_workflow_schedules_with_locality():
    """The training-epoch DAG built from a real config runs in the simulator
    and the proactive scheduler pipelines batches (paper's claim, applied to
    the framework's own input pipeline)."""
    cfg = get_smoke("granite-3-2b")
    g = epoch_workflow(cfg, n_steps=6, n_dp=4, batch=8, seq=64,
                       step_flops=5e11)
    wf = compile_workflow(g, HPC_CLUSTER)
    r = simulate(wf, ProactiveScheduler, n_nodes=4, hw=HPC_CLUSTER)
    assert r.tasks_done == len(g.tasks)
    assert r.bytes_prefetched > 0          # batches were pipelined
