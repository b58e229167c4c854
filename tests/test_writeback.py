"""Write-back / write-around / coordinated eviction (PR 3 tentpole).

Covers dirty-bit tracking, WriteBackQueue drain ordering, write-around
read-once semantics, and the coordinated-eviction sole-copy protection —
plus the simulator/executor plumbing that keeps the flush off the critical
path.
"""

import pytest

from repro.core import (HPC_CLUSTER, LocalityScheduler, ProactiveScheduler,
                        StorageHierarchy, TierSpec, WorkflowExecutor,
                        compile_workflow)
from repro.core.locstore import LocStore, Placement, REMOTE_TIER, SimObject
from repro.core.prefetch import PrefetchEngine
from repro.core.simulator import WorkflowSimulator
from repro.core.workloads import fig2_workflow, montage_workflow

GB = float(1 << 30)


def small_hierarchy(cap=100.0):
    return StorageHierarchy(
        [TierSpec("hbm", cap, 800e9),
         TierSpec("host", 2 * cap, 100e9),
         TierSpec("bb", 4 * cap, 8e9)],
        remote=TierSpec("remote", float("inf"), 2e9))


def pressure_hierarchy(cap):
    """A quarter of ``cap`` in HBM, ``cap`` of host, 16x in a burst buffer,
    over a 0.5 GB/s PFS."""
    return StorageHierarchy(
        [TierSpec("hbm", cap / 4, 819e9),
         TierSpec("host", cap, 100e9),
         TierSpec("bb", 16 * cap, 8e9)],
        remote=TierSpec("remote", float("inf"), 0.5e9))


def tiny_hierarchy(cap=100.0):
    """One node tier: eviction spills straight to the PFS."""
    return StorageHierarchy([TierSpec("hbm", cap, 800e9)],
                            remote=TierSpec("remote", float("inf"), 2e9))


class TestDirtyTracking:
    def test_fresh_put_is_dirty(self):
        st = LocStore(2, hierarchy=small_hierarchy())
        st.put("a", SimObject(10.0), loc=0)
        assert st.is_dirty("a")
        assert st.is_dirty("a", 0)
        assert not st.is_dirty("a", 1)          # no replica there

    def test_pfs_pinned_put_is_clean(self):
        st = LocStore(2, hierarchy=small_hierarchy())
        st.put("a", SimObject(10.0), loc=Placement((REMOTE_TIER,),
                                                   tier="remote"))
        assert not st.is_dirty("a")

    def test_synchronous_spill_cleans(self):
        """Write-through spill to the PFS makes the durable copy current."""
        st = LocStore(1, hierarchy=tiny_hierarchy(100))
        st.put("huge", SimObject(500.0), loc=0)   # fits nowhere: sync spill
        assert not st.is_dirty("huge")

    def test_drain_clears_dirty(self):
        st = LocStore(1, hierarchy=tiny_hierarchy(100), write_policy="back")
        st.put("a", SimObject(90.0), loc=0)
        st.put("b", SimObject(90.0), loc=0)       # a evicted -> queued flush
        assert st.is_dirty("a") and len(st.writeback) == 1
        assert st.stat("a").resident_on(REMOTE_TIER)   # logical move now
        drained = st.drain_writebacks()
        assert [e.name for e in drained] == ["a"]
        assert not st.is_dirty("a")

    def test_overwrite_redirties_and_cancels_stale_flush(self):
        st = LocStore(1, hierarchy=tiny_hierarchy(100), write_policy="back")
        st.put("a", SimObject(90.0), loc=0)
        st.put("b", SimObject(90.0), loc=0)       # queue flush of a-v1
        st.put("a", SimObject(50.0), loc=0)       # overwrite: v1 must not land
        assert st.writeback.cancelled == 1
        assert st.is_dirty("a")
        drained = st.drain_writebacks()
        assert "a" not in [e.name for e in drained]


class TestWriteBackQueue:
    def test_drain_is_fifo(self):
        st = LocStore(1, hierarchy=tiny_hierarchy(100), write_policy="back")
        for i in range(5):
            st.put(f"o{i}", SimObject(90.0), loc=0)   # evicts o0..o3 in order
        drained = st.drain_writebacks()
        assert [e.name for e in drained] == ["o0", "o1", "o2", "o3"]
        assert [e.seq for e in drained] == sorted(e.seq for e in drained)

    def test_partial_drain_respects_limit(self):
        st = LocStore(1, hierarchy=tiny_hierarchy(100), write_policy="back")
        for i in range(5):
            st.put(f"o{i}", SimObject(90.0), loc=0)
        first = st.drain_writebacks(max_entries=2)
        assert [e.name for e in first] == ["o0", "o1"]
        assert len(st.writeback) == 2
        rest = st.drain_writebacks()
        assert [e.name for e in rest] == ["o2", "o3"]

    def test_clean_eviction_is_free(self):
        """Once flushed, re-staged replicas evict with zero PFS traffic."""
        st = LocStore(2, hierarchy=tiny_hierarchy(100), write_policy="back")
        st.put("a", SimObject(90.0), loc=0)
        st.put("b", SimObject(90.0), loc=0)       # a -> writeback queue
        st.drain_writebacks()                     # a durable on PFS
        st.replicate("a", [0])                    # stage a back in (evicts b)
        before = st.remote_bytes
        st.put("c", SimObject(90.0), loc=0)       # evicts clean a: free
        assert st.clean_drops >= 1
        assert st.remote_bytes == before          # no second PFS write for a
        assert st.exists("a")

    @pytest.mark.parametrize("policy", ["through", "back"])
    def test_cyclic_reuse_flushes_each_object_once(self, policy):
        """A working set of 32 64-MiB objects on a node whose tiers hold
        about 60% of it, re-read and re-staged twice: each object crosses
        the PFS boundary at most once, and later evictions of PFS-backed
        replicas are free drops."""
        n, obj = 32, float(64 << 20)
        cap = n * obj / 2.0
        st = LocStore(1, hierarchy=StorageHierarchy(
            [TierSpec("hbm", cap / 4, 819e9),
             TierSpec("host", cap / 2, 100e9),
             TierSpec("bb", cap / 2, 8e9)],
            remote=TierSpec("remote", float("inf"), 0.5e9)),
            write_policy=policy)
        for i in range(n):
            st.put(f"o{i}", SimObject(obj), loc=0)
        for _ in range(2):
            st.drain_writebacks()
            for i in range(n):
                st.get(f"o{i}", at=0)
                st.replicate(f"o{i}", [0])
        st.drain_writebacks()
        rep = st.movement_report()
        assert rep["writebacks"] <= n, "an object was flushed more than once"
        assert rep["clean_drops"] > 0, "reuse rounds produced no free drops"

    def test_writeback_recorded_as_transfer_and_counted(self):
        st = LocStore(1, hierarchy=tiny_hierarchy(100), write_policy="back")
        st.put("a", SimObject(90.0), loc=0)
        st.put("b", SimObject(90.0), loc=0)
        (wb,) = [t for t in st.transfers if t.kind == "writeback"]
        assert wb.name == "a" and wb.dst == REMOTE_TIER
        assert wb.est_seconds > 0
        assert st.writeback_bytes == 90.0
        assert st.remote_bytes == 90.0            # the bytes will cross
        rep = st.movement_report()
        assert rep["writebacks"] == 1.0
        assert rep["writeback_pending"] == 1.0


class TestWriteAround:
    def test_put_streams_to_pfs_only(self):
        st = LocStore(2, hierarchy=small_hierarchy())
        p = st.put("stream", SimObject(50.0), loc=0, mode="around")
        assert p.nodes == (REMOTE_TIER,) and p.tiers == ("remote",)
        assert not st.is_dirty("stream")          # the PFS copy IS the copy
        assert st.remote_bytes == 50.0            # producer -> PFS write
        (t,) = [t for t in st.transfers if t.kind == "writearound"]
        assert t.src == 0 and t.dst == REMOTE_TIER

    def test_pfs_origin_put_counts_no_movement(self):
        st = LocStore(2, hierarchy=small_hierarchy())
        st.put("ext", SimObject(50.0), mode="around",
               loc=Placement((REMOTE_TIER,), tier="remote"))
        assert st.remote_bytes == 0.0

    def test_reads_are_never_cached(self):
        st = LocStore(2, hierarchy=small_hierarchy())
        st.put("stream", SimObject(50.0), loc=0, mode="around")
        for _ in range(2):                        # every read pays the PFS
            _, tr = st.get("stream", at=1)
            assert tr.src == REMOTE_TIER and not tr.local
        assert st.stat("stream").nodes == (REMOTE_TIER,)
        assert st.remote_bytes == 50.0 * 3        # 1 write + 2 reads

    def test_replicate_is_noop(self):
        st = LocStore(2, hierarchy=small_hierarchy())
        st.put("stream", SimObject(50.0), loc=0, mode="around")
        p = st.replicate("stream", [1])
        assert p.nodes == (REMOTE_TIER,)

    def test_prefetch_engine_skips_read_once(self):
        st = LocStore(2, hierarchy=small_hierarchy())
        st.put("stream", SimObject(50.0), loc=0, mode="around")
        eng = PrefetchEngine(st)
        eng.submit("stream", 1)
        eng.drain()
        assert eng.skipped_read_once == 1
        assert st.stat("stream").nodes == (REMOTE_TIER,)

    def test_streaming_outputs_do_not_evict_the_hot_set(self):
        """32 64-MiB streamed outputs written around a node whose host
        tier is full of a hot working set demote nothing."""
        n, obj = 32, float(64 << 20)
        st = LocStore(1, hierarchy=pressure_hierarchy(n * obj / 2.0))
        for i in range(n):
            st.put(f"hot{i}", SimObject(obj), loc=0, tier="host")
        st.reset_accounting()
        for i in range(n):
            st.put(f"stream{i}", SimObject(obj), loc=0, mode="around")
        assert st.movement_report()["demotions"] == 0

    def test_store_wide_around_rejected(self):
        with pytest.raises(ValueError):
            LocStore(1, write_policy="around")
        with pytest.raises(ValueError):
            LocStore(1).put("x", SimObject(1.0), mode="nonsense")

    def test_around_rejects_conflicting_pins(self):
        st = LocStore(2, hierarchy=small_hierarchy())
        with pytest.raises(ValueError):            # tier pin is contradictory
            st.put("s", SimObject(1.0), loc=0, tier="host", mode="around")
        with pytest.raises(ValueError):            # so is multi-node loc
            st.put("s", SimObject(1.0), loc=(0, 1), mode="around")


class TestCoordinatedEviction:
    def test_replicated_victim_dropped_before_sole_copy(self):
        st = LocStore(2, hierarchy=tiny_hierarchy(100),
                      coordinated_eviction=True)
        st.put("dup", SimObject(60.0), loc=(0, 1))
        st.put("sole", SimObject(30.0), loc=0)
        st.put("new", SimObject(60.0), loc=0)     # pressure on node 0
        # dup's node-0 replica dropped (free: node 1 still has it);
        # sole survives on node 0 (demoted at worst), never dropped
        assert st.stat("dup").nodes == (1,)
        assert st.exists("sole")
        assert (0 in st.stat("sole").nodes
                or st.stat("sole").resident_on(REMOTE_TIER))
        assert st.coord_drops == 1
        assert st.bytes_coord_dropped == 60.0
        assert st.coordination_violations == 0

    def test_drop_moves_no_bytes(self):
        st = LocStore(2, hierarchy=tiny_hierarchy(100),
                      coordinated_eviction=True)
        st.put("dup", SimObject(90.0), loc=(0, 1))
        before = st.movement_report()
        st.put("new", SimObject(90.0), loc=0)     # dup@0 dropped, not demoted
        after = st.movement_report()
        assert st.coord_drops == 1
        assert after["bytes_demoted"] == before["bytes_demoted"]
        assert after["remote_bytes"] == before["remote_bytes"]

    def test_sole_copies_are_demoted_not_dropped(self):
        """No dataset is ever lost: with only sole copies under pressure the
        coordinated policy degrades to plain demotion."""
        st = LocStore(1, hierarchy=small_hierarchy(100),
                      coordinated_eviction=True)
        for i in range(10):
            st.put(f"o{i}", SimObject(90.0), loc=0)
        assert all(st.exists(f"o{i}") for i in range(10))
        assert st.coord_drops == 0
        assert st.demotions > 0

    def test_prefers_victim_with_fast_duplicate(self):
        """Class 0 (duplicate in an equal-or-faster tier elsewhere) evicts
        before class 1 (only cold duplicates — the last fast-tier copy)."""
        st = LocStore(2, hierarchy=small_hierarchy(100),
                      coordinated_eviction=True, promote_on_access=False)
        # cold_dup: node-0 hbm copy + node-1 burst-buffer copy (cold)
        st.put("cold_dup", SimObject(40.0), loc=0)
        st.replicate("cold_dup", [1], tier="bb")
        # fast_dup: node-0 hbm copy + node-1 hbm copy (fast)
        st.put("fast_dup", SimObject(40.0), loc=(0, 1))
        st.get("fast_dup", at=0)    # make fast_dup the LRU-protected one...
        st.get("cold_dup", at=0)    # ...and cold_dup most-recently used
        st.put("new", SimObject(40.0), loc=0)     # evict one from node-0 hbm
        # plain LRU would evict fast_dup (older); coordination drops it too —
        # but only because it has a FAST duplicate; cold_dup (last fast copy,
        # fresher anyway) must still be in node-0 hbm
        assert st.stat("cold_dup").tier_on(0) == "hbm"
        assert st.stat("fast_dup").nodes == (1,)

    def test_last_fast_copy_dropped_only_when_no_alternative(self):
        """With ONLY class-1 candidates, the last fast-tier replica is
        dropped (free — the cold duplicate keeps the data safe), never
        demoted through the PFS."""
        st = LocStore(2, hierarchy=tiny_hierarchy(100),
                      coordinated_eviction=True)
        st.put("d", SimObject(90.0), loc=0)
        st.replicate("d", [REMOTE_TIER])          # cold duplicate on the PFS
        before = st.remote_bytes
        st.put("new", SimObject(90.0), loc=0)
        assert st.coord_drops == 1
        assert st.remote_bytes == before          # dropped, not re-written
        assert st.exists("d")


class TestDoNotEvictPins:
    """PR 4 satellite: prefetched replicas are pinned do-not-evict for their
    consumer's lifetime, so coordinated eviction at comfortable capacity
    cannot undo prefetch work (a regression once seen on montage at 1 GiB,
    which ``TestSimulatorPlumbing.test_policy_sweep`` covers)."""

    def test_pinned_replica_survives_eviction_pressure(self):
        st = LocStore(2, hierarchy=tiny_hierarchy(100),
                      coordinated_eviction=True)
        st.put("dup", SimObject(60.0), loc=(0, 1))   # prefetched duplicate
        st.pin("dup", 0)
        st.put("new", SimObject(60.0), loc=0)        # pressure on node 0
        # without the pin this is exactly test_replicated_victim_dropped_...:
        # dup@0 would be the coordinated-eviction victim. Pinned, it stays.
        assert st.stat("dup").resident_on(0)
        assert st.coord_drops == 0
        assert st.pin_protected_evictions > 0
        st.unpin("dup", 0)
        st.put("more", SimObject(60.0), loc=0)       # unpinned: fair game
        assert not st.stat("dup").resident_on(0)
        assert st.coord_drops >= 1

    def test_pin_refcounting(self):
        st = LocStore(1, hierarchy=tiny_hierarchy(100))
        st.put("a", SimObject(10.0), loc=0)
        st.pin("a", 0)
        st.pin("a", 0)
        st.unpin("a", 0)
        assert st.is_pinned("a", 0)                  # one pin still held
        st.unpin("a", 0)
        assert not st.is_pinned("a", 0)
        st.unpin("a", 0)                             # over-unpin is harmless
        assert not st.is_pinned("a", 0)

    def test_delete_clears_pins(self):
        st = LocStore(1, hierarchy=tiny_hierarchy(100))
        st.put("a", SimObject(10.0), loc=0)
        st.pin("a", 0)
        st.delete("a")
        assert not st.is_pinned("a", 0)

    def test_fully_pinned_tier_runs_overfull_never_drops(self):
        st = LocStore(1, hierarchy=tiny_hierarchy(100),
                      coordinated_eviction=True)
        st.put("a", SimObject(60.0), loc=0)
        st.pin("a", 0)
        st.put("b", SimObject(60.0), loc=0)          # no victim available
        st.pin("b", 0)
        assert st.stat("a").tier_on(0) == "hbm"
        assert st.stat("b").tier_on(0) == "hbm"      # overfull, not dropped

    def test_prefetch_engine_pins_until_release(self):
        st = LocStore(2, hierarchy=small_hierarchy(100))
        st.put("x", SimObject(10.0), loc=0)
        eng = PrefetchEngine(st)
        eng.submit("x", 1, tier="hbm", pin_for="consumer_task")
        eng.drain()
        assert st.is_pinned("x", 1)
        assert eng.report()["pins_held"] == 1
        assert eng.release("consumer_task") == 1
        assert not st.is_pinned("x", 1)
        assert eng.release("consumer_task") == 0     # idempotent
        eng.shutdown()

    def test_prefetch_tier_upgrade_resubmits(self):
        """A later request for a FASTER tier must not be swallowed by the
        (name, dst) idempotence — a bb-staged session cache still needs its
        HBM warm-up."""
        st = LocStore(2, hierarchy=small_hierarchy(100))
        st.put("x", SimObject(10.0), loc=0)
        eng = PrefetchEngine(st)
        eng.submit("x", 1, tier="bb")
        eng.drain()
        assert st.stat("x").tier_on(1) == "bb"
        eng.submit("x", 1, tier="hbm")
        eng.drain()
        assert st.stat("x").tier_on(1) == "hbm"
        assert eng.submitted == 2
        eng.shutdown()

    def test_sim_releases_all_pins_by_end_of_run(self):
        wf = compile_workflow(montage_workflow(16), HPC_CLUSTER)
        sim = WorkflowSimulator(wf, ProactiveScheduler(wf), n_nodes=4,
                                hw=HPC_CLUSTER,
                                hierarchy=StorageHierarchy(
                                    [TierSpec("hbm", 0.25 * GB / 4, 819e9),
                                     TierSpec("host", 0.25 * GB, 100e9),
                                     TierSpec("bb", 4 * GB, 8e9)],
                                    remote=TierSpec("remote", float("inf"),
                                                    0.5e9)),
                                write_policy="back",
                                coordinated_eviction=True)
        r = sim.run()
        assert r.tasks_done == len(wf.graph.tasks)
        rep = sim.store.movement_report()
        assert rep["pins"] == 0        # every prefetch pin was released


class TestSimulatorPlumbing:
    @pytest.mark.parametrize("width,cap_gb", [(16, 0.125), (16, 0.25),
                                              (24, 1.0)])
    def test_policy_sweep(self, width, cap_gb):
        """Montage under write-through, write-back and write-back with
        coordinated eviction. At every point: no sole copy dropped, every
        dataset resolvable, every task done, every prefetch pin released.
        At the tight points (up to 0.25 GiB) write-back flushes and cuts
        io-wait; at comfortable capacity (1 GiB) the pins shield
        prefetched replicas from coordinated eviction."""
        wf = compile_workflow(montage_workflow(width), HPC_CLUSTER)
        results = {}
        for label, kw in (("through", {}),
                          ("back", {"write_policy": "back"}),
                          ("back_coord", {"write_policy": "back",
                                          "coordinated_eviction": True})):
            sim = WorkflowSimulator(wf, ProactiveScheduler(wf), n_nodes=4,
                                    hw=HPC_CLUSTER,
                                    hierarchy=pressure_hierarchy(cap_gb * GB), **kw)
            r = results[label] = sim.run()
            assert sim.store.coordination_violations == 0
            assert r.tasks_done == len(wf.graph.tasks)
            for name in sim.store.loc.names():
                assert sim.store.exists(name)
            assert sim.store.movement_report()["pins"] == 0
        if cap_gb <= 0.25:
            assert results["back"].writebacks > 0
            assert (results["back"].io_wait_total
                    < results["through"].io_wait_total)
        if cap_gb >= 1.0:
            assert results["back_coord"].pin_protected_evictions > 0

    def test_writeback_reduces_io_wait_under_pressure(self):
        wf = compile_workflow(montage_workflow(16), HPC_CLUSTER)
        hier = pressure_hierarchy(0.125 * GB)
        r_thru = WorkflowSimulator(wf, LocalityScheduler(wf), n_nodes=4,
                                   hw=HPC_CLUSTER, hierarchy=hier).run()
        r_back = WorkflowSimulator(wf, LocalityScheduler(wf), n_nodes=4,
                                   hw=HPC_CLUSTER, hierarchy=pressure_hierarchy(0.125 * GB),
                                   write_policy="back").run()
        assert r_back.writebacks > 0
        assert r_back.io_wait_total < r_thru.io_wait_total
        assert r_back.tasks_done == r_thru.tasks_done == len(wf.graph.tasks)

    def test_coordinated_eviction_sim_never_loses_data(self):
        wf = compile_workflow(montage_workflow(16), HPC_CLUSTER)
        sim = WorkflowSimulator(wf, ProactiveScheduler(wf), n_nodes=4,
                                hw=HPC_CLUSTER, hierarchy=pressure_hierarchy(0.25 * GB),
                                write_policy="back", coordinated_eviction=True)
        r = sim.run()
        assert r.tasks_done == len(wf.graph.tasks)
        assert r.coord_drops > 0                  # coordination actually fired
        assert sim.store.coordination_violations == 0

    def test_queue_drained_by_end_of_run(self):
        wf = compile_workflow(montage_workflow(12), HPC_CLUSTER)
        sim = WorkflowSimulator(wf, LocalityScheduler(wf), n_nodes=4,
                                hw=HPC_CLUSTER, hierarchy=pressure_hierarchy(0.125 * GB),
                                write_policy="back")
        sim.run()
        assert len(sim.store.writeback) == 0
        assert not any(sim.store.is_dirty(n) for n in sim.store.loc.names()
                       if sim.store.stat(n).resident_on(REMOTE_TIER))


class TestExecutorPlumbing:
    def test_executor_drains_writebacks_off_critical_path(self):
        wf = compile_workflow(fig2_workflow(256.0), HPC_CLUSTER)

        def body(tid):
            def fn(**inputs):
                t = wf.graph.tasks[tid]
                return {o: SimObject(wf.sizes[o]) for o in t.outputs}
            return fn
        for tid in wf.graph.tasks:
            wf.graph.tasks[tid].fn = body(tid)
        ex = WorkflowExecutor(wf, LocalityScheduler(wf), n_nodes=2,
                              hierarchy=StorageHierarchy(
                                  [TierSpec("hbm", 96.0, 800e9)],
                                  remote=TierSpec("remote", float("inf"), 2e9)),
                              write_policy="back",
                              inject_inputs={"raw": SimObject(256.0)})
        res = ex.run()
        assert set(res.outputs) == {"result"}
        assert len(ex.store.writeback) == 0       # drainer flushed everything
        assert res.writebacks > 0

    def test_executor_rejects_store_plus_policy(self):
        wf = compile_workflow(fig2_workflow(), HPC_CLUSTER)
        with pytest.raises(ValueError):
            WorkflowExecutor(wf, LocalityScheduler(wf), n_nodes=2,
                             store=LocStore(2), write_policy="back")


class TestTierPinning:
    """The compiler->scheduler loop: est_stage_seconds picks prefetch tiers."""

    def test_hot_input_pinned_to_top_tier(self):
        # compute-heavy tasks: staging is hideable -> hbm
        wf = compile_workflow(fig2_workflow(4 * GB, flops_per_byte=200000.0),
                              HPC_CLUSTER)
        s = ProactiveScheduler(wf)
        assert s._pin_tier("raw", "split", _TieredView()) == "hbm"

    def test_bulk_input_pinned_to_burst_buffer(self):
        # I/O-dominated tasks: staging dwarfs compute -> bb
        wf = compile_workflow(fig2_workflow(4 * GB, flops_per_byte=1.0),
                              HPC_CLUSTER)
        s = ProactiveScheduler(wf)
        assert wf.est_stage_seconds["split"] > wf.est_seconds["split"]
        assert s._pin_tier("raw", "split", _TieredView()) == "bb"

    def test_explicit_tier_still_pins_everything(self):
        wf = compile_workflow(fig2_workflow(4 * GB, flops_per_byte=1.0),
                              HPC_CLUSTER)
        s = ProactiveScheduler(wf, prefetch_tier="hbm")
        assert s._pin_tier("raw", "split", _TieredView()) == "hbm"

    def test_preplace_emits_pinned_requests(self):
        wf = compile_workflow(fig2_workflow(4 * GB, flops_per_byte=1.0),
                              HPC_CLUSTER)
        s = ProactiveScheduler(wf)
        # raw lives on busy node 2: whichever free node wins needs a prefetch
        view = _TieredView(free=[0, 1],
                           loc={"raw": Placement((2,), tier="hbm",
                                                 tiers=("hbm",))})
        reqs = s.preplace(["split"], view, {})
        assert reqs and all(r.tier == "bb" for r in reqs
                            if r.data_name == "raw")

    def test_compiler_exposes_per_dataset_stage_seconds(self):
        wf = compile_workflow(fig2_workflow(4 * GB), HPC_CLUSTER)
        assert wf.stage_seconds["raw"] == pytest.approx(
            wf.est_stage_seconds["split"])
        assert "part_a" not in wf.stage_seconds    # internal datasets excluded


class _TieredView:
    def __init__(self, free=(0,), loc=None):
        self._free, self._loc = list(free), dict(loc or {})

    def free_workers(self):
        return list(self._free)

    def locate(self, name):
        return self._loc.get(name)

    def link_gbps(self, src, dst):
        return float("inf") if src == dst else 10e9

    def tier_gbps(self, tier):
        return {"hbm": 800e9, "host": 100e9, "bb": 8e9,
                "remote": 2e9}.get(tier, float("inf"))

    def top_tier(self):
        return "hbm"

    def bulk_tier(self):
        return "bb"

    def worker_speed(self, node):
        return 1.0
