"""Tier machinery: eviction/demotion under capacity pressure, promote-on-
access, per-tier Transfer accounting, tier-aware scheduling decisions, and
analyzer-proven write-around under the runtime sanitizer."""

import pytest

from repro.core import (HPC_CLUSTER, LocalityScheduler, ProactiveScheduler,
                        SimConfig, StorageHierarchy, TierSpec,
                        WorkflowSimulator, compile_workflow, simulate,
                        tiered_hierarchy)
from repro.core.locstore import LocStore, Placement, REMOTE_TIER, SimObject
from repro.core.prefetch import PrefetchEngine
from repro.core.workloads import (fig2_workflow, montage_workflow,
                                  pipeline_chain_workflow)

GB = float(1 << 30)


def small_hierarchy(cap=100.0):
    return StorageHierarchy(
        [TierSpec("hbm", cap, 800e9),
         TierSpec("host", 2 * cap, 100e9),
         TierSpec("bb", 4 * cap, 8e9)],
        remote=TierSpec("remote", float("inf"), 2e9))


class TestEvictionDemotion:
    def test_capacity_pressure_demotes_not_drops(self):
        st = LocStore(2, hierarchy=small_hierarchy(100))
        for i in range(10):                 # 900 bytes into 700 of node tiers
            st.put(f"o{i}", SimObject(90.0), loc=0)
        # nothing is ever dropped: every object still resolvable
        assert all(st.exists(f"o{i}") for i in range(10))
        assert st.demotions > 0 and st.bytes_demoted > 0
        # the freshest object sits in the top tier, the coldest spilled to PFS
        assert st.stat("o9").tier_on(0) == "hbm"
        assert st.stat("o0").resident_on(REMOTE_TIER)
        # per-node tier usage never exceeds capacity
        rep = st.tier_report()
        assert rep["hbm"]["resident_bytes"] <= 100
        assert rep["host"]["resident_bytes"] <= 200
        assert rep["bb"]["resident_bytes"] <= 400

    def test_demotions_recorded_as_transfers(self):
        st = LocStore(1, hierarchy=small_hierarchy(100))
        st.put("a", SimObject(90.0), loc=0)
        st.put("b", SimObject(90.0), loc=0)   # evicts a: hbm -> host
        demotes = [t for t in st.transfers if t.kind == "demote"]
        assert demotes and demotes[0].name == "a"
        assert demotes[0].src_tier == "hbm" and demotes[0].dst_tier == "host"
        assert demotes[0].est_seconds > 0     # media time is charged

    def test_spill_to_remote_counts_network_bytes(self):
        st = LocStore(1, hierarchy=small_hierarchy(10))
        for i in range(20):
            st.put(f"o{i}", SimObject(9.0), loc=0)
        assert st.remote_bytes > 0
        assert any(t.kind == "demote" and t.dst == REMOTE_TIER
                   for t in st.transfers)

    def test_oversized_object_cascades_past_small_tiers(self):
        st = LocStore(1, hierarchy=small_hierarchy(100))
        p = st.put("big", SimObject(350.0), loc=0)   # only bb (400) fits it
        assert p.tier_on(0) == "bb"

    def test_skip_tier_cascade_still_counts_remote_spill(self):
        """A victim that outsizes the next tier down spills to the PFS — and
        that crossing must show up in remote_bytes and the Transfer dst."""
        h = StorageHierarchy([TierSpec("host", 200, 100e9),
                              TierSpec("bb", 100, 8e9)],
                             remote=TierSpec("remote", float("inf"), 2e9))
        st = LocStore(1, hierarchy=h)
        st.put("a", SimObject(150.0), loc=0)
        st.put("b", SimObject(150.0), loc=0)   # a: host -> (bb too small) -> PFS
        assert st.stat("a").resident_on(REMOTE_TIER)
        assert st.remote_bytes == 150.0 and st.bytes_moved == 150.0
        (d,) = [t for t in st.transfers if t.kind == "demote"]
        assert d.dst == REMOTE_TIER and d.dst_tier == "remote"

    def test_put_oversized_everywhere_counts_remote_spill(self):
        st = LocStore(1, hierarchy=small_hierarchy(100))
        st.put("huge", SimObject(500.0), loc=0)   # fits no node tier
        assert st.stat("huge").resident_on(REMOTE_TIER)
        assert st.remote_bytes == 500.0
        # but pinning data ON the PFS is its origin, not a movement
        st2 = LocStore(1, hierarchy=small_hierarchy(100))
        st2.put("ext", SimObject(500.0),
                loc=Placement((REMOTE_TIER,), tier="remote"))
        assert st2.remote_bytes == 0.0

    def test_cost_aware_eviction_prefers_large_cold(self):
        h = StorageHierarchy([TierSpec("hbm", 100, 800e9)],
                             remote=TierSpec("remote", float("inf"), 2e9))
        st = LocStore(1, hierarchy=h, eviction_policy="cost",
                      promote_on_access=False)
        st.put("large", SimObject(60.0), loc=0)
        st.put("small", SimObject(10.0), loc=0)
        st.get("large", at=0)                  # large is now the most recent
        st.put("new", SimObject(60.0), loc=0)  # must evict something
        # plain LRU would evict "small" (oldest); cost-aware picks the big one
        assert st.stat("large").resident_on(REMOTE_TIER)
        assert st.stat("small").tier_on(0) == "hbm"


class TestReplicaLifecycle:
    def test_migrate_normalizes_foreign_tier_names(self):
        """A Placement whose tier name isn't in this hierarchy (legacy 'host'
        against an hbm-only store) must land on the node's top tier, not get
        silently stranded on the PFS."""
        h = StorageHierarchy([TierSpec("hbm", 1000.0, 800e9)],
                             remote=TierSpec("remote", float("inf"), 2e9))
        st = LocStore(2, hierarchy=h)
        st.put("x", SimObject(10.0), loc=0)
        st.migrate("x", Placement(nodes=(1,)))      # default tier "host"
        assert st.stat("x").real_loc == 1
        assert st.stat("x").tier_on(1) == "hbm"

    def test_forget_last_replica_deletes_object(self):
        st = LocStore(2)
        st.put("x", SimObject(10.0), loc=0)
        st.forget_replica("x", 0)
        assert not st.exists("x")
        # with a surviving replica the object stays resolvable
        st.put("y", SimObject(10.0), loc=(0, 1))
        st.forget_replica("y", 0)
        assert st.exists("y") and st.stat("y").nodes == (1,)


class TestPromoteOnAccess:
    def test_get_promotes_to_top_tier(self):
        st = LocStore(1, hierarchy=small_hierarchy(100))
        st.put("a", SimObject(90.0), loc=0)
        st.put("b", SimObject(90.0), loc=0)    # a demoted to host
        assert st.stat("a").tier_on(0) == "host"
        _, tr = st.get("a", at=0)
        assert tr.local
        assert st.stat("a").tier_on(0) == "hbm"
        assert st.promotions >= 1
        # promotion shows up in the hop log: host read, then hbm landing
        assert tr.hops[0].src_tier == "host"
        assert tr.hops[-1].dst_tier == "hbm"

    def test_promote_disabled_leaves_tier(self):
        st = LocStore(1, hierarchy=small_hierarchy(100),
                      promote_on_access=False)
        st.put("a", SimObject(90.0), loc=0)
        st.put("b", SimObject(90.0), loc=0)
        st.get("a", at=0)
        assert st.stat("a").tier_on(0) == "host"
        assert st.promotions == 0

    def test_prefetch_engine_targets_tier(self):
        st = LocStore(4, hierarchy=small_hierarchy(100))
        st.put("d", SimObject(50.0), loc=0)
        eng = PrefetchEngine(st)
        eng.submit("d", 3, tier="bb")
        eng.drain()
        assert st.stat("d").tier_on(3) == "bb"
        # device prefetch (default) promotes into hbm
        eng2 = PrefetchEngine(st)
        eng2.submit("d", 2)
        eng2.drain()
        assert st.stat("d").tier_on(2) == "hbm"

    def test_explicit_promote_api(self):
        st = LocStore(2, hierarchy=small_hierarchy(100))
        st.put("a", SimObject(50.0), loc=0, tier="bb")
        assert st.stat("a").tier_on(0) == "bb"
        st.promote("a", 0)
        assert st.stat("a").tier_on(0) == "hbm"
        assert st.promotions == 1
        # pinning DOWN-tier is allowed but is not a promotion
        st.promote("a", 0, tier="bb")
        assert st.stat("a").tier_on(0) == "bb"
        assert st.promotions == 1
        # promote cannot conjure a replica on a node that has none
        with pytest.raises(KeyError):
            st.promote("a", 1)

    def test_promotion_hops_belong_to_the_read_object(self):
        """Victim demotions triggered by a promotion are their own demote
        transfers — the fetch Transfer's hops only describe the read object."""
        st = LocStore(1, hierarchy=small_hierarchy(100))
        st.put("a", SimObject(90.0), loc=0)
        st.put("b", SimObject(80.0), loc=0)    # a demoted to host, b in hbm
        _, tr = st.get("a", at=0)              # promoting a evicts b
        assert tr.name == "a"
        assert all(h.nbytes == 90.0 for h in tr.hops)   # never b's 80 bytes
        assert any(t.name == "b" and t.kind == "demote"
                   and t.src_tier == "hbm" for t in st.transfers)


class TestTransferAccounting:
    def test_local_hit_charges_resident_tier_media_time(self):
        st = LocStore(1, hierarchy=small_hierarchy(100),
                      promote_on_access=False)
        st.put("a", SimObject(80.0), loc=0, tier="bb")
        _, tr = st.get("a", at=0)
        assert tr.local and tr.src_tier == "bb"
        assert tr.est_seconds == pytest.approx(80.0 / 8e9)

    def test_network_fetch_records_tier_path(self):
        st = LocStore(2, hierarchy=small_hierarchy(100))
        st.put("a", SimObject(64.0), loc=0, tier="bb")
        _, tr = st.get("a", at=1)
        assert not tr.local
        assert tr.src_tier == "bb" and tr.dst_tier == "hbm"
        # read-from-bb + write-to-hbm media time
        assert tr.est_seconds == pytest.approx(64.0 / 8e9 + 64.0 / 800e9)
        assert len(tr.hops) == 1 and tr.hops[0].nbytes == 64.0

    def test_per_tier_read_bytes(self):
        st = LocStore(1, hierarchy=small_hierarchy(100),
                      promote_on_access=False)
        st.put("a", SimObject(30.0), loc=0, tier="host")
        st.put("b", SimObject(30.0), loc=0, tier="bb")
        st.get("a", at=0)
        st.get("b", at=0)
        rep = st.tier_report()
        assert rep["host"]["bytes_read"] == 30.0
        assert rep["bb"]["bytes_read"] == 30.0

    def test_flat_hierarchy_keeps_original_accounting(self):
        st = LocStore(4)                       # default: flat two-tier
        st.put("a", SimObject(1000.0), loc=2)
        _, tl = st.get("a", at=2)
        _, tf = st.get("a", at=0)
        assert tl.est_seconds == 0.0           # flat media is free
        assert st.demotions == 0 and st.promotions == 0
        rep = st.movement_report()
        assert rep["bytes_local"] == 1000.0 and rep["bytes_moved"] == 1000.0


class FakeTieredCluster:
    """ClusterView exposing per-tier media bandwidths."""

    def __init__(self, free, locations, tier_bw):
        self._free, self._loc, self._bw = free, locations, tier_bw

    def free_workers(self):
        return list(self._free)

    def locate(self, name):
        return self._loc.get(name)

    def link_gbps(self, src, dst):
        return float("inf") if src == dst else 10e9

    def tier_gbps(self, tier):
        return self._bw.get(tier, float("inf"))

    def worker_speed(self, node):
        return 1.0


class TestTierAwareScheduling:
    def test_tier_changes_placement_decision(self):
        """A replica parked in a crawling burst buffer on node 0 loses to the
        HBM replica on node 1 — the flat model can't tell them apart."""
        wf = compile_workflow(fig2_workflow(), HPC_CLUSTER)
        bw = {"hbm": 800e9, "host": 100e9, "bb": 0.1e9}
        raw_sz = wf.sizes["raw"]
        loc = {"raw": Placement(nodes=(0, 1), tier="bb", tiers=("bb", "hbm"))}
        s = LocalityScheduler(wf)
        tiered = FakeTieredCluster([0, 1], loc, bw)
        (a_tiered,) = s.select(["split"], tiered)
        # flat view of the SAME placement: no tier info -> both replicas look
        # free and the first free node wins
        flat = FakeTieredCluster([0, 1], loc, bw)
        flat.tier_gbps = None                  # view exposes no hierarchy
        s2 = LocalityScheduler(wf)
        (a_flat,) = s2.select(["split"], flat)
        assert a_flat.node == 0                # resident replica looks free
        assert a_tiered.node == 1              # tier-aware: HBM replica wins
        assert a_tiered.move_seconds == pytest.approx(raw_sz / 800e9)

    def test_move_seconds_charges_source_tier(self):
        wf = compile_workflow(fig2_workflow(), HPC_CLUSTER)
        s = LocalityScheduler(wf)
        bw = {"bb": 1e9}
        loc = {"raw": Placement(nodes=(3,), tier="bb", tiers=("bb",))}
        cl = FakeTieredCluster([0], loc, bw)
        raw_sz = wf.sizes["raw"]
        got = s.move_seconds("split", 0, cl)
        assert got == pytest.approx(raw_sz / 10e9 + raw_sz / 1e9)


class TestSimulatorUnderPressure:
    def _hiers(self, cap):
        flat = StorageHierarchy([TierSpec("host", cap, 100e9)],
                                remote=TierSpec("remote", float("inf"), 0.5e9))
        tiered = StorageHierarchy(
            [TierSpec("hbm", cap / 4, 819e9),
             TierSpec("host", cap, 100e9),
             TierSpec("bb", 16 * cap, 8e9)],
            remote=TierSpec("remote", float("inf"), 0.5e9))
        return flat, tiered

    def test_tiered_moves_fewer_remote_bytes_than_flat(self):
        """The acceptance claim: under capacity pressure the hierarchy keeps
        spilled data node-local, so re-reads skip the PFS."""
        wf = compile_workflow(montage_workflow(16), HPC_CLUSTER)
        flat, tiered = self._hiers(0.5 * GB)
        rf = simulate(wf, LocalityScheduler, n_nodes=4, hw=HPC_CLUSTER,
                      hierarchy=flat)
        rt = simulate(wf, LocalityScheduler, n_nodes=4, hw=HPC_CLUSTER,
                      hierarchy=tiered)
        assert rf.tasks_done == rt.tasks_done == len(wf.graph.tasks)
        assert rt.demotions > 0                 # pressure actually happened
        assert rt.remote_bytes < rf.remote_bytes
        assert rt.io_wait_total < rf.io_wait_total

    @pytest.mark.parametrize("cap_gb", [0.5, 2.0])
    def test_sweep_under_sanitizer(self, cap_gb):
        """The montage capacity sweep, flat and tiered, with every
        incremental structure cross-checked against a rebuild."""
        wf = compile_workflow(montage_workflow(16), HPC_CLUSTER)
        for hier in self._hiers(cap_gb * GB):
            r = WorkflowSimulator(wf, LocalityScheduler(wf), config=SimConfig(
                n_nodes=4, hw=HPC_CLUSTER, hierarchy=hier,
                sanitize=True)).run()
            assert r.tasks_done == len(wf.graph.tasks)

    @pytest.mark.parametrize("cap_gb", [0.5, 2.0])
    def test_proven_write_around_cuts_eviction_pressure(self, cap_gb):
        """pipeline_chain intermediates have one consumer each, so the
        linter proves their mode="around" pins safe and the default
        honor_write_modes="auto" streams them to the PFS: eviction pressure
        is no higher than with the pins ignored (sanitizer on)."""
        wf = compile_workflow(pipeline_chain_workflow(4, 4), HPC_CLUSTER)
        _, hier = self._hiers(cap_gb * GB)

        def run(**kw):
            sim = WorkflowSimulator(wf, LocalityScheduler(wf),
                                    config=SimConfig(n_nodes=4,
                                                     hw=HPC_CLUSTER,
                                                     hierarchy=hier,
                                                     sanitize=True, **kw))
            return sim, sim.run()
        _, r_off = run(honor_write_modes=False)
        sim_on, r_on = run()
        assert sum(t.nbytes for t in sim_on.store.transfers
                   if t.kind == "writearound") > 0
        assert r_on.demotions <= r_off.demotions

    def test_default_flat_sim_unchanged(self):
        """No hierarchy argument -> the original two-tier cost model."""
        wf = compile_workflow(fig2_workflow(), HPC_CLUSTER)
        r = simulate(wf, ProactiveScheduler, n_nodes=4, hw=HPC_CLUSTER)
        assert r.tasks_done == len(wf.graph.tasks)
        assert r.demotions == 0 and r.bytes_demoted == 0.0

    def test_executor_rejects_store_plus_hierarchy(self):
        from repro.core import LocalityScheduler as LS, WorkflowExecutor
        wf = compile_workflow(fig2_workflow(), HPC_CLUSTER)
        with pytest.raises(ValueError):
            WorkflowExecutor(wf, LS(wf), n_nodes=2,
                             store=LocStore(2),
                             hierarchy=tiered_hierarchy())

    def test_failure_handling_with_hierarchy(self):
        wf = compile_workflow(montage_workflow(12), HPC_CLUSTER)
        _, tiered = self._hiers(1 * GB)
        r = simulate(wf, ProactiveScheduler, n_nodes=8, hw=HPC_CLUSTER,
                     hierarchy=tiered, failures=[(1.0, 0)])
        assert r.tasks_done == len(wf.graph.tasks)


class TestCompilerTierModel:
    def test_est_stage_seconds_present_and_tiered(self):
        wf = compile_workflow(fig2_workflow(), HPC_CLUSTER)
        # split reads the external "raw" -> staging cost from the PFS
        assert wf.est_stage_seconds["split"] > 0
        assert wf.est_stage_seconds["merge"] == 0.0   # internal inputs only
        expect = wf.hw.move_seconds_tiered(wf.sizes["raw"], REMOTE_TIER, 0,
                                           "remote", "hbm")
        assert wf.est_stage_seconds["split"] == pytest.approx(expect)

    def test_hardware_model_tier_bw_overrides(self):
        hw = HPC_CLUSTER
        assert hw.tier_bw("host") == float("inf")     # flat default: free
        assert hw.tier_bw("remote") == hw.remote_tier_gbps
        hw2 = type(hw)(tier_gbps={"bb": 5e9})
        assert hw2.tier_bw("bb") == 5e9

    def test_default_hierarchy_factory(self):
        h = tiered_hierarchy()
        assert h.names() == ("hbm", "host", "bb", "remote")
        assert h.top == "hbm"
        assert h.next_down("bb") is None       # below bb lies the PFS
