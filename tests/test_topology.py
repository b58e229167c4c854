"""Topology-aware cluster model (PR 10 tentpole).

Four layers of proof: the link-graph math itself (paths, min-capacity
bandwidth, growth fallback); the cross-layer behavior under a real two-tier
fabric (rack-spread placement, per-link contention charging, aware-vs-blind
scheduling, predictive re-replication ahead of a flagged failure); the lint
rules that audit a topology before a run (trigger + clean pair each); and
the sanitizer checks that catch an injected desync in the topology-derived
caches, naming the first divergent entry. Flat-topology bit-equivalence
lives in tests/test_sched_equivalence.py.
"""

import pytest

from repro.analysis import sanitize
from repro.analysis.lint import lint
from repro.analysis.sanitize import SanitizerError
from repro.core import (ClusterTopology, HPC_CLUSTER, LocalityScheduler,
                        NodeProfile, ProactiveScheduler, SimConfig,
                        StorageHierarchy, TierSpec, WorkflowSimulator,
                        compile_workflow)
from repro.core.locstore import LocStore, SimObject
from repro.core.workloads import mapreduce_workflow, pipeline_chain_workflow

TIGHT = StorageHierarchy(
    [TierSpec("hbm", 6e9, 800e9), TierSpec("bb", 12e9, 10e9)],
    remote=TierSpec("remote", float("inf"), 0.5e9))

INF = float("inf")


# ------------------------------------------------------------- link graph
class TestTopologyModel:
    def test_two_tier_shapes_and_racks(self):
        topo = ClusterTopology.two_tier(2, 4, nic_gbps=1.25e9,
                                        oversubscription=4.0)
        assert topo.n_nodes == 8 and topo.n_racks == 2
        assert topo.rack_of == (0, 0, 0, 0, 1, 1, 1, 1)
        assert topo.same_rack(0, 3) and not topo.same_rack(3, 4)
        assert not topo.same_rack(0, -1)        # the PFS is in no rack
        assert topo.up_gbps == (0.3125e9, 0.3125e9)
        assert topo.up_capacity_gbps == (1.25e9, 1.25e9)

    def test_link_gbps_is_min_capacity_on_path(self):
        topo = ClusterTopology.two_tier(2, 4, nic_gbps=1.25e9,
                                        oversubscription=4.0, pfs_gbps=0.5e9)
        assert topo.link_gbps(0, 1) == 1.25e9           # rack-local: NIC
        assert topo.link_gbps(0, 4) == 0.3125e9         # cross-rack: uplink
        assert topo.link_gbps(0, -1) == 0.3125e9        # PFS via the uplink
        assert topo.link_gbps(3, 3) == INF              # self-transfer

    def test_links_enumerates_the_path(self):
        topo = ClusterTopology.two_tier(2, 2)
        assert topo.links(0, 1) == (0, 1)
        assert topo.links(0, 3) == (0, 3, ("up", 0), ("up", 1))
        assert topo.links(2, -1) == (2, ("up", 1), ("pfs",))

    def test_profiles_feed_speeds_nics_and_classes(self):
        profs = [NodeProfile(speed=0.5, cls="old-gen", nic_gbps=0.625e9),
                 NodeProfile(), NodeProfile(cls="spot"), NodeProfile()]
        topo = ClusterTopology.two_tier(2, 2, profiles=profs)
        assert topo.speed(0) == 0.5 and topo.speed(1) == 1.0
        assert topo.nic(0) == 0.625e9 and topo.nic(1) == 1.25e9
        assert topo.node_class(2) == "spot"
        assert topo.speeds() == {0: 0.5}
        # the slow NIC caps even a rack-local transfer from node 0
        assert topo.link_gbps(0, 1) == 0.625e9

    def test_growth_join_fallback(self):
        topo = ClusterTopology.two_tier(2, 2)
        # node 4 joined after the topology was frozen: round-robin rack,
        # default NIC, nominal profile
        assert topo.rack(4) == 0 and topo.rack(5) == 1
        assert topo.nic(4) == 1.25e9
        assert topo.speed(4) == 1.0 and topo.node_class(4) == "standard"

    def test_one_switch_is_flat(self):
        topo = ClusterTopology.one_switch(4)
        assert topo.flat and topo.n_racks == 1
        assert topo.link_gbps(0, 3) == INF and topo.link_gbps(0, -1) == INF
        assert topo.same_rack(0, 3)

    def test_validation(self):
        with pytest.raises(ValueError, match="rack_of"):
            ClusterTopology(n_nodes=2, rack_of=(0,), nic_gbps=(1e9, 1e9),
                            up_gbps=(1e9,), up_capacity_gbps=(1e9,),
                            oversub=(1.0,))
        with pytest.raises(ValueError, match="rack id"):
            ClusterTopology(n_nodes=2, rack_of=(0, 5), nic_gbps=(1e9, 1e9),
                            up_gbps=(1e9,), up_capacity_gbps=(1e9,),
                            oversub=(1.0,))
        with pytest.raises(ValueError, match="oversubscription"):
            ClusterTopology.two_tier(2, 2, oversubscription=0.0)


# --------------------------------------------------------- cross-layer sim
def aware_vs_blind(aware, topo=None):
    """The mapreduce shuffle on a 2-rack fabric, 4:1 unless ``topo`` is
    given; *blind* plans with the flat model while the network charges
    real paths."""
    wf = compile_workflow(mapreduce_workflow(12, 6, 2e9, flops_per_byte=4.0),
                          HPC_CLUSTER)
    if topo is None:
        topo = ClusterTopology.two_tier(2, 4, nic_gbps=1.25e9,
                                        oversubscription=4.0)
    sim = WorkflowSimulator(wf, LocalityScheduler(wf, speed_aware=True),
                            n_nodes=8, hw=HPC_CLUSTER, topology=topo,
                            topology_aware=aware, external_loc="scattered",
                            hierarchy=TIGHT, sanitize=True, sanitize_every=1)
    r = sim.run()
    assert r.tasks_done == len(wf.graph.tasks)
    return sim, r


MIXED_GEN = [NodeProfile() if i < 4 else
             NodeProfile(speed=0.6, cls="old-gen", nic_gbps=0.625e9)
             for i in range(8)]


class TestTopologyCharging:
    def test_transfers_are_charged_per_link(self):
        sim, r = aware_vs_blind(True)
        # shuffle traffic crossed the spine and the ledger says where
        assert r.cross_spine_bytes > 0
        assert ("up", 0) in r.link_bytes and ("up", 1) in r.link_bytes
        assert any(isinstance(k, int) for k in r.link_bytes)   # NIC lanes
        # cross-spine is a subset of all charged bytes
        up = r.link_bytes[("up", 0)] + r.link_bytes[("up", 1)]
        assert r.cross_spine_bytes <= up + 1e-6

    def test_aware_beats_blind_on_oversubscribed_spine(self):
        """The whole point of the refactor: a scheduler/store that sees the
        topology moves fewer bytes across the oversubscribed spine and
        finishes sooner than one that plans with the flat model while the
        network charges real paths."""
        _, aware = aware_vs_blind(True)
        _, blind = aware_vs_blind(False)
        assert aware.cross_spine_bytes < blind.cross_spine_bytes
        assert aware.makespan < blind.makespan

    @pytest.mark.parametrize("topo,strict", [
        (ClusterTopology.two_tier(2, 4, oversubscription=1.0), False),
        (ClusterTopology.two_tier(2, 4, oversubscription=4.0,
                                  profiles=MIXED_GEN), True),
    ], ids=["nonblocking", "mixed-gen"])
    def test_aware_never_loses_to_blind(self, topo, strict):
        """On a non-blocking 1:1 spine awareness costs nothing; on a 4:1
        spine with one rack of old-generation nodes (0.6x compute, half
        the NIC) it strictly cuts cross-spine bytes and makespan."""
        _, aware = aware_vs_blind(True, topo)
        _, blind = aware_vs_blind(False, topo)
        if strict:
            assert aware.cross_spine_bytes < blind.cross_spine_bytes
            assert aware.makespan < blind.makespan
        else:
            assert aware.cross_spine_bytes <= blind.cross_spine_bytes
            assert aware.makespan <= blind.makespan

    def test_topology_size_mismatch_is_refused(self):
        wf = compile_workflow(mapreduce_workflow(4, 2), HPC_CLUSTER)
        with pytest.raises(ValueError, match="topology"):
            WorkflowSimulator(wf, LocalityScheduler(wf), n_nodes=8,
                              hw=HPC_CLUSTER,
                              topology=ClusterTopology.two_tier(2, 2))


class TestRackAwareStore:
    def test_default_placement_spreads_across_racks(self):
        topo = ClusterTopology.two_tier(2, 4)
        store = LocStore(8, default_policy="rr", topology=topo)
        racks = [topo.rack(store.put(f"d{i}", SimObject(1)).nodes[0])
                 for i in range(8)]
        # round-robin placement alternates racks instead of filling rack 0
        assert racks[:4] == [0, 1, 0, 1]

    def test_rereplication_prefers_the_other_rack(self):
        topo = ClusterTopology.two_tier(2, 2)
        store = LocStore(4, topology=topo)
        store.put("near", SimObject(5), loc=1)    # rack 0, same as 0
        store.put("far", SimObject(5), loc=2)     # rack 1
        cands = store.rereplication_candidates(0)
        # equal risk and size: the cross-rack source ranks first — copying
        # it to node 0 buys rack-domain diversity
        assert [c[0] for c in cands] == ["far", "near"]

    def test_only_src_restricts_to_the_suspect(self):
        store = LocStore(4)
        store.put("a", SimObject(1), loc=1)
        store.put("b", SimObject(1), loc=2)
        cands = store.rereplication_candidates(0, only_src=1)
        assert [c[0] for c in cands] == ["a"]
        assert store.rereplicate_to(0, only_src=2) == ("b",)


# ------------------------------------------------- predictive re-replication
def predictive_run(predict):
    wf = compile_workflow(pipeline_chain_workflow(8, 6), HPC_CLUSTER)
    sim = WorkflowSimulator(wf, ProactiveScheduler(wf, risk_aware=True),
                            n_nodes=4, hw=HPC_CLUSTER, hierarchy=TIGHT,
                            failures=[(8.0, 1)], predict_failures=predict,
                            predict_lead_s=3.0, sanitize=True,
                            sanitize_every=1)
    r = sim.run()
    assert r.tasks_done == len(wf.graph.tasks)
    return r


class TestPredictiveRereplication:
    def test_predictive_beats_reactive(self):
        """Flagging the failing node ``predict_lead_s`` early and draining
        its sole copies to another rack-domain must strictly reduce the
        data lost with the node — fewer reruns and dirty losses than the
        purely reactive run of the same schedule."""
        pred = predictive_run(True)
        react = predictive_run(False)
        assert pred.predictive_rereplications > 0
        assert pred.bytes_predictively_rereplicated > 0
        assert react.predictive_rereplications == 0
        assert (pred.dirty_lost + pred.reruns
                < react.dirty_lost + react.reruns)

    def test_predict_off_is_the_default(self):
        c = SimConfig.from_kwargs(n_nodes=4, hw=HPC_CLUSTER)
        assert c.predict_failures is False and c.topology_aware is True


# ------------------------------------------------------------------- lint
def lint_config(topology, n_nodes=None, **kw):
    return SimConfig.from_kwargs(
        n_nodes=topology.n_nodes if n_nodes is None else n_nodes,
        hw=HPC_CLUSTER, topology=topology, **kw)


class TestTopologyLint:
    WF = compile_workflow(mapreduce_workflow(8, 4, 2e9), HPC_CLUSTER)

    def rules(self, findings, rule):
        return [f for f in findings if f.rule == rule]

    def test_unreachable_node_flags_dead_links(self):
        topo = ClusterTopology(n_nodes=4, rack_of=(0, 0, 1, 1),
                               nic_gbps=(0.0, 1e9, 1e9, 1e9),
                               up_gbps=(1e9, 0.0),
                               up_capacity_gbps=(4e9, 0.0),
                               oversub=(1.0, 1.0))
        out = self.rules(lint(self.WF, config=lint_config(topo)),
                         "unreachable-node")
        targets = {f.target for f in out}
        assert "node0" in targets          # zero NIC
        assert "rack1" in targets          # zero uplink, two racks

    def test_unreachable_node_flags_size_mismatch_and_dead_pfs(self):
        topo = ClusterTopology.two_tier(2, 2, pfs_gbps=0.0)
        out = self.rules(
            lint(self.WF, config=lint_config(topo, n_nodes=8,
                                             external_loc="remote")),
            "unreachable-node")
        targets = {f.target for f in out}
        assert "topology.n_nodes" in targets
        assert "topology.pfs_gbps" in targets

    def test_unreachable_node_clean_on_healthy_topology(self):
        topo = ClusterTopology.two_tier(2, 4, oversubscription=4.0)
        out = self.rules(lint(self.WF, config=lint_config(topo)),
                         "unreachable-node")
        assert out == []

    def test_oversubscribed_link_triggers_on_starved_pfs(self):
        topo = ClusterTopology.two_tier(2, 4, pfs_gbps=1e4)
        out = self.rules(
            lint(self.WF, config=lint_config(topo, external_loc="remote")),
            "oversubscribed-link")
        assert any(f.target == "pfs" for f in out)

    def test_oversubscribed_link_triggers_on_thin_uplinks(self):
        topo = ClusterTopology.two_tier(2, 4, oversubscription=1e6)
        out = self.rules(
            lint(self.WF, config=lint_config(topo, external_loc="remote")),
            "oversubscribed-link")
        assert {f.target for f in out} >= {"rack0", "rack1"}

    def test_oversubscribed_link_factor_is_configurable(self):
        topo = ClusterTopology.two_tier(2, 4, pfs_gbps=1e4)
        cfg = lint_config(topo, external_loc="remote")
        assert self.rules(lint(self.WF, config=cfg), "oversubscribed-link")
        relaxed = lint(self.WF, config=cfg,
                       params={"oversub-factor": 1e12})
        assert self.rules(relaxed, "oversubscribed-link") == []

    @pytest.mark.parametrize("oversub,pfs_gbps,flagged", [
        (8.0, 1e7, True), (1.0, 4e9, False)], ids=["8to1", "1to1"])
    def test_oversubscribed_link_on_stage_in_plan(self, oversub, pfs_gbps,
                                                  flagged):
        """The mapreduce stage-in from a remote PFS trips the rule on an
        8:1 spine with a starved PFS and stays quiet on a 1:1 fabric."""
        topo = ClusterTopology.two_tier(2, 4, oversubscription=oversub,
                                        pfs_gbps=pfs_gbps)
        wf = compile_workflow(mapreduce_workflow(12, 6, 2e9), HPC_CLUSTER)
        out = self.rules(
            lint(wf, config=lint_config(topo, external_loc="remote")),
            "oversubscribed-link")
        assert bool(out) == flagged

    def test_oversubscribed_link_clean_on_adequate_fabric(self):
        topo = ClusterTopology.two_tier(2, 4, pfs_gbps=2e9)
        out = self.rules(
            lint(self.WF, config=lint_config(topo, external_loc="remote")),
            "oversubscribed-link")
        assert out == []


# -------------------------------------------------------------- sanitizer
class TestTopologySanitizer:
    """The topology-derived caches, corrupted after a clean aware run, are
    caught — and the error names the first divergent entry."""

    @pytest.fixture(scope="class")
    def ran(self):
        sim, _ = aware_vs_blind(True)
        return sim

    def test_link_path_desync(self, ran):
        cache = ran._path_cache
        assert cache, "an aware run must populate the path table"
        sanitize.check_link_paths(cache, ran._topo_real)   # clean before
        key = sorted(cache)[0]
        stash = cache[key]
        cache[key] = stash + (("up", 99),)
        try:
            with pytest.raises(SanitizerError) as ei:
                sanitize.check_link_paths(cache, ran._topo_real)
        finally:
            cache[key] = stash
        assert ei.value.check == "link-path" and ei.value.key == key

    def test_link_path_cache_must_be_empty_without_topology(self):
        with pytest.raises(SanitizerError) as ei:
            sanitize.check_link_paths({(0, 1): (0, 1)}, None)
        assert ei.value.check == "link-path"

    def test_link_row_desync(self, ran):
        rows = ran.cluster._link_rows
        if not rows:
            pytest.skip("run left no cached link rows")
        sanitize.check_link_rows(ran.cluster)              # clean before
        src = sorted(rows)[0]
        row, _ = rows[src]
        dst = (src + 1) % ran.cluster.n_nodes
        row[dst] += 1.0
        try:
            with pytest.raises(SanitizerError) as ei:
                sanitize.check_link_rows(ran.cluster)
        finally:
            row[dst] -= 1.0
        assert ei.value.check == "link-row"
        assert ei.value.key == (src, dst)

    def test_link_row_uniform_marker_desync(self, ran):
        rows = ran.cluster._link_rows
        if not rows:
            pytest.skip("run left no cached link rows")
        src = sorted(rows)[0]
        row, uniform = rows[src]
        rows[src] = (row, 123.456)
        try:
            with pytest.raises(SanitizerError) as ei:
                sanitize.check_link_rows(ran.cluster)
        finally:
            rows[src] = (row, uniform)
        assert ei.value.check == "link-row"
        assert ei.value.key == (src, "uniform")
