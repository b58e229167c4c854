"""Cross-check: simulator-reported NIC/tier traffic must equal the LocStore's
Transfer/TierHop ledger for the same workload trace (PR 3 satellite — catches
the class of spill-accounting bugs found in the PR 2 review: bytes counted in
a scalar but missing from the transfer log, or vice versa).
"""

import pytest

from repro.core import (HPC_CLUSTER, LocalityScheduler, ProactiveScheduler,
                        StorageHierarchy, TierSpec, compile_workflow)
from repro.core.locstore import LocStore, REMOTE_TIER, SimObject
from repro.core.simulator import WorkflowSimulator
from repro.core.workloads import fig2_workflow, montage_workflow

GB = float(1 << 30)

SPILL_KINDS = ("demote", "spill", "writeback", "writearound")


def recompute_from_transfers(store: LocStore) -> dict:
    """Re-derive every scalar movement counter from the transfer ledger."""
    fetches = [t for t in store.transfers if t.kind == "fetch"]
    migrates = [t for t in store.transfers if t.kind == "migrate"]
    spills = [t for t in store.transfers
              if t.kind in SPILL_KINDS and t.dst == REMOTE_TIER]
    demotes = [t for t in store.transfers if t.kind == "demote"]
    writebacks = [t for t in store.transfers if t.kind == "writeback"]
    tier_reads: dict[str, float] = {}
    for t in fetches:
        tier_reads[t.src_tier] = tier_reads.get(t.src_tier, 0.0) + t.nbytes
    return {
        "bytes_local": sum(t.nbytes for t in fetches if t.local),
        "bytes_moved": (sum(t.nbytes for t in fetches if not t.local)
                        + sum(t.nbytes for t in migrates)
                        + sum(t.nbytes for t in spills)),
        "remote_bytes": (sum(t.nbytes for t in fetches if not t.local
                             and (t.src == REMOTE_TIER or t.dst == REMOTE_TIER))
                         + sum(t.nbytes for t in migrates
                               if t.src == REMOTE_TIER or t.dst == REMOTE_TIER)
                         + sum(t.nbytes for t in spills)),
        "bytes_demoted": (sum(t.nbytes for t in demotes)
                          + sum(t.nbytes for t in writebacks)),
        "demotions": len(demotes) + len(writebacks),
        "writebacks": len(writebacks),
        "writeback_bytes": sum(t.nbytes for t in writebacks),
        "tier_reads": tier_reads,
    }


def assert_ledger_balances(store: LocStore) -> None:
    got = store.movement_report()
    want = recompute_from_transfers(store)
    for key in ("bytes_local", "bytes_moved", "remote_bytes", "bytes_demoted",
                "writeback_bytes"):
        assert got[key] == pytest.approx(want[key]), key
    assert got["demotions"] == want["demotions"]
    assert got["writebacks"] == want["writebacks"]
    # per-tier read traffic balances too
    rep = store.tier_report()
    for tier, nb in want["tier_reads"].items():
        assert rep[tier]["bytes_read"] == pytest.approx(nb), tier
    # every hop in every transfer describes the transferred object, nothing
    # else (the PR 2 hop-attribution rule)
    for t in store.transfers:
        assert all(h.nbytes == t.nbytes for h in t.hops), t


def _tiered(cap):
    return StorageHierarchy(
        [TierSpec("hbm", cap / 4, 819e9),
         TierSpec("host", cap, 100e9),
         TierSpec("bb", 16 * cap, 8e9)],
        remote=TierSpec("remote", float("inf"), 0.5e9))


def _flat_capped(cap):
    return StorageHierarchy([TierSpec("host", cap, 100e9)],
                            remote=TierSpec("remote", float("inf"), 0.5e9))


class TestSimulatorTraceBalances:
    @pytest.mark.parametrize("policy,coord", [
        ("through", False), ("back", False), ("back", True)],
        ids=["through", "back", "back+coord"])
    def test_montage_under_pressure(self, policy, coord):
        wf = compile_workflow(montage_workflow(16), HPC_CLUSTER)
        sim = WorkflowSimulator(wf, ProactiveScheduler(wf), n_nodes=4,
                                hw=HPC_CLUSTER, hierarchy=_tiered(0.25 * GB),
                                write_policy=policy,
                                coordinated_eviction=coord)
        r = sim.run()
        assert r.tasks_done == len(wf.graph.tasks)
        assert_ledger_balances(sim.store)
        # the SimResult counters are the same ledger
        rep = sim.store.movement_report()
        assert r.bytes_moved == rep["bytes_moved"]
        assert r.remote_bytes == rep["remote_bytes"]
        assert r.bytes_demoted == rep["bytes_demoted"]
        assert r.writeback_bytes == rep["writeback_bytes"]

    def test_flat_capped_sweep_point(self):
        wf = compile_workflow(montage_workflow(16), HPC_CLUSTER)
        sim = WorkflowSimulator(wf, LocalityScheduler(wf), n_nodes=4,
                                hw=HPC_CLUSTER, hierarchy=_flat_capped(0.5 * GB))
        sim.run()
        assert_ledger_balances(sim.store)

    def test_default_flat_fig2(self):
        wf = compile_workflow(fig2_workflow(), HPC_CLUSTER)
        sim = WorkflowSimulator(wf, ProactiveScheduler(wf), n_nodes=4,
                                hw=HPC_CLUSTER)
        sim.run()
        assert_ledger_balances(sim.store)

    def test_failure_path_balances(self):
        wf = compile_workflow(montage_workflow(12), HPC_CLUSTER)
        sim = WorkflowSimulator(wf, ProactiveScheduler(wf), n_nodes=8,
                                hw=HPC_CLUSTER, hierarchy=_tiered(1 * GB),
                                write_policy="back", failures=[(1.0, 0)])
        r = sim.run()
        assert r.tasks_done == len(wf.graph.tasks)
        assert_ledger_balances(sim.store)


class TestStoreLevelTraceBalances:
    def test_spill_heavy_trace(self):
        """Oversized puts, migrations and replicas — every byte in a scalar
        counter has a Transfer record behind it."""
        st = LocStore(2, hierarchy=_tiered(400 * 4.0))
        st.put("big", SimObject(16000.0), loc=0)        # fits nowhere: spill
        st.put("a", SimObject(300.0), loc=0)
        st.put("b", SimObject(300.0), loc=0)
        st.get("a", at=1)
        st.replicate("a", [1])
        st.migrate("b", 1)
        st.get("big", at=0)                             # PFS demand fetch
        assert_ledger_balances(st)

    def test_writeback_trace(self):
        st = LocStore(1, hierarchy=_tiered(400 * 4.0), write_policy="back")
        for i in range(12):
            st.put(f"o{i}", SimObject(350.0), loc=0)
        st.drain_writebacks()
        for i in range(12):
            st.get(f"o{i}", at=0)
        assert_ledger_balances(st)

    def test_writearound_trace(self):
        st = LocStore(2, hierarchy=_tiered(400 * 4.0))
        st.put("s", SimObject(100.0), loc=0, mode="around")
        st.get("s", at=1)
        st.get("s", at=0)
        assert_ledger_balances(st)
