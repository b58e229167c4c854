"""Failure-path suite: durability windows, atomic node drops, and serving
failover (ISSUE 5).

The paper's compute-on-data-path keeps fresh output on the node that made it,
so a node failure can take the only copy of a dataset — or a parked session's
KV cache — down with it. These tests pin the failure semantics:

* ``drop_node`` is atomic: replicas forgotten, in-flight write-back flushes
  sourced on the dead node cancelled (no phantom PFS copies), pins cleared;
* sole-copy loss re-runs the producer, replicated loss does not;
* dirty loss re-runs, flushed loss does not — per durability policy;
* a transfer cannot "arrive" from a node that died mid-flight;
* a parked session whose engine died resumes bit-identically on a surviving
  engine, without a prefill, when its KV slice was durable.
"""

import dataclasses

import jax
import pytest

from repro.configs import get_smoke
from repro.core.dag import TaskGraph
from repro.core.hints import Complexity, size_hint, task
from repro.core.locstore import (LocStore, Placement, REMOTE_TIER, SimObject,
                                 StorageHierarchy, TierSpec, tiered_hierarchy)
from repro.core.scheduler import LocalityScheduler, ProactiveScheduler
from repro.core.simulator import WorkflowSimulator
from repro.core.wfcompiler import HPC_CLUSTER, compile_workflow
from repro.core.workloads import pipeline_chain_workflow
from repro.models import init_params
from repro.serve.engine import Router, ServingEngine, _cache_name

GB = float(1 << 30)
MB = float(1 << 20)


def small_tiers(cap: float = 1e6) -> StorageHierarchy:
    return tiered_hierarchy(hbm_bytes=cap, host_bytes=cap, bb_bytes=cap)


# --------------------------------------------------------------- store layer
class TestDurabilityWindows:
    def test_pending_writeback_is_not_durable(self):
        st = LocStore(2, hierarchy=small_tiers(), write_policy="back")
        for n in "wxyz":                      # w falls off bb -> queued flush
            st.put(n, SimObject(8e5), loc=0)
        assert st.writeback.has("w")
        assert not st.durable("w"), "queued bytes have not crossed the network"
        st.drain_writebacks()
        assert st.durable("w"), "a drained flush is what durability means"

    def test_flush_before_ack_put_is_durable(self):
        st = LocStore(2, durability="flush_before_ack")
        st.put("a", SimObject(1e6), loc=0)
        assert st.durable("a")
        assert st.fsyncs == 1 and st.fsync_bytes == 1e6
        assert st.transfers[-1].kind == "fsync"

    def test_fsync_on_barrier_window(self):
        st = LocStore(2, durability="fsync_on_barrier")
        st.put("a", SimObject(1e6), loc=0)
        st.put("b", SimObject(2e6), loc=1)
        assert not st.durable("a") and not st.durable("b")
        assert st.barrier() == 2
        assert st.durable("a") and st.durable("b")
        assert st.barrier() == 0, "nothing dirty: the barrier is free"

    def test_flush_before_ack_migrate_keeps_window_closed(self):
        st = LocStore(2, durability="flush_before_ack")
        st.put("a", SimObject(1e6), loc=0)
        st.migrate("a", 1)                    # re-pin drops the PFS replica…
        assert st.durable("a"), "…but the policy re-flushes before returning"

    def test_fsync_supersedes_pending_writeback(self):
        st = LocStore(2, hierarchy=small_tiers(), write_policy="back")
        for n in "wxyz":
            st.put(n, SimObject(8e5), loc=0)
        assert st.writeback.has("w")
        assert st.fsync(["w"]) == 1
        assert st.durable("w")
        assert not st.drain_writebacks(), "the fsync IS the flush"

    def test_unknown_policy_rejected(self):
        with pytest.raises(ValueError, match="durability"):
            LocStore(2, durability="eventually")


class TestDropNode:
    def test_sole_copy_lost_replicated_survives(self):
        st = LocStore(3)
        st.put("sole", SimObject(1e5), loc=0)
        st.put("dup", SimObject(1e5), loc=(0, 1))
        rep = st.drop_node(0)
        assert rep.lost == ("sole",) and rep.survived == ("dup",)
        assert not st.exists("sole"), "exists() must turn False: re-run"
        assert st.exists("dup") and st.stat("dup").nodes == (1,)

    def test_durable_object_survives_node_loss(self):
        st = LocStore(2, durability="flush_before_ack")
        st.put("a", SimObject(1e6), loc=0)
        rep = st.drop_node(0)
        assert rep.survived == ("a",) and rep.lost == ()
        assert st.exists("a")
        assert st.stat("a").nodes == (REMOTE_TIER,)

    def test_phantom_writeback_cancelled(self):
        """Regression (ISSUE 5 satellite 1): a pending flush sourced on the
        dead node must be cancelled — a later drain must NOT mark the lost
        object durable on the strength of a phantom PFS copy."""
        st = LocStore(2, hierarchy=small_tiers(), write_policy="back")
        for n in "wxyz":
            st.put(n, SimObject(8e5), loc=0)
        assert st.writeback.has("w")          # flush queued, bytes NOT moved
        rep = st.drop_node(0)
        assert rep.cancelled_flushes == 1
        assert rep.phantom_remote_revoked == 1
        assert "w" in rep.lost and "w" in rep.dirty_lost
        assert not st.drain_writebacks(), "cancelled flush must not drain"
        assert not st.exists("w")
        assert st.phantom_durable == 0, "drop_node beat the drain to it"

    def test_drain_defense_in_depth(self):
        """Even when a caller skips drop_node, a drain sourced on a node in
        the failed set must not launder lost bytes into durability."""
        st = LocStore(2, hierarchy=small_tiers(), write_policy="back")
        for n in "wxyz":
            st.put(n, SimObject(8e5), loc=0)
        st._failed_nodes.add(0)               # failure outside drop_node
        assert not st.drain_writebacks()
        assert st.phantom_durable >= 1
        assert not st.durable("w")

    def test_pins_cleared_for_dead_node(self):
        """Regression (satellite 2): a failed node's pin refcounts must not
        keep shielding ghosts in ``_victim``."""
        st = LocStore(2)
        st.put("p", SimObject(1e5), loc=(0, 1))
        st.pin("p", 0)
        st.pin("p", 0)
        st.pin("p", 1)
        rep = st.drop_node(0)
        assert rep.released_pins == 2
        assert not st.is_pinned("p", 0)
        assert st.is_pinned("p", 1), "the survivor's pin stands"

    def test_default_placement_avoids_failed_nodes(self):
        from repro.core.locstore import _stable_hash
        st = LocStore(4)
        home = _stable_hash("obj") % 4        # where the hash would put it
        st.drop_node(home)
        p = st.put("obj", SimObject(1e5))
        assert p.real_loc != home
        assert p.real_loc not in st.failed_nodes

    def test_dirty_lost_accounting(self):
        st = LocStore(2, write_policy="back")
        st.put("d", SimObject(1e5), loc=0)    # dirty: no PFS copy yet
        st.fsync(["d"])
        st.put("e", SimObject(1e5), loc=0)    # dirty
        rep = st.drop_node(0)
        assert "e" in rep.dirty_lost
        assert "d" in rep.survived, "the flushed object survived on the PFS"


# ----------------------------------------------------------- simulator layer
def _chain_wf(depth: int = 6):
    return compile_workflow(pipeline_chain_workflow(4, depth), HPC_CLUSTER)


class TestSimulatorFailures:
    def test_sole_copy_loss_reruns_producer(self):
        g = TaskGraph()
        g.add_data("src", size_bytes=size_hint(256 * MB))
        g.add_task("produce", inputs=("src",), outputs=("mid",),
                   hints=task(compute=Complexity("linear",
                                                 flops_per_byte=2000.0)))
        g.add_task("consume", inputs=("mid",), outputs=("out",),
                   hints=task(compute=Complexity("linear",
                                                 flops_per_byte=2000.0)))
        wf = compile_workflow(g, HPC_CLUSTER)
        base = WorkflowSimulator(wf, LocalityScheduler(wf), n_nodes=2,
                                 hw=HPC_CLUSTER).run()
        assert base.reruns == 0
        # fail the producing node right after `produce` finishes
        t_fail = base.task_records["produce"]["finish"] + 1e-3
        node = base.task_records["produce"]["node"]
        r = WorkflowSimulator(wf, LocalityScheduler(wf), n_nodes=2,
                              hw=HPC_CLUSTER, failures=[(t_fail, node)]).run()
        assert r.reruns >= 1, "sole-copy loss must re-run the producer"
        assert r.tasks_done == len(wf.graph.tasks)

    def test_replicated_loss_does_not_rerun(self):
        g = TaskGraph()
        g.add_data("src", size_bytes=size_hint(256 * MB))
        g.add_data("mid", pinned_loc=(0, 1))   # S_LOC: replicate the output
        g.add_task("produce", inputs=("src",), outputs=("mid",),
                   hints=task(compute=Complexity("linear",
                                                 flops_per_byte=2000.0)))
        g.add_task("consume", inputs=("mid",), outputs=("out",),
                   hints=task(compute=Complexity("linear",
                                                 flops_per_byte=2000.0)))
        wf = compile_workflow(g, HPC_CLUSTER)
        base = WorkflowSimulator(wf, LocalityScheduler(wf), n_nodes=3,
                                 hw=HPC_CLUSTER).run()
        t_fail = base.task_records["produce"]["finish"] + 1e-3
        r = WorkflowSimulator(wf, LocalityScheduler(wf), n_nodes=3,
                              hw=HPC_CLUSTER, failures=[(t_fail, 0)]).run()
        # the requeued-if-running task may count one rerun; the replicated
        # dataset itself must not force a producer re-execution
        assert all(not rep.lost or "mid" not in rep.lost
                   for rep in r.drop_reports)
        assert r.tasks_done == len(wf.graph.tasks)

    def test_dirty_loss_reruns_flushed_loss_does_not(self):
        """The headline durability claim: under write-back, a mid-run failure
        re-runs every dirty sole-copy producer; fsync_on_barrier bounds the
        window to one barrier interval, at an io-wait cost."""
        wf = _chain_wf()
        results = {}
        for pol in ("none", "fsync_on_barrier", "flush_before_ack"):
            r = WorkflowSimulator(wf, ProactiveScheduler(wf), n_nodes=4,
                                  hw=HPC_CLUSTER, write_policy="back",
                                  durability=pol, failures=[(4.0, 0)]).run()
            assert r.tasks_done == len(wf.graph.tasks)
            assert r.phantom_durable == 0
            results[pol] = r
        none, barrier = results["none"], results["fsync_on_barrier"]
        ack = results["flush_before_ack"]
        assert none.dirty_lost > 0, "the failure must hit dirty data"
        assert barrier.dirty_lost == 0 and ack.dirty_lost == 0
        assert barrier.reruns < none.reruns
        assert ack.reruns < none.reruns
        assert barrier.fsyncs > 0 and ack.fsyncs > 0
        assert none.fsyncs == 0

    @pytest.mark.parametrize("failures", [(), ((4.0, 0),)],
                             ids=["f0", "f1"])
    def test_policy_sweep_keeps_invariants(self, failures):
        """Every durability policy, with and without a mid-run failure:
        every task finishes, no object is phantom-durable, no pin leaks.
        Without failures the policies differ only in fsyncs (the window
        claims under a failure are test_dirty_loss_reruns_flushed_loss_does_not,
        on the same workload and schedule)."""
        wf = _chain_wf()
        fsyncs = {}
        for pol in ("none", "fsync_on_barrier", "flush_before_ack"):
            sim = WorkflowSimulator(wf, ProactiveScheduler(wf), n_nodes=4,
                                    hw=HPC_CLUSTER, write_policy="back",
                                    durability=pol, failures=list(failures))
            r = sim.run()
            assert r.tasks_done == len(wf.graph.tasks)
            assert r.phantom_durable == 0
            assert sim.store.movement_report()["pins"] == 0, "leaked pins"
            fsyncs[pol] = r.fsyncs
        if not failures:
            assert fsyncs["none"] == 0 and fsyncs["fsync_on_barrier"] > 0

    def test_failure_cancelled_task_releases_pins(self):
        """Regression (satellite 2): prefetch pins of a task cancelled by the
        failure must be released — task-finish unpin never fires for it."""
        wf = _chain_wf()
        sim = WorkflowSimulator(wf, ProactiveScheduler(wf), n_nodes=4,
                                hw=HPC_CLUSTER, write_policy="back",
                                failures=[(4.0, 0), (4.5, 2)])
        r = sim.run()
        assert r.tasks_done == len(wf.graph.tasks)
        assert sim.store.movement_report()["pins"] == 0, "leaked pin refcounts"

    def test_transfer_from_dead_node_aborts(self):
        """Regression (satellite 3): an in-flight prefetch whose SOURCE node
        dies must not 'arrive' and materialize a replica."""
        C = lambda: Complexity("linear", flops_per_byte=2000.0)  # noqa: E731
        g = TaskGraph()
        g.add_data("seed", size_bytes=size_hint(256 * MB))
        g.add_data("big0", size_bytes=size_hint(5 * GB))
        g.add_data("big1", size_bytes=size_hint(4 * GB))
        g.add_task("warm", inputs=("seed",), outputs=("w",),
                   hints=task(compute=C()))
        g.add_task("consume", inputs=("w", "big0", "big1"), outputs=("out",),
                   hints=task(compute=C()))
        wf = compile_workflow(g, HPC_CLUSTER)
        sim = WorkflowSimulator(wf, ProactiveScheduler(wf), n_nodes=3,
                                hw=HPC_CLUSTER, external_loc="scattered",
                                failures=[(1.0, 1)])
        # deterministic geometry: warm on node 2; consume preassigned to
        # node 0 (big0's 5 GB gravity) so big1 prefetches node 1 -> node 0,
        # a ~3 s transfer that is mid-flight when node 1 dies at t=1
        sim.store.migrate("seed", 2)
        sim.store.migrate("big0", 0)
        sim.store.migrate("big1", 1)
        r = sim.run()
        assert r.prefetch_aborts >= 1, "the dead-source transfer arrived"
        assert r.tasks_done == len(wf.graph.tasks)
        # big1 was re-staged from the PFS, not from the ghost of node 1
        assert sim.store.stat("big1").resident_on(0) or \
            sim.store.stat("big1").nodes == (REMOTE_TIER,)

    def test_fsync_rides_demand_lane(self):
        """fsync-on-barrier's cost is real: the same workload pays more
        io-wait than durability='none' because flushes block the demand NIC."""
        wf = _chain_wf()
        free = WorkflowSimulator(wf, ProactiveScheduler(wf), n_nodes=4,
                                 hw=HPC_CLUSTER, write_policy="back").run()
        paid = WorkflowSimulator(wf, ProactiveScheduler(wf), n_nodes=4,
                                 hw=HPC_CLUSTER, write_policy="back",
                                 durability="fsync_on_barrier").run()
        assert paid.fsyncs > 0
        assert paid.io_wait_total >= free.io_wait_total
        assert paid.makespan >= free.makespan

    def test_risk_aware_priority_orders_at_risk_consumers(self):
        """Durability as a scheduling signal: with equal upward ranks, the
        consumer of sole-copy non-durable bytes outranks one whose input is
        already safe on the PFS."""
        C = lambda: Complexity("linear", flops_per_byte=2000.0)  # noqa: E731
        g = TaskGraph()
        g.add_data("risky", size_bytes=size_hint(1 * GB))
        g.add_data("safe", size_bytes=size_hint(1 * GB))
        g.add_task("eat_risky", inputs=("risky",), outputs=("o1",),
                   hints=task(compute=C()))
        g.add_task("eat_safe", inputs=("safe",), outputs=("o2",),
                   hints=task(compute=C()))
        wf = compile_workflow(g, HPC_CLUSTER)
        sched = LocalityScheduler(wf, risk_aware=True)
        sim = WorkflowSimulator(wf, sched, n_nodes=1, hw=HPC_CLUSTER)
        sim.store.migrate("risky", 0)          # sole node-local copy: dirty
        assert not sim.store.durable("risky")
        assert sim.store.durable("safe")       # external on the PFS
        sched.note_ready("eat_risky")
        sched.note_ready("eat_safe")
        ranks = sorted(["eat_safe", "eat_risky"],
                       key=lambda t: sched._queue_key(t, sim.cluster))
        assert ranks[0] == "eat_risky"


# -------------------------------------------------------------- serving layer
@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_smoke("granite-3-2b"), dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _failover_store(kv: float, durability: str = "flush_before_ack"):
    return LocStore(2, hierarchy=tiered_hierarchy(
        hbm_bytes=2 * kv, host_bytes=2 * kv, bb_bytes=float(1 << 30)),
        write_policy="back", durability=durability)


class TestServingFailover:
    def test_failover_resumes_bit_identical_no_prefill(self, setup):
        cfg, params = setup
        kv = ServingEngine(cfg, params, max_batch=2, max_seq=64).slot_bytes()

        # control: same park/resume lifecycle, no failure, single engine
        ctrl = ServingEngine(cfg, params, max_batch=2, max_seq=64, node=0,
                             store=_failover_store(kv))
        sid_c = ctrl.submit([5, 6, 7])
        for _ in range(3):
            ctrl.step()
        ctrl.park(sid_c)
        ctrl.resume(sid_c)
        for _ in range(3):
            ctrl.step()
        want = ctrl.sessions[sid_c].tokens[:7]

        store = _failover_store(kv)
        a = ServingEngine(cfg, params, max_batch=2, max_seq=64, node=0,
                          store=store)
        b = ServingEngine(cfg, params, max_batch=2, max_seq=64, node=1,
                          store=store)
        router = Router([a, b], store)
        sid = a.submit([5, 6, 7])
        for _ in range(3):
            a.step()
        a.park(sid)
        assert store.durable(_cache_name(sid))
        prefills = a.prefills + b.prefills
        rep = router.fail_engine(0)
        assert rep.resumed == (sid,) and rep.lost == ()
        assert router.failover_resumes == 1
        assert a.prefills + b.prefills == prefills, \
            "failover must save the re-prefill"
        assert b.sessions[sid].slot is not None
        for _ in range(3):
            b.step()
        assert b.sessions[sid].tokens[:7] == want, \
            "decode after failover must be bit-identical"
        assert store.getxattr(_cache_name(sid), "engine") == 1

    def test_failover_beside_a_live_session(self, setup):
        """A parked and a live session on the engine that dies: the parked
        one resumes elsewhere bit-identically without a prefill, the live
        one is lost."""
        cfg, params = setup
        kv = ServingEngine(cfg, params, max_batch=2, max_seq=64).slot_bytes()
        ctrl = ServingEngine(cfg, params, max_batch=2, max_seq=64, node=0,
                             store=_failover_store(kv))
        sid_c = ctrl.submit([5, 6, 7])
        for _ in range(3):
            ctrl.step()
        ctrl.park(sid_c)
        ctrl.resume(sid_c)
        for _ in range(3):
            ctrl.step()
        want = ctrl.sessions[sid_c].tokens[:7]

        store = _failover_store(kv)
        a, b = (ServingEngine(cfg, params, max_batch=2, max_seq=64, node=i,
                              store=store) for i in range(2))
        router = Router([a, b], store)
        sid = a.submit([5, 6, 7])
        for _ in range(3):
            a.step()
        a.park(sid)
        live_sid = a.submit([9, 8, 7])
        assert store.durable(_cache_name(sid))
        prefills = a.prefills + b.prefills
        rep = router.fail_engine(0)
        assert rep.resumed == (sid,), "the durable parked session fails over"
        assert rep.lost == (live_sid,), "the live slot's KV died with a"
        assert a.prefills + b.prefills == prefills, "no re-prefill"
        for _ in range(3):
            b.step()
        assert b.sessions[sid].tokens[:7] == want
        assert router.failover_resumes >= 1

    def test_live_slot_session_is_lost(self, setup):
        cfg, params = setup
        kv = ServingEngine(cfg, params, max_batch=2, max_seq=64).slot_bytes()
        store = _failover_store(kv)
        a = ServingEngine(cfg, params, max_batch=2, max_seq=64, node=0,
                          store=store)
        b = ServingEngine(cfg, params, max_batch=2, max_seq=64, node=1,
                          store=store)
        router = Router([a, b], store)
        sid = a.submit([1, 2])                # live: KV is engine memory
        rep = router.fail_engine(0)
        assert rep.lost == (sid,) and rep.resumed == ()
        assert router.failover_lost == 1
        assert not store.exists(_cache_name(sid))

    def test_parked_inside_open_window_is_lost(self, setup):
        cfg, params = setup
        kv = ServingEngine(cfg, params, max_batch=2, max_seq=64).slot_bytes()
        store = _failover_store(kv, durability="none")
        a = ServingEngine(cfg, params, max_batch=2, max_seq=64, node=0,
                          store=store)
        b = ServingEngine(cfg, params, max_batch=2, max_seq=64, node=1,
                          store=store)
        router = Router([a, b], store)
        sid = a.submit([1, 2, 3])
        a.park(sid)
        assert not store.durable(_cache_name(sid))
        rep = router.fail_engine(0)
        assert rep.lost == (sid,), \
            "an un-flushed parked slice dies with its node"

    def test_saturated_survivor_adopts_parked_not_lost(self, setup):
        """Capacity pressure must not forfeit a durable replica: when the
        surviving engine has no free slot, the failed-over session is
        adopted PARKED (no slot needed) and resumes on a later turn."""
        cfg, params = setup
        kv = ServingEngine(cfg, params, max_batch=1, max_seq=64).slot_bytes()
        store = LocStore(2, hierarchy=tiered_hierarchy(
            hbm_bytes=2 * kv, host_bytes=2 * kv, bb_bytes=float(1 << 30)),
            write_policy="back", durability="flush_before_ack")
        a = ServingEngine(cfg, params, max_batch=1, max_seq=64, node=0,
                          store=store)
        b = ServingEngine(cfg, params, max_batch=1, max_seq=64, node=1,
                          store=store)
        router = Router([a, b], store, allow_park=False)
        sid = a.submit([5, 6, 7])
        for _ in range(2):
            a.step()
        a.park(sid)
        want_next = None
        busy = b.submit([4, 4])               # saturate the survivor
        rep = router.fail_engine(0)
        assert rep.resumed == (sid,), "a full engine is still a valid home"
        assert b.sessions[sid].slot is None, "adopted parked, not resumed"
        assert store.exists(_cache_name(sid)), "the durable slice survives"
        assert store.getxattr(_cache_name(sid), "engine") == 1, "re-homed"
        b.finish(busy)                        # a slot frees up later…
        assert b.resume(sid)                  # …and the session re-hydrates
        tok = b.step()
        want_next = tok.get(sid)
        assert want_next is not None, "decode continues after late resume"

    def test_incompatible_slot_shape_deferred_not_lost(self, setup):
        # a durable slice no *currently registered* engine can load is not
        # forfeited: it parks unhomed (failover_deferred) and the next
        # compatible join_engine adopts it without a prefill
        cfg, params = setup
        kv = ServingEngine(cfg, params, max_batch=2, max_seq=64).slot_bytes()
        store = _failover_store(kv)
        a = ServingEngine(cfg, params, max_batch=2, max_seq=64, node=0,
                          store=store)
        b = ServingEngine(cfg, params, max_batch=2, max_seq=32, node=1,
                          store=store)       # different max_seq: shape clash
        router = Router([a, b], store)
        sid = a.submit([5, 6, 7])
        a.park(sid)
        rep = router.fail_engine(0)
        assert rep.deferred == (sid,) and rep.resumed == () and rep.lost == ()
        assert router.failover_deferred == 1 and router.failover_lost == 0
        assert store.exists(_cache_name(sid)), \
            "the durable slice must survive the no-compatible-home window"
        # a compatible engine joins: the deferred session is adopted
        c = ServingEngine(cfg, params, max_batch=2, max_seq=64, node=2,
                          store=store)
        jrep = router.join_engine(2, c)
        assert jrep.adopted == (sid,)
        assert router.failover_resumes == 1
        assert c.sessions[sid].slot is not None, "free slot: resumed"
