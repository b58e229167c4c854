"""Serving engine: continuous batching, slot isolation, location-aware
routing, and the tiered session lifecycle (KV caches as first-class
LocStore replicas: submit -> idle-park -> resume-promote -> finish)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.locstore import (LocStore, StorageHierarchy, TierSpec,
                                 tiered_hierarchy)
from repro.core.prefetch import PrefetchEngine
from repro.models import decode_step, forward_logits, init_params, prefill
from repro.serve.engine import (Router, ServingEngine, _cache_name,
                                _read_slot, _write_slot)


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_smoke("granite-3-2b"), dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_generate_deterministic(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64)
    out1 = eng.generate([5, 6, 7], max_new=6)
    eng2 = ServingEngine(cfg, params, max_batch=2, max_seq=64)
    out2 = eng2.generate([5, 6, 7], max_new=6)
    assert out1 == out2
    assert len(out1) == 6


def test_batched_sessions_isolated(setup):
    """Two concurrent sessions decode as if they were alone (slot masking)."""
    cfg, params = setup
    solo = ServingEngine(cfg, params, max_batch=1, max_seq=64)
    a_solo = solo.generate([1, 2, 3, 4], max_new=5)

    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64)
    sa = eng.submit([1, 2, 3, 4])
    sb = eng.submit([9, 8, 7])
    for _ in range(4):
        eng.step()
    a_batched = eng.sessions[sa].tokens[:5]
    assert a_batched == a_solo[:5]


def test_cached_decode_matches_cache_free_forward(setup):
    """The serving reference (chip_smoke.py runs it at full width): logits
    decode produced through the KV cache equal those of one cache-free
    forward pass over prompt + generated tokens."""
    cfg, params = setup
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    batch = {"tokens": jnp.asarray([prompt], jnp.int32)}
    batch["labels"] = batch["tokens"]
    logits, state = prefill(cfg, params, batch, 32)
    tokens, rows = [int(jnp.argmax(logits[0, -1]))], []
    for _ in range(6):
        logits, state = decode_step(cfg, params, state,
                                    jnp.asarray([[tokens[-1]]], jnp.int32))
        rows.append(np.asarray(logits[0, -1]))
        tokens.append(int(jnp.argmax(logits[0, -1])))
    seq = jnp.asarray([prompt + tokens[:-1]], jnp.int32)
    want = np.asarray(forward_logits(cfg, params, seq)[0, len(prompt):])
    np.testing.assert_allclose(np.stack(rows), want, rtol=1e-4, atol=1e-4)


def test_write_slot_roundtrip(setup):
    cfg, params = setup
    from repro.models import init_decode_state
    pooled = init_decode_state(cfg, 4, 32)
    batch = {"tokens": jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32)}
    batch["labels"] = batch["tokens"]
    _, single = prefill(cfg, params, batch, 32)
    merged = _write_slot(pooled, single, 2)
    # decode from slot 2 of merged equals decode from the single state
    tok = jnp.asarray([[7]], jnp.int32)
    l_single, _ = decode_step(cfg, params, single, tok)
    toks4 = jnp.zeros((4, 1), jnp.int32).at[2, 0].set(7)
    l_merged, _ = decode_step(cfg, params, merged, toks4)
    np.testing.assert_allclose(np.asarray(l_merged[2], np.float32),
                               np.asarray(l_single[0], np.float32),
                               rtol=2e-4, atol=2e-4)


def test_slots_recycled(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, max_batch=1, max_seq=64)
    s1 = eng.submit([1, 2])
    slot1 = eng.sessions[s1].slot
    eng.finish(s1)                   # releases the slot (slot -> None)
    s2 = eng.submit([3, 4])          # must not raise: slot recycled
    assert eng.sessions[s2].slot == slot1
    assert eng.sessions[s1].slot is None


def _tiered_store(n_nodes, kv_bytes, slots_per_node=2):
    """hbm holds exactly the live slots; parked sessions land in bb."""
    return LocStore(n_nodes, hierarchy=tiered_hierarchy(
        hbm_bytes=slots_per_node * kv_bytes,
        host_bytes=slots_per_node * kv_bytes,
        bb_bytes=float(1 << 30)), write_policy="back")


def test_submit_registers_true_kv_bytes(setup):
    """The zero-byte-placeholder bugfix: capacity accounting must see the
    session cache's real size, not 0 bytes hidden in an xattr."""
    cfg, params = setup
    probe = ServingEngine(cfg, params, max_batch=2, max_seq=64)
    kv = probe.slot_bytes()
    assert kv > 0
    store = _tiered_store(1, kv)
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64, node=0,
                        store=store)
    sid = eng.submit([1, 2, 3])
    name = _cache_name(sid)
    assert store.getxattr(name, "size") == kv
    rep = store.tier_report()
    assert rep["hbm"]["resident_bytes"] == kv        # true bytes, top tier
    assert store.stat(name).tier_on(0) == "hbm"
    sid2 = eng.submit([4, 5])
    assert store.tier_report()["hbm"]["resident_bytes"] == 2 * kv
    eng.finish(sid)
    eng.finish(sid2)
    assert store.tier_report()["hbm"]["resident_bytes"] == 0.0


def test_session_lifecycle_submit_park_resume_finish(setup):
    cfg, params = setup
    probe = ServingEngine(cfg, params, max_batch=2, max_seq=64)
    kv = probe.slot_bytes()
    store = _tiered_store(1, kv)
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64, node=0,
                        store=store)
    control = ServingEngine(cfg, params, max_batch=2, max_seq=64)

    sid = eng.submit([5, 6, 7])
    c_sid = control.submit([5, 6, 7])
    for _ in range(2):
        eng.step()
        control.step()
    # idle-demote: the KV slice moves to the burst-buffer tier, slot frees
    eng.park(sid)
    name = _cache_name(sid)
    assert eng.sessions[sid].slot is None
    assert eng.can_admit()
    assert store.stat(name).tier_on(0) == "bb"
    assert store.tier_report()["bb"]["resident_bytes"] == kv
    # resume-promote: back to hbm, slot re-hydrated from the stored slice —
    # NO re-prefill, and decode continues bit-identically to never parking
    prefills_before = eng.prefills
    assert eng.resume(sid)
    assert eng.prefills == prefills_before
    assert eng.resumes == 1
    assert store.stat(name).tier_on(0) == "hbm"
    for _ in range(2):
        eng.step()
        control.step()
    assert eng.sessions[sid].tokens == control.sessions[c_sid].tokens
    # finish deletes the replica
    eng.finish(sid)
    assert not store.exists(name)
    assert store.tier_report()["hbm"]["resident_bytes"] == 0.0


def test_decode_state_is_donated_and_not_read_again(setup):
    """The pooled state handed to decode is donated: its buffers are gone
    after the step (a read would raise), the engine holds only the state it
    got back, and decoding goes on through park and resume."""
    cfg, params = setup
    store = LocStore(1, hierarchy=tiered_hierarchy())
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64, store=store)
    handed = []
    decode = eng.backend.decode

    def recording(p, state, tokens):
        handed.append(state)
        return decode(p, state, tokens)

    eng.backend.decode = recording
    sid = eng.submit([5, 6, 7])
    eng.step()
    eng.park(sid)
    eng.resume(sid)
    eng.step()
    assert len(handed) == 2
    for state in handed:
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(state))
        assert not any(a is b for a, b in zip(jax.tree.leaves(state),
                                              jax.tree.leaves(eng.state)))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(eng.state))
    assert len(eng.sessions[sid].tokens) == 3


def test_single_slot_write_copies_the_stored_slice(setup):
    """With one slot the written state is the slot: a copy, so that
    donating it to decode leaves the stored slice readable."""
    cfg, params = setup
    from repro.models import init_decode_state
    pooled = init_decode_state(cfg, 1, 32)
    batch = {"tokens": jnp.asarray([[3, 1, 4]], jnp.int32)}
    batch["labels"] = batch["tokens"]
    _, single = prefill(cfg, params, batch, 32)
    merged = _write_slot(pooled, single, 0)
    for a, b in zip(jax.tree.leaves(merged), jax.tree.leaves(single)):
        assert a is not b
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    jax.jit(lambda s: s, donate_argnums=0)(merged)
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(single))


def test_idle_slots_read_no_cache(setup):
    """A slot that holds no session goes to the step with token -1, and
    the step sets its cached length to 0 before it attends, so it reads
    none of the slot's cache however long it idles; live slots decode as a
    control that never shared the pool."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, max_batch=3, max_seq=64)
    control = ServingEngine(cfg, params, max_batch=3, max_seq=64)
    gone = eng.submit([9, 9, 9, 9, 9])
    sid = eng.submit([1, 2, 3])
    c_sid = control.submit([1, 2, 3])
    idle_slot = eng.sessions[gone].slot
    eng.step()
    eng.finish(gone)
    for _ in range(6):
        eng.step()
        control.step()
    pos = np.asarray(eng.state["pos"])
    assert pos[idle_slot] == 1         # cleared to 0, then one step
    assert pos[eng.sessions[sid].slot] == 3 + 7
    assert eng.sessions[sid].tokens[:7] == control.sessions[c_sid].tokens[:7]


def test_park_idle_sweep(setup):
    cfg, params = setup
    probe = ServingEngine(cfg, params, max_batch=2, max_seq=64)
    store = _tiered_store(1, probe.slot_bytes())
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64, node=0,
                        store=store)
    s1 = eng.submit([1, 2])
    s2 = eng.submit([3, 4])          # s2 touched after s1
    parked = eng.park_idle(max_idle=0)   # stale == anything but the newest
    assert parked == [s1]
    assert eng.sessions[s1].slot is None
    assert eng.sessions[s2].slot is not None


def test_read_slot_inverts_write_slot(setup):
    cfg, params = setup
    from repro.models import init_decode_state
    pooled = init_decode_state(cfg, 4, 32)
    template = init_decode_state(cfg, 1, 32)
    batch = {"tokens": jnp.asarray([[3, 1, 4]], jnp.int32)}
    batch["labels"] = batch["tokens"]
    _, single = prefill(cfg, params, batch, 32)
    merged = _write_slot(pooled, single, 2)
    back = _read_slot(merged, template, 2)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(single)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_router_routes_to_cache_holder(setup):
    cfg, params = setup
    store = LocStore(2)
    engines = [ServingEngine(cfg, params, max_batch=2, max_seq=64, node=i,
                             store=store) for i in range(2)]
    router = Router(engines, store)
    eng = router.engine_for()
    sid = eng.submit([1, 2, 3])
    # a follow-up for this session must land on the same engine
    again = router.engine_for(sid)
    assert again.node == eng.node
    assert router.locality_hits == 1
    # unknown session falls through to load balancing
    other = router.engine_for(99_999)
    assert router.locality_misses == 1
    assert other.can_admit()


def test_router_full_engine_locality_hit_falls_through(setup):
    """The PR 4 router bugfix: a locality hit whose engine cannot admit the
    session must fall through to load balancing (counted as a distinct
    locality_evictions stat) instead of letting the caller hit 'engine
    full'."""
    cfg, params = setup
    probe = ServingEngine(cfg, params, max_batch=1, max_seq=64)
    store = _tiered_store(2, probe.slot_bytes(), slots_per_node=1)
    engines = [ServingEngine(cfg, params, max_batch=1, max_seq=64, node=i,
                             store=store) for i in range(2)]
    e0, e1 = engines
    # e1 has a measured prefill cost and a free slot (a migrate target)
    warm = e1.submit([7, 7])
    e1.finish(warm)
    router = Router(engines, store, allow_park=False)   # flat-pinning rules
    sid = e0.submit([1, 2, 3])
    e0.park(sid)                     # parked: resuming needs a slot
    blocker = e0.submit([9, 9])      # ...but e0's only slot is taken
    assert not e0.can_admit()
    target = router.engine_for(sid)  # must NOT return the full holder
    assert target is e1
    assert router.locality_evictions == 1
    assert router.locality_hits == 0
    # follow_up completes the migration without an 'engine full' error
    hist = list(e0.sessions[sid].tokens)
    d = router.follow_up(sid, hist)
    assert d.engine is e1 and d.sid != sid
    assert d.kind == "migrate" and d.prefilled and not d.resumed
    assert router.migrations == 1
    assert e0.sessions[sid].done     # the holder dropped the stale session
    assert e0.sessions[blocker].slot is not None    # blocker untouched


def test_router_resumes_parked_session_by_parking_victim(setup):
    """With parking allowed and no cheap migrate target, a follow-up to a
    full engine parks the LRU victim and re-hydrates in place — zero
    re-prefills."""
    cfg, params = setup
    probe = ServingEngine(cfg, params, max_batch=1, max_seq=64)
    store = _tiered_store(2, probe.slot_bytes(), slots_per_node=1)
    engines = [ServingEngine(cfg, params, max_batch=1, max_seq=64, node=i,
                             store=store) for i in range(2)]
    e0, e1 = engines                 # e1 idle: no measured prefill -> inf
    router = Router(engines, store)
    sid = e0.submit([1, 2, 3])
    e0.park(sid)
    blocker = e0.submit([9, 9])
    prefills = e0.prefills
    d = router.follow_up(sid, [1, 2, 3])
    assert d.engine is e0 and d.sid == sid
    assert d.kind == "hit_parked" and d.resumed and not d.prefilled
    assert e0.sessions[sid].slot is not None         # re-hydrated
    assert e0.sessions[blocker].slot is None         # victim parked
    assert e0.prefills == prefills                   # no re-prefill
    assert router.locality_hits == 1
    assert e0.resumes == 1


def test_router_pressure_prefers_fast_migrate(setup):
    """Tier-awareness: when the parked cache sits behind a glacial medium,
    the priced resume loses to a re-prefill on a free engine."""
    cfg, params = setup
    probe = ServingEngine(cfg, params, max_batch=1, max_seq=64)
    kv = probe.slot_bytes()
    # burst buffer at 10 B/s: promoting the parked KV costs ~kv/10 seconds
    store = LocStore(2, hierarchy=StorageHierarchy(
        [TierSpec("hbm", kv, 819e9), TierSpec("bb", float(1 << 30), 10.0)],
        remote=TierSpec("remote", float("inf"), 2e9)))
    engines = [ServingEngine(cfg, params, max_batch=1, max_seq=64, node=i,
                             store=store) for i in range(2)]
    e0, e1 = engines
    warm = e1.submit([7, 7])         # measured (fast) prefill on e1
    e1.finish(warm)
    router = Router(engines, store)
    sid = e0.submit([1, 2, 3])
    e0.park(sid)
    assert e0.can_admit()            # a slot IS free: only cost disqualifies
    target = router.engine_for(sid)
    assert target is e1
    assert router.locality_evictions == 1


def test_router_warm_promotes_parked_cache(setup):
    cfg, params = setup
    probe = ServingEngine(cfg, params, max_batch=2, max_seq=64)
    store = _tiered_store(1, probe.slot_bytes())
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64, node=0,
                        store=store)
    prefetch = PrefetchEngine(store)
    router = Router([eng], store, prefetch=prefetch)
    sid = eng.submit([1, 2, 3])
    eng.park(sid)
    assert store.stat(_cache_name(sid)).tier_on(0) == "bb"
    assert router.warm(sid)
    prefetch.drain()
    assert store.stat(_cache_name(sid)).tier_on(0) == "hbm"
    assert router.warmups == 1
    assert not router.warm(99_999)   # unknown session: no-op
    prefetch.shutdown()
