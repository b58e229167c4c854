"""DeepSeek-V3 as the program serves it, against the benchmark's plain
reference (``bench/configs/deepseek-v3-671b.py``) on seeded random weights
at a small size: prefill and then decode through the donated latent pool;
the held expert shares of every EP rank summed; faults planted in the
router, the rope scaling and the dispatch; and arctic's softmax router."""

import copy
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from repro import obs  # noqa: E402
from repro.configs import get_smoke  # noqa: E402
from repro.core.config import ServingConfig  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models import mla as mla_mod  # noqa: E402
from repro.models import moe as moe_mod  # noqa: E402
from repro.serve.engine import ServingEngine  # noqa: E402

CONFIG = ROOT / "bench" / "configs" / "deepseek-v3-671b.json"
ref_mod = harness.load_module(CONFIG.with_suffix(".py"))

# the published shapes at a small size: 16 routed experts in 4 groups, 2 of
# them kept, 4 chosen per token; 2 experts and 4 heads held here
SMALL = {"hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
         "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "intermediate_size": 96, "moe_intermediate_size": 32,
         "n_routed_experts": 2, "num_experts_per_tok": 4, "n_group": 4,
         "topk_group": 2, "vocab_size": 503, "torch_dtype": "float32"}
PUBLISHED_SMALL = {"num_hidden_layers": 61, "first_k_dense_replace": 3,
                   "n_routed_experts": 16, "num_attention_heads": 16,
                   "vocab_size": 129280, "num_nextn_predict_layers": 1}
PROMPT, STEPS, MAX_SEQ, SLOTS = 40, 6, 64, 3
TOL = 2e-3               # f32 program against the f32 reference, in logits


def small_cfg(held=2, rank=1, **kw) -> dict:
    cfg = json.loads(CONFIG.read_text())
    cfg.update(SMALL, n_routed_experts=held, **kw)
    cfg["published"] = dict(PUBLISHED_SMALL)
    cfg["deployment"] = dict(cfg["deployment"], ep_rank=rank)
    return cfg


def served_logits(cfg: dict, seed: int, mcfg=None):
    """The program's logits over a prompt, then ``STEPS`` tokens decoded
    one by one through the pooled state (donated, ``SLOTS`` slots, the
    session in slot 1, the others idle), at every served position; and
    the sequence they were read at."""
    mcfg = mcfg or ref_mod.model_config(cfg)
    params = ref_mod.make_weights(cfg, seed)
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg["vocab_size"], PROMPT).tolist()
    logits, fresh = jax.jit(lambda p, b: M.prefill(mcfg, p, b, MAX_SEQ))(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)})
    from repro.serve.engine import _write_slot
    state = _write_slot(M.init_decode_state(mcfg, SLOTS, MAX_SEQ), fresh, 1)
    step = jax.jit(lambda p, s, t: M.decode_step(mcfg, p, s, t),
                   donate_argnums=(1,))
    out = [np.asarray(logits[0, -1])]
    seq = list(prompt)
    for _ in range(STEPS):
        tok = int(np.argmax(out[-1][:cfg["vocab_size"]]))
        seq.append(tok)
        tokens = np.full((SLOTS, 1), -1, np.int32)
        tokens[1, 0] = tok
        logits, state = step(params, state, jnp.asarray(tokens))
        out.append(np.asarray(logits[1, -1]))
    return np.stack(out)[:, :cfg["vocab_size"]], seq


def reference_logits(cfg: dict, seed: int, seq, *, fp8=False):
    ref = ref_mod.Reference(cfg, ref_mod.make_published(cfg, seed), fp8=fp8)
    rows = np.arange(PROMPT - 1, PROMPT + STEPS)
    return ref.logits(seq, rows)


def gap(cfg: dict, seed: int, mcfg=None) -> float:
    got, seq = served_logits(cfg, seed, mcfg)
    want = reference_logits(cfg, seed, seq)
    return float(np.abs(got - want).max())


@pytest.mark.parametrize("held,rank,impl", [
    (2, 1, "xla"), (16, 0, "xla"), (2, 1, "interpret"), (16, 0, "interpret")],
    ids=["share-of-8-ranks", "all-held", "share-of-8-ranks-kernel",
         "all-held-kernel"])
def test_prefill_then_pooled_decode_matches_the_reference(held, rank, impl,
                                                          monkeypatch):
    """Through the XLA einsums and through the MLA decode kernel (in
    interpret mode, as a TPU would run it)."""
    monkeypatch.setattr(M, "_pooled_attention_impl", lambda: impl)
    cfg = small_cfg(held, rank)
    got, seq = served_logits(cfg, 11)
    want = reference_logits(cfg, 11, seq)
    assert np.abs(got - want).max() < TOL
    assert want.std() > 0.3                 # logits that can tell faults
    # the float8 control is far from the program
    low = reference_logits(cfg, 11, seq, fp8=True)
    assert np.abs(low - want).max() > 20 * TOL


def test_held_shares_of_all_32_ranks_sum_to_the_uncut_layer():
    """One MoE layer of 64 experts, 2 held on each of 32 EP ranks: the
    ranks' outputs, the shared expert counted once, are the uncut layer."""
    cfg = small_cfg(64, 0, n_group=8, topk_group=4, num_experts_per_tok=8)
    cfg["published"]["n_routed_experts"] = 64
    pub = jax.jit(ref_mod._published(cfg))(ref_mod.seed_key(5))
    whole = ref_mod.fold(cfg, pub)["moe_blocks"]["moe"]
    layer = jax.tree.map(lambda a: a[0], whole)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 24, 64)),
                    jnp.float32)
    k = ref_mod.dims(cfg)
    w = jax.tree.map(lambda a: a[0].astype(jnp.float32), pub["moe"])
    xt = x[0]
    weight = ref_mod._route(k, w, xt, False)
    shared = ref_mod._mlp(w["shared"], xt, False)
    uncut = shared + sum(
        weight[:, e, None] * ref_mod._mlp(
            jax.tree.map(lambda t: t[e], w["experts"]), xt, False)
        for e in range(64))
    total = -31 * shared
    mcfg = ref_mod.model_config(cfg)
    for r in range(32):
        p = dict(layer, w1=layer["w1"][2 * r:2 * r + 2],
                 w3=layer["w3"][2 * r:2 * r + 2],
                 w2=layer["w2"][2 * r:2 * r + 2])
        rcfg = dataclasses.replace(mcfg, ep_size=32, ep_rank=r)
        out, _ = moe_mod.moe_ffn_serve(rcfg, p, x)
        total = total + out[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               rtol=1e-4, atol=1e-4)


def _bias_in_weights(cfg, router, bias, xt):
    """The published router, but weighting by score + bias."""
    topw, topi, probs = ROUTE(cfg, router, bias, xt)
    scores = jax.nn.sigmoid(xt.astype(jnp.float32) @ router)
    w = jnp.take_along_axis(scores + bias, topi, -1)
    return cfg.routed_scale * w / w.sum(-1, keepdims=True), topi, probs


def _dropped_at_capacity(cfg, p, x):
    """The training dispatch in the server's place: tokens beyond 1.25x an
    expert's mean load are dropped."""
    out, _ = moe_mod._moe_ffn_global(dataclasses.replace(
        cfg, capacity_factor=1.25), p, x)
    topi = moe_mod.route(cfg, p["router"], p["router_bias"],
                         x.reshape(-1, x.shape[-1]))[1]
    return out, topi.reshape(*x.shape[:2], -1)


ROUTE = moe_mod.route
FAULTS = {
    "softmax-router": lambda m, mp: dataclasses.replace(
        m, router_score="softmax"),
    "no-group-limit": lambda m, mp: dataclasses.replace(m, n_groups=1),
    "bias-in-weights": lambda m, mp: mp.setattr(moe_mod, "route",
                                                _bias_in_weights) or m,
    "mscale-missing": lambda m, mp: mp.setattr(
        mla_mod, "yarn_softmax_factor", lambda y: 1.0) or m,
    "dropped-at-capacity": lambda m, mp: mp.setattr(
        moe_mod, "moe_ffn_serve", _dropped_at_capacity) or m,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_fails_the_comparison(fault, monkeypatch):
    cfg = small_cfg(16, 0)
    mcfg = FAULTS[fault](ref_mod.model_config(cfg), monkeypatch)
    assert gap(cfg, 11, mcfg) > 10 * TOL


def test_arctic_keeps_softmax_top2():
    cfg = dataclasses.replace(get_smoke("arctic-480b"), dtype="float32")
    assert cfg.router_score == "softmax" and cfg.experts_per_token == 2
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    xt = jnp.asarray(np.random.default_rng(1).normal(size=(16, cfg.d_model)),
                     jnp.float32)
    w, ids, _ = moe_mod.route(cfg, p["router"], p.get("router_bias"), xt)
    probs = jax.nn.softmax(xt @ p["router"], -1)
    top = np.argsort(-np.asarray(probs), -1)[:, :2]
    np.testing.assert_array_equal(np.sort(np.asarray(ids), -1),
                                  np.sort(top, -1))
    pw = np.take_along_axis(np.asarray(probs), top, -1)
    np.testing.assert_allclose(np.sort(np.asarray(w), -1),
                               np.sort(pw / pw.sum(-1, keepdims=True), -1),
                               rtol=1e-5)


def test_engine_counts_the_experts_a_step_touches(tmp_path):
    """Two sessions on a 3-slot engine: each step counts the held experts
    its live sequences chose and the picks that landed on them, as the
    state's ``routed`` leaf records them for the live slots only."""
    cfg = small_cfg(2, 1)
    mcfg = ref_mod.model_config(cfg)
    eng = ServingEngine(mcfg, ref_mod.make_weights(cfg, 3),
                        config=ServingConfig(max_batch=3, max_seq=MAX_SEQ))
    a = eng.submit(list(range(1, 20)))
    eng.submit(list(range(30, 45)))
    obs.reset()
    want = [0, 0]
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            eng.step()
            ids = np.asarray(eng.state["routed"])
            live = [s.slot for s in eng._slotted.values()]
            got = ids[:, live]
            held = (got >= 2) & (got < 4)
            want[0] += sum(len(set(layer[h].tolist()))
                           for layer, h in zip(got, held))
            want[1] += int(held.sum())
    c = obs.summary()["counters"]
    obs.reset()
    assert c["engine.decode_steps"] == 3 and c["engine.decode_tokens"] == 6
    assert (c["moe.experts_touched"], c["moe.tokens_held"]) == tuple(want)
    assert c["moe.tokens_held"] > 0
    assert eng.state["routed"].shape == (2, 3, 4)
    eng.finish(a)


def test_engine_counts_the_latent_blocks_a_step_reads(tmp_path):
    """Each step counts the latent blocks the MLA kernel fetches over the
    dense and MoE layers, for the slots' cached lengths (an idle slot
    fetches one), and the blocks the pools hold."""
    from repro.kernels.decode_attention import block_size, blocks_fetched
    from repro.kernels.mla_decode import DEFAULT_BK
    cfg = small_cfg(2, 1)
    mcfg = ref_mod.model_config(cfg)
    eng = ServingEngine(mcfg, ref_mod.make_weights(cfg, 3),
                        config=ServingConfig(max_batch=3, max_seq=MAX_SEQ))
    eng.submit(list(range(1, 20)))
    eng.submit(list(range(30, 45)))
    L, bk = mcfg.n_layers, block_size(MAX_SEQ, DEFAULT_BK)
    obs.reset()
    want = [0, 0]
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            lengths = [0] * 3
            for s in eng._slotted.values():
                lengths[s.slot] = s.prompt_len + len(s.tokens) - 1
            want[0] += L * blocks_fetched(lengths, MAX_SEQ, DEFAULT_BK)
            want[1] += L * 3 * MAX_SEQ // bk
            eng.step()
    c = obs.summary()["counters"]
    obs.reset()
    assert (c["engine.kv_blocks_read"], c["engine.kv_blocks_pool"]) == \
        tuple(want)
    assert c["engine.kv_blocks_read"] == 3 * L * 3   # one block a slot


def test_latent_kernel_runs_on_a_tpu_without_a_mesh(monkeypatch):
    """The MLA decode takes the kernel where the pooled steps do, and the
    XLA einsums under a mesh: the rules put the latent pool's positions on
    "model", even on a 1x1 mesh."""
    from repro.dist.hints import sharding_rules
    from repro.dist.mesh import make_local_mesh
    mcfg = ref_mod.model_config(small_cfg())
    assert M._latent_kernel(mcfg) is None                   # the CPU
    monkeypatch.setattr(M, "_pooled_attention_impl", lambda: "pallas")
    assert M._latent_kernel(mcfg).keywords == {
        "softmax_scale": mla_mod.softmax_scale(mcfg), "interpret": False}
    with sharding_rules(make_local_mesh(1, 1)):
        assert M._latent_kernel(mcfg) is None


def test_published_config_routes_as_deepseek_v3():
    from repro.configs import get_config
    c = get_config("deepseek-v3-671b")
    assert (c.router_score, c.n_groups, c.topk_groups, c.routed_scale) == \
        ("sigmoid", 8, 4, 2.5)
    assert c.rope_scaling.factor == 40.0
    assert mla_mod.softmax_scale(c) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2)
    # the frequencies match the reference's float64 YaRN
    from repro.models.layers import rope_frequencies
    k = ref_mod.dims(json.loads(CONFIG.read_text()))
    np.testing.assert_allclose(
        np.asarray(rope_frequencies(64, 1e4, c.rope_scaling)),
        ref_mod.yarn_inv_freq(k), rtol=1e-6)


def test_benchmark_config_states_its_cut():
    cfg = json.loads(CONFIG.read_text())
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert set(entry["reduced"]) == set(cfg["published"])
    for key in entry["reduced"]:
        assert cfg[key] != cfg["published"][key]
    m = ref_mod.model_config(copy.deepcopy(cfg))
    assert (m.n_experts, m.experts_held, m.n_heads, m.mtp_depth) == \
        (256, (0, 8), 32, 0)
