"""Mixture-of-Experts layer: the router, and two dispatches.

Covers both assigned MoE architectures:
  * deepseek-v3-671b: 1 shared expert + 256 routed, top-8, with the published
    router (``router_score="sigmoid"``, :func:`route`), first 3 layers dense.
  * arctic-480b: 128 routed top-2 (softmax, renormalised) + a *dense
    residual* FFN in parallel.

Serving (:func:`moe_ffn_serve`, prefill and decode) drops no token. The
layer holds the routed experts ``cfg.experts_held`` of the ``n_experts`` it
routes over (one chip's share under expert parallelism; all of them by
default): each held expert runs on every token, and a token's result is
weighted by its routing weight for that expert, zero where it did not choose
it. What experts held elsewhere would add is left out.

Training (:func:`moe_ffn`) uses the GShard/Switch capacity scheme — top-k
per token, position within expert via a sort, scatter to (E, C, d), expert
einsum, gather back. This is dense-shape, compiles under pjit, and shards
cleanly with experts on the "model" axis (EP) and tokens on "data" — the
all-to-all shows up explicitly in the dry-run collective accounting. Tokens
beyond an expert's capacity are dropped there.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.layers import init_linear

Pytree = Any


def init_moe(key: jax.Array, cfg: ModelConfig, dtype, n_layers: int = 1) -> Pytree:
    d, ff = cfg.d_model, cfg.moe_d_ff
    E = cfg.experts_held[1]
    ks = jax.random.split(key, 5)
    out_scale = 1.0 / np.sqrt(ff) / np.sqrt(2.0 * n_layers)
    p = {
        # the router scores every routed expert, held here or not (f32)
        "router": init_linear(ks[0], d, cfg.n_experts, jnp.float32),
        "w1": (0.02 * jax.random.normal(ks[1], (E, d, ff), jnp.float32)
               ).astype(dtype),
        "w3": (0.02 * jax.random.normal(ks[2], (E, d, ff), jnp.float32)
               ).astype(dtype),
        "w2": (out_scale * jax.random.normal(ks[3], (E, ff, d), jnp.float32)
               ).astype(dtype),
    }
    if cfg.router_score == "sigmoid":
        # selection bias per routed expert (DeepSeek-V3's
        # e_score_correction_bias); it never enters the weights
        p["router_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
    if cfg.n_shared_experts:
        ff_s = ff * cfg.n_shared_experts
        kk = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w1": init_linear(kk[0], d, ff_s, dtype),
            "w3": init_linear(kk[1], d, ff_s, dtype),
            "w2": init_linear(kk[2], ff_s, d, dtype, scale=out_scale),
        }
    return p


def _capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(np.ceil(cfg.capacity_factor * n_tokens * cfg.experts_per_token
                    / cfg.n_experts))
    return max(8, int(np.ceil(c / 8)) * 8)


def route(cfg: ModelConfig, router: jax.Array, bias: jax.Array | None,
          xt: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Each token's experts and their weights. xt: (T, d) ->
    (weights (T, k) f32, expert ids (T, k) over all ``n_experts``, per-expert
    probabilities (T, E) f32 for the load-balancing loss).

    ``softmax``: the top k of the softmax, renormalised. ``sigmoid``
    (DeepSeek-V3, ``noaux_tc``): scores are sigmoids; experts are chosen by
    score + ``bias``, only within the ``topk_groups`` of ``n_groups`` groups
    whose two best biased scores sum highest; the weights are the chosen
    experts' scores without the bias, normalised to sum 1, times
    ``routed_scale``."""
    k = cfg.experts_per_token
    logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)   # (T, E)
    if cfg.router_score == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        topw, topi = jax.lax.top_k(probs, k)
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
        return topw * cfg.routed_scale, topi, probs
    scores = jax.nn.sigmoid(logits)
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    if cfg.n_groups > 1:
        T, E = choice.shape
        g = choice.reshape(T, cfg.n_groups, E // cfg.n_groups)
        group_score = jax.lax.top_k(g, 2)[0].sum(-1)                # (T, G)
        _, gi = jax.lax.top_k(group_score, cfg.topk_groups)
        keep = jax.nn.one_hot(gi, cfg.n_groups, dtype=jnp.bool_).any(1)
        choice = jnp.where(keep[:, :, None], g, -jnp.inf).reshape(T, E)
    _, topi = jax.lax.top_k(choice, k)
    topw = jnp.take_along_axis(scores, topi, axis=-1)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-20)
    probs = scores / scores.sum(-1, keepdims=True)
    return topw * cfg.routed_scale, topi, probs


def _shared(p: Pytree, xt: jax.Array) -> jax.Array:
    sp = p["shared"]
    return (jax.nn.silu(xt @ sp["w1"]) * (xt @ sp["w3"])) @ sp["w2"]


def moe_ffn_serve(cfg: ModelConfig, p: Pytree, x: jax.Array
                  ) -> tuple[jax.Array, jax.Array]:
    """The serving dispatch, which drops no token. x: (B, S, d) ->
    (out (B, S, d), each token's chosen expert ids (B, S, k) over all
    ``n_experts``).

    Every held expert runs on every token; a token's result from it is
    weighted by the token's routing weight for it, zero where the token did
    not choose it. The shared expert is added unweighted."""
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    topw, topi, _ = route(cfg, p["router"], p.get("router_bias"), xt)
    first, n = cfg.experts_held
    chose = topi[:, :, None] == first + jnp.arange(n)             # (T, k, n)
    comb = jnp.sum(jnp.where(chose, topw[:, :, None], 0.0), axis=1)  # (T, n)
    h = jnp.einsum("td,edf->etf", xt, p["w1"])
    g = jnp.einsum("td,edf->etf", xt, p["w3"])
    y = jnp.einsum("etf,efd->etd", jax.nn.silu(h) * g, p["w2"])
    out = jnp.einsum("te,etd->td", comb.astype(y.dtype), y,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    if "shared" in p:
        out = out + _shared(p, xt)
    return out.reshape(B, S, d), topi.reshape(B, S, -1)


def moe_ffn(cfg: ModelConfig, p: Pytree, x: jax.Array
            ) -> tuple[jax.Array, jax.Array]:
    """The training dispatch. x: (B, S, d) -> (out (B, S, d), router aux loss
    scalar f32). The layer must hold every routed expert.

    Under an active mesh (dist.hints.sharding_rules) the routed experts run
    through the shard_map path (local dispatch + psum combine — see
    :func:`_routed_shard_map`); the global-shape path below is the reference
    used on unmeshed CPU runs and as the numerical oracle in tests.
    """
    from repro.dist import hints as hint_rules
    if cfg.ep_size != 1:
        raise ValueError("the training dispatch needs every routed expert "
                         f"held; {cfg.name} holds {cfg.experts_held}")
    r = hint_rules.get_rules()
    if r is not None and r.get("mesh") is not None:
        return _moe_ffn_sharded(cfg, p, x, r)
    return _moe_ffn_global(cfg, p, x)


def _moe_ffn_global(cfg: ModelConfig, p: Pytree, x: jax.Array
                    ) -> tuple[jax.Array, jax.Array]:
    B, S, d = x.shape
    T = B * S
    E, k = cfg.n_experts, cfg.experts_per_token
    C = _capacity(cfg, T)
    xt = x.reshape(T, d)

    topw, topi, probs = route(cfg, p["router"], p.get("router_bias"), xt)

    # Position of each (token, slot) inside its expert, via a single stable
    # sort over the T*k flat assignments. (The obvious per-slot one-hot
    # cumsum materializes (T, E) int32 per slot — measured at ~1 TB of
    # transient traffic per MoE layer on the deepseek train_4k dry-run cell;
    # the sort keeps everything O(T*k). See EXPERIMENTS §Perf.)
    e_flat = topi.reshape(-1)                                  # (T*k,)
    order = jnp.argsort(e_flat, stable=True)
    counts = jnp.zeros((E,), jnp.int32).at[e_flat].add(1)
    seg_start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos_sorted = jnp.arange(T * k, dtype=jnp.int32) - seg_start[
        e_flat[order]]
    pos_flat = jnp.zeros((T * k,), jnp.int32).at[order].set(pos_sorted)
    keep_f = pos_flat < C                                      # capacity drop

    # scatter tokens -> (E*C, d)
    flat_idx = e_flat * C + pos_flat                           # (T*k,)
    from repro.dist.hints import hint
    src = jnp.repeat(xt, k, axis=0) * keep_f[:, None].astype(x.dtype)
    disp = jnp.zeros((E * C, d), x.dtype).at[
        jnp.where(keep_f, flat_idx, E * C - 1)].add(
            jnp.where(keep_f[:, None], src, 0))
    disp = hint(disp.reshape(E, C, d), "tp", "dp", None)       # EP + capacity on dp

    # expert FFN (einsum over experts)
    h = hint(jnp.einsum("ecd,edf->ecf", disp, p["w1"]), "tp", "dp", None)
    g = hint(jnp.einsum("ecd,edf->ecf", disp, p["w3"]), "tp", "dp", None)
    y = hint(jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * g, p["w2"]),
             "tp", "dp", None)

    # gather back with routing weights; pin the gather result to the token
    # layout up front — without it SPMD "involuntarily fully rematerializes"
    # (replicates) the combine gather between the (E,C) and token shardings.
    picked = hint(y.reshape(E * C, d)[flat_idx], "dp", None)   # (T*k, d)
    w = (topw.reshape(-1) * keep_f).astype(x.dtype)
    out = (picked * w[:, None]).reshape(T, k, d).sum(axis=1)
    out = hint(out, "dp", None)

    # load-balancing aux loss (Switch): E * sum_e f_e * P_e
    frac = counts.astype(jnp.float32) / jnp.maximum(T * k, 1)
    mean_prob = probs.mean(0)
    aux = E * jnp.sum(frac * mean_prob) * cfg.router_aux_weight

    if "shared" in p:
        out = out + _shared(p, xt)
    return out.reshape(B, S, d), aux


# ------------------------------------------------------------- shard_map path
def _moe_ffn_sharded(cfg: ModelConfig, p: Pytree, x: jax.Array, rules: dict
                     ) -> tuple[jax.Array, jax.Array]:
    """Routed experts via shard_map — the TPU-native dispatch.

    Key observations (measured on the deepseek-v3 train_4k dry-run cell; see
    EXPERIMENTS §Perf):
      * under pjit auto-sharding, the global-capacity scatter dispatch lowers
        to full-buffer all-reduces (2+ GiB × layers × microbatches) plus
        "involuntary full rematerialization" gathers;
      * activations are replicated across the model axis anyway, so each
        (data, model) device can dispatch its LOCAL tokens to its LOCAL
        experts with a per-shard capacity — no dispatch communication at all;
      * the only cross-device traffic left is (a) the FSDP weight all_gather
        (whose AD transpose is automatically a reduce-scatter of the expert
        grads — the thing the SPMD partitioner refused to emit) and (b) one
        psum of the (T_local, d) combined output over the model axis.
    Capacity semantics shift from global to per-(data-shard, expert) — the
    standard per-device capacity used by production MoE systems.
    """
    from jax.sharding import PartitionSpec as P

    mesh = rules["mesh"]
    dp_axes = rules["dp"] or ()
    tp = rules["tp"]
    E, k = cfg.n_experts, cfg.experts_per_token
    B, S, d = x.shape
    tp_size = rules["tp_size"] if tp else 1
    dp_size = rules["dp_size"]
    if E % tp_size != 0 or (B * S) % max(dp_size, 1) != 0:
        return _moe_ffn_global(cfg, p, x)

    T_loc = B * S // max(dp_size, 1)
    C_loc = _capacity(cfg, T_loc)
    E_loc = E // tp_size

    dp_spec = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    in_specs = (P(dp_spec, None, None),          # x: tokens over dp
                P(None, None),                   # router (replicated inside)
                P(None),                         # router bias
                P(tp, None, "data"),             # w1 (E, d, ff)
                P(tp, None, "data"),             # w3
                P(tp, "data", None))             # w2 (E, ff, d)
    out_specs = (P(dp_spec, None, None), P())

    def local_fn(x_loc, router, bias, w1, w3, w2):
        Bl, Sl, _ = x_loc.shape
        xt = x_loc.reshape(Bl * Sl, d)
        Tl = Bl * Sl

        topw, topi, probs = route(cfg, router, bias, xt)

        e_flat = topi.reshape(-1)                          # (Tl*k,)
        order = jnp.argsort(e_flat, stable=True)
        counts = jnp.zeros((E,), jnp.int32).at[e_flat].add(1)
        seg_start = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
        pos_flat = jnp.zeros((Tl * k,), jnp.int32).at[order].set(
            jnp.arange(Tl * k, dtype=jnp.int32) - seg_start[e_flat[order]])

        # this model rank dispatches only its expert range
        rank = jax.lax.axis_index(tp) if tp else 0
        lo = rank * E_loc
        keep = (e_flat >= lo) & (e_flat < lo + E_loc) & (pos_flat < C_loc)
        slot = jnp.where(keep, (e_flat - lo) * C_loc + pos_flat,
                         E_loc * C_loc)                    # overflow slot
        src = jnp.repeat(xt, k, axis=0) * keep[:, None].astype(xt.dtype)
        disp = jnp.zeros((E_loc * C_loc + 1, d), xt.dtype
                         ).at[slot].add(src)[:-1].reshape(E_loc, C_loc, d)

        # FSDP gather of the local experts' weights (AD: reduce-scatter grads)
        if dp_axes:
            w1f = jax.lax.all_gather(w1, "data", axis=2, tiled=True)
            w3f = jax.lax.all_gather(w3, "data", axis=2, tiled=True)
            w2f = jax.lax.all_gather(w2, "data", axis=1, tiled=True)
        else:
            w1f, w3f, w2f = w1, w3, w2
        h = jnp.einsum("ecd,edf->ecf", disp, w1f)
        g = jnp.einsum("ecd,edf->ecf", disp, w3f)
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * g, w2f)

        yf = jnp.concatenate([y.reshape(E_loc * C_loc, d),
                              jnp.zeros((1, d), y.dtype)], axis=0)
        picked = yf[slot]                                  # (Tl*k, d)
        w = (topw.reshape(-1) * keep).astype(xt.dtype)
        part = (picked * w[:, None]).reshape(Tl, k, d).sum(axis=1)
        out = jax.lax.psum(part, tp) if tp else part       # combine experts

        frac = counts.astype(jnp.float32) / jnp.maximum(Tl * k, 1)
        aux = E * jnp.sum(frac * probs.mean(0)) * cfg.router_aux_weight
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes if len(dp_axes) > 1
                                else dp_axes[0])
        return out.reshape(Bl, Sl, d), aux

    out, aux = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(
        x, p["router"].astype(jnp.float32),
        p.get("router_bias", jnp.zeros((E,), jnp.float32)), p["w1"], p["w3"],
        p["w2"])

    if "shared" in p:
        out = out + _shared(p, x.reshape(B * S, d)).reshape(B, S, d)
    return out, aux
