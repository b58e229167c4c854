"""Unified model API for the 10 assigned architectures.

Public surface (all pure functions of a frozen :class:`ModelConfig`):

  init_params(cfg, key)                 -> param pytree (stacked layers)
  loss_fn(cfg, params, batch)           -> (loss, metrics)     [train_step core]
  prefill(cfg, params, batch, max_seq)  -> (last_logits, decode_state)
  init_decode_state(cfg, batch, max_seq)-> decode_state        [for dry-run]
  decode_step(cfg, params, state, tok)  -> (logits, decode_state)
  forward_logits(cfg, params, tokens)   -> (B,S,V) logits, no cache [dense]
  param_count(cfg) / active_param_count(cfg)

Batch convention: ``{"tokens": (B,S) i32, "labels": (B,S) i32}`` plus
``"frames": (B, n_frames, d)`` for encdec (whisper — audio frontend stubbed to
precomputed frame embeddings) and ``"patches": (B, n_patches, d)`` for vlm
(llama-3.2-vision — patch embeddings stubbed likewise).

Implementation notes
  * layers are stacked and driven by ``lax.scan`` (small HLO, fast compiles at
    61-100 layers) with per-layer remat (``nothing_saveable``) during training;
  * decode keeps KV/SSM caches in the scan *carry* and updates slices in place
    (single cache buffer; pairs with buffer donation in the serve step). The
    pooled ``dense``/``localglobal`` step instead reads its (L, B, S, Hkv*hd)
    pool from outside the scan and writes the step's tokens after it, as the
    MLA ``moe`` step (deepseek-v3) does with its (L, B, S, kv_lora) and
    (L, B, S, rope) latent pools;
  * architectures with periodic special layers (zamba2 shared attention,
    llama-vision cross-attention) scan over *groups* so special-layer params
    and caches have exact shapes (no dead weights);
  * vocab sizes are padded to a multiple of 256 for clean TP sharding; padded
    logits are masked to -inf in the loss/decode heads.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (AttnDims, apply_rope, attention,
                                 cross_attention_block, decode_attention,
                                 init_attn, init_mlp, mlp_block, rms_norm,
                                 softmax_xent, init_linear,
                                 uniform_scale_init)

Pytree = Any

# --------------------------------------------------------------------- misc
REMAT_POLICY = jax.checkpoint_policies.nothing_saveable


def _remat(fn):
    return jax.checkpoint(fn, policy=REMAT_POLICY)


def padded_vocab(cfg: ModelConfig) -> int:
    return int(np.ceil(cfg.vocab / 256) * 256)


def _logit_mask(cfg: ModelConfig) -> jax.Array | float:
    vp = padded_vocab(cfg)
    if vp == cfg.vocab:
        return 0.0
    return jnp.where(jnp.arange(vp) < cfg.vocab, 0.0, -1e30)


def _dims(cfg: ModelConfig) -> AttnDims:
    return AttnDims(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd)


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def stacked_init(fn, key: jax.Array, n: int) -> Pytree:
    return jax.vmap(fn)(jax.random.split(key, n))


def _positions(tokens: jax.Array) -> jax.Array:
    B, S = tokens.shape
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))


def _embed_init(cfg: ModelConfig, key: jax.Array) -> Pytree:
    dt = _dtype(cfg)
    k1, k2 = jax.random.split(key)
    p = {"tok": uniform_scale_init(k1, (padded_vocab(cfg), cfg.d_model), dt)}
    if not cfg.tie_embeddings:
        p["head"] = init_linear(k2, cfg.d_model, padded_vocab(cfg), dt)
    return p


def _embed(p: Pytree, tokens: jax.Array) -> jax.Array:
    from repro.dist.hints import hint
    h = jnp.take(p["embed"]["tok"], tokens, axis=0)
    return hint(h, "dp", *([None] * (h.ndim - 1)))


def _head(cfg: ModelConfig, p: Pytree, h: jax.Array) -> jax.Array:
    from repro.dist.hints import hint
    e = p["embed"]
    w = e["head"] if "head" in e else e["tok"].T
    return hint((h @ w), "dp", None, "tp") + _logit_mask(cfg)


def _lm_loss(cfg: ModelConfig, logits: jax.Array, labels: jax.Array):
    return softmax_xent(logits, labels)


# ============================================================ dense / gemma3
def _windows(cfg: ModelConfig) -> np.ndarray:
    """Per-layer sliding window (0 = full attention)."""
    L = cfg.n_layers
    if cfg.family != "localglobal":
        return np.zeros((L,), np.int32)
    w = np.full((L,), cfg.sliding_window, np.int32)
    w[cfg.global_every - 1::cfg.global_every] = 0        # 1 global per group
    return w


def _dense_block_init(cfg: ModelConfig, key: jax.Array) -> Pytree:
    dt = _dtype(cfg)
    k1, k2 = jax.random.split(key)
    return {"ln1": jnp.zeros((cfg.d_model,), dt),
            "attn": init_attn(k1, _dims(cfg), dt, cfg.n_layers),
            "ln2": jnp.zeros((cfg.d_model,), dt),
            "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, dt, cfg.n_layers)}


def _dense_init(cfg: ModelConfig, key: jax.Array) -> Pytree:
    ke, kb, kf = jax.random.split(key, 3)
    return {"embed": _embed_init(cfg, ke),
            "blocks": stacked_init(partial(_dense_block_init, cfg), kb,
                                   cfg.n_layers),
            "final_norm": jnp.zeros((cfg.d_model,), _dtype(cfg))}


def _gqa_layer(cfg: ModelConfig, p: Pytree, h: jax.Array, positions, window,
               *, build_cache: int = 0):
    """One GQA decoder layer. If build_cache>0, also return (k, v) padded to
    that capacity."""
    dims = _dims(cfg)
    B, S, _ = h.shape
    hn = rms_norm(h, p["ln1"], cfg.norm_eps)
    q = (hn @ p["attn"]["wq"]).reshape(B, S, dims.n_heads, dims.hd)
    k = (hn @ p["attn"]["wk"]).reshape(B, S, dims.n_kv_heads, dims.hd)
    v = (hn @ p["attn"]["wv"]).reshape(B, S, dims.n_kv_heads, dims.hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    o = attention(q, k, v, q_pos=positions, k_pos=positions, causal=True,
                  window=window)
    from repro.dist.hints import hint
    h = h + o.reshape(B, S, dims.n_heads * dims.hd) @ p["attn"]["wo"]
    h = hint(h, "dp", "sp_seq", None)     # Megatron-SP residual (opt-in)
    h = h + mlp_block(p["mlp"], rms_norm(h, p["ln2"], cfg.norm_eps))
    h = hint(h, "dp", "sp_seq", None)
    if build_cache:
        pad = build_cache - S
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return h, (kc, vc)
    return h


def _dense_hidden(cfg: ModelConfig, params: Pytree, tokens: jax.Array):
    h = _embed(params, tokens)
    positions = _positions(tokens)
    windows = jnp.asarray(_windows(cfg))

    body = _remat(lambda p, h, w: _gqa_layer(cfg, p, h, positions, w))

    def step(h, pw):
        p, w = pw
        return body(p, h, w), None

    h, _ = jax.lax.scan(step, h, (params["blocks"], windows))
    return rms_norm(h, params["final_norm"], cfg.norm_eps)


def _dense_train(cfg: ModelConfig, params: Pytree, batch: Pytree):
    h = _dense_hidden(cfg, params, batch["tokens"])
    logits = _head(cfg, params, h)
    loss = _lm_loss(cfg, logits, batch["labels"])
    return loss, {"loss": loss}


def _dense_prefill(cfg: ModelConfig, params: Pytree, batch: Pytree,
                   max_seq: int):
    tokens = batch["tokens"]
    B, S = tokens.shape
    h = _embed(params, tokens)
    positions = _positions(tokens)
    windows = jnp.asarray(_windows(cfg))

    def step(h, pw):
        p, w = pw
        h, kv = _gqa_layer(cfg, p, h, positions, w, build_cache=max_seq)
        return h, tuple(c.reshape(B, max_seq, -1) for c in kv)

    h, (ck, cv) = jax.lax.scan(step, h, (params["blocks"], windows))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _head(cfg, params, h[:, -1:])
    state = {"pos": jnp.full((B,), S, jnp.int32), "k": ck, "v": cv}
    return logits, state


def _dense_decode_state(cfg: ModelConfig, batch: int, max_seq: int) -> Pytree:
    """The pooled decode state: ``pos`` (B,) is each slot's cached length;
    ``k``/``v`` are (L, B, S, Hkv*hd), the kv heads side by side in the
    minor dimension (the layout ``kernels.decode_attention`` reads)."""
    dims = _dims(cfg)
    shape = (cfg.n_layers, batch, max_seq, dims.n_kv_heads * dims.hd)
    return {"pos": jnp.zeros((batch,), jnp.int32),
            "k": jnp.zeros(shape, _dtype(cfg)),
            "v": jnp.zeros(shape, _dtype(cfg))}


_POOLED_FAMILIES = ("dense", "localglobal")   # decode reads a pooled cache


def pooled_cache_axes(cfg: ModelConfig) -> dict | None:
    """Where the pooled K/V cache keeps its axes, for ``dist.sharding``: the
    slot (``b_dim``), the position (``s_dim``) and the channel (``h_dim``)
    that holds ``heads`` kv heads side by side. None for a family whose
    decode state is not pooled."""
    if cfg.family not in _POOLED_FAMILIES:
        return None
    return {"b_dim": 1, "s_dim": 2, "h_dim": 3, "heads": cfg.n_kv_heads}


def pooled_kv_blocks(cfg: ModelConfig, lengths, max_seq: int
                     ) -> tuple[int, int] | None:
    """Blocks of K (as many again of V) that one pooled decode step fetches
    over its layers, each with its window, for these per-slot cached
    lengths; and the blocks the pool holds over its layers. For MLA, the
    blocks of the latent pool (its rope pool alongside) over the dense and
    MoE layers. None for a family whose decode state is not pooled."""
    if cfg.mla is not None:
        from repro.kernels import mla_decode
        from repro.kernels.decode_attention import block_size, blocks_fetched
        bk = mla_decode.DEFAULT_BK
        per_slot = max_seq // block_size(max_seq, bk)
        return (cfg.n_layers * blocks_fetched(lengths, max_seq, bk),
                cfg.n_layers * len(lengths) * per_slot)
    if cfg.family not in _POOLED_FAMILIES:
        return None
    from repro.kernels import decode_attention as kernel
    windows, layers = np.unique(_windows(cfg), return_counts=True)
    read = sum(int(n) * kernel.blocks_fetched(lengths, max_seq, window=int(w))
               for w, n in zip(windows, layers))
    per_slot = max_seq // kernel.block_size(max_seq)
    return read, cfg.n_layers * len(lengths) * per_slot


def _pooled_attention_impl() -> str:
    """How the pooled decode steps attend: the Pallas kernels ("pallas") on
    a TPU backend, XLA ("xla") elsewhere; GQA's and MLA's alike."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _pooled_kernel(cfg: ModelConfig, pool: jax.Array):
    """The decode kernel for this pool, or None where the XLA path attends.

    Under a mesh (``dist.hints.sharding_rules``) the kernel runs on each
    device's shard of the pool, as ``dist.sharding.decode_state_specs``
    places it: slots on the DP axes, kv heads on "model" where they divide
    it. A pool sharded along its positions (kv heads that do not divide
    "model", or one slot over DP) would need a softmax merged across chips,
    so there the XLA path attends, in that same layout."""
    impl = _pooled_attention_impl()
    if impl == "xla":
        return None
    from repro.dist.hints import get_rules
    from repro.dist.sharding import decode_state_specs
    from repro.kernels.decode_attention import decode_attention
    kernel = partial(decode_attention, interpret=impl == "interpret")
    rules = get_rules()
    if rules is None:
        return kernel
    _, b, s, c = decode_state_specs(cfg, {"k": pool}, rules["mesh"])["k"]
    if s is not None:
        return None
    P = jax.sharding.PartitionSpec
    kv = P(None, b, None, c)
    return jax.shard_map(
        kernel, mesh=rules["mesh"],
        in_specs=(P(b, c, None), kv, kv, P(b, c), P(b, c), P(b), P(), P()),
        out_specs=P(b, c, None), check_vma=False)


def _latent_kernel(cfg: ModelConfig):
    """The MLA decode kernel, with its options bound, or None where the XLA
    einsums of ``mla.mla_decode_pooled`` attend: off a TPU, and under a mesh
    (``dist.hints.sharding_rules``), whose rules put the latent pool's
    positions on "model" (the latent has no heads to take it), so that the
    softmax would have to merge across chips."""
    from repro.dist.hints import get_rules
    impl = _pooled_attention_impl()
    if impl == "xla" or get_rules() is not None:
        return None
    from repro.kernels.mla_decode import mla_decode_attention
    return partial(mla_decode_attention,
                   softmax_scale=mla_mod.softmax_scale(cfg),
                   interpret=impl == "interpret")


def _dense_decode(cfg: ModelConfig, params: Pytree, state: Pytree,
                  tokens: jax.Array):
    """One token for every slot. The scan reads the pool and never writes
    it: each layer attends over the slot's cached positions plus the token's
    own key and value, and emits them; after the scan, one update a slot
    writes the L layers' tokens into the pool (in place where the caller
    donates it). A slot whose token is negative holds no session: its
    cached length is set to 0 first, so that the step reads none of its
    cache."""
    dims = _dims(cfg)
    B = tokens.shape[0]
    pos = jnp.where(tokens[:, 0] >= 0, state["pos"], 0)   # (B,)
    ck, cv = state["k"], state["v"]                        # (L, B, S, C)
    S, C = ck.shape[2], ck.shape[3]
    h = _embed(params, jnp.maximum(tokens, 0))             # (B,1,d)
    windows = jnp.asarray(_windows(cfg))
    bidx = jnp.arange(B)
    kernel = _pooled_kernel(cfg, ck)

    def attend(q, k_new, v_new, li, w):
        if kernel is None:
            def layer(c, new):
                c = jax.lax.dynamic_index_in_dim(c, li, 0, keepdims=False)
                return c.at[bidx, pos].set(new).reshape(
                    B, S, dims.n_kv_heads, dims.hd)
            return decode_attention(q, layer(ck, k_new), layer(cv, v_new),
                                    q_pos=pos, window=w)
        return kernel(q[:, 0], ck, cv, k_new, v_new, pos, li, w)[:, None]

    def step(h, x):
        p, li, w = x
        hn = rms_norm(h, p["ln1"], cfg.norm_eps)
        q = (hn @ p["attn"]["wq"]).reshape(B, 1, dims.n_heads, dims.hd)
        k = (hn @ p["attn"]["wk"]).reshape(B, 1, dims.n_kv_heads, dims.hd)
        v = (hn @ p["attn"]["wv"]).reshape(B, C).astype(cv.dtype)
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta).reshape(
            B, C).astype(ck.dtype)
        o = attend(q, k, v, li, w)
        h = h + o.reshape(B, 1, dims.n_heads * dims.hd) @ p["attn"]["wo"]
        h = h + mlp_block(p["mlp"], rms_norm(h, p["ln2"], cfg.norm_eps))
        return h, (k, v)

    h, (nk, nv) = jax.lax.scan(
        step, h, (params["blocks"], jnp.arange(cfg.n_layers), windows))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _head(cfg, params, h)
    return logits, {"pos": pos + 1, "k": _write_tokens(ck, nk, pos),
                    "v": _write_tokens(cv, nv, pos)}


def _write_tokens(pool: jax.Array, new: jax.Array, pos: jax.Array):
    """Each slot's new token, ``new`` (L, B, C), written at the slot's
    position ``pos`` (B,) of the (L, B, S, C) pool: one (L, 1, 1, C) update a
    slot, in place where the caller donates the pool. A scatter over (slot,
    position) would have XLA relayout the whole pool around it."""
    for b in range(pool.shape[1]):
        pool = jax.lax.dynamic_update_slice(
            pool, new[:, b, None, None].astype(pool.dtype), (0, b, pos[b], 0))
    return pool


# ======================================================================= moe
def _moe_attn_init(cfg: ModelConfig, key: jax.Array) -> Pytree:
    dt = _dtype(cfg)
    if cfg.mla is not None:
        return mla_mod.init_mla(key, cfg, dt, cfg.n_layers)
    return init_attn(key, _dims(cfg), dt, cfg.n_layers)


def _moe_attn_apply(cfg: ModelConfig, p: Pytree, h: jax.Array, positions):
    if cfg.mla is not None:
        return mla_mod.mla_attention(cfg, p, h, positions)
    dims = _dims(cfg)
    B, S, _ = h.shape
    q = (h @ p["wq"]).reshape(B, S, dims.n_heads, dims.hd)
    k = (h @ p["wk"]).reshape(B, S, dims.n_kv_heads, dims.hd)
    v = (h @ p["wv"]).reshape(B, S, dims.n_kv_heads, dims.hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    o = attention(q, k, v, q_pos=positions, k_pos=positions, causal=True)
    return o.reshape(B, S, dims.n_heads * dims.hd) @ p["wo"]


def _moe_block_init(cfg: ModelConfig, key: jax.Array) -> Pytree:
    dt = _dtype(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"ln1": jnp.zeros((cfg.d_model,), dt),
         "attn": _moe_attn_init(cfg, k1),
         "ln2": jnp.zeros((cfg.d_model,), dt),
         "moe": moe_mod.init_moe(k2, cfg, dt, cfg.n_layers)}
    if cfg.dense_residual:
        p["dense_mlp"] = init_mlp(k3, cfg.d_model, cfg.d_ff, dt, cfg.n_layers)
    return p


def _dense_ffn_block_init(cfg: ModelConfig, key: jax.Array) -> Pytree:
    dt = _dtype(cfg)
    k1, k2 = jax.random.split(key)
    return {"ln1": jnp.zeros((cfg.d_model,), dt),
            "attn": _moe_attn_init(cfg, k1),
            "ln2": jnp.zeros((cfg.d_model,), dt),
            "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, dt, cfg.n_layers)}


def _moe_init(cfg: ModelConfig, key: jax.Array) -> Pytree:
    ke, kd, km, kmtp = jax.random.split(key, 4)
    dt = _dtype(cfg)
    n_moe = cfg.n_layers - cfg.first_dense_layers
    p = {"embed": _embed_init(cfg, ke),
         "moe_blocks": stacked_init(partial(_moe_block_init, cfg), km, n_moe),
         "final_norm": jnp.zeros((cfg.d_model,), dt)}
    if cfg.first_dense_layers:
        p["dense_blocks"] = stacked_init(partial(_dense_ffn_block_init, cfg),
                                         kd, cfg.first_dense_layers)
    if cfg.mtp_depth:
        k1, k2 = jax.random.split(kmtp)
        p["mtp"] = {"proj": init_linear(k1, 2 * cfg.d_model, cfg.d_model, dt),
                    "block": _dense_ffn_block_init(cfg, k2),
                    "norm": jnp.zeros((cfg.d_model,), dt)}
    return p


def _moe_layer(cfg: ModelConfig, p: Pytree, h: jax.Array, positions):
    from repro.dist.hints import hint
    h = h + _moe_attn_apply(cfg, p["attn"],
                            rms_norm(h, p["ln1"], cfg.norm_eps), positions)
    h = hint(h, "dp", "sp_seq", None)
    hn = rms_norm(h, p["ln2"], cfg.norm_eps)
    y, aux = moe_mod.moe_ffn(cfg, p["moe"], hn)
    if cfg.dense_residual:
        y = y + mlp_block(p["dense_mlp"], hn)
    return hint(h + y, "dp", "sp_seq", None), aux


def _dense_ffn_layer(cfg: ModelConfig, p: Pytree, h: jax.Array, positions):
    from repro.dist.hints import hint
    h = h + _moe_attn_apply(cfg, p["attn"],
                            rms_norm(h, p["ln1"], cfg.norm_eps), positions)
    h = hint(h, "dp", "sp_seq", None)
    return hint(h + mlp_block(p["mlp"], rms_norm(h, p["ln2"], cfg.norm_eps)),
                "dp", "sp_seq", None)


def _moe_hidden(cfg: ModelConfig, params: Pytree, tokens: jax.Array):
    h = _embed(params, tokens)
    positions = _positions(tokens)
    aux_total = jnp.zeros((), jnp.float32)
    if cfg.first_dense_layers:
        dense_body = _remat(lambda p, h: _dense_ffn_layer(cfg, p, h, positions))
        h, _ = jax.lax.scan(lambda h, p: (dense_body(p, h), None), h,
                            params["dense_blocks"])
    moe_body = _remat(lambda p, h: _moe_layer(cfg, p, h, positions))

    def step(carry, p):
        h, aux = carry
        h, a = moe_body(p, h)
        return (h, aux + a), None

    (h, aux_total), _ = jax.lax.scan(step, (h, aux_total), params["moe_blocks"])
    return rms_norm(h, params["final_norm"], cfg.norm_eps), aux_total


def _moe_train(cfg: ModelConfig, params: Pytree, batch: Pytree):
    h, aux = _moe_hidden(cfg, params, batch["tokens"])
    logits = _head(cfg, params, h)
    xent = _lm_loss(cfg, logits, batch["labels"])
    loss = xent + aux
    metrics = {"loss": loss, "xent": xent, "aux": aux}
    if cfg.mtp_depth:
        # multi-token prediction: fuse h with the embedding of the (t+1) token
        # and predict t+2 through one extra dense layer + the shared head.
        m = params["mtp"]
        emb_next = _embed(params, batch["labels"].clip(0))
        z = jnp.concatenate([rms_norm(h, m["norm"], cfg.norm_eps),
                             emb_next], axis=-1) @ m["proj"]
        z = _dense_ffn_layer(cfg, m["block"], z, _positions(batch["tokens"]))
        mtp_logits = _head(cfg, params, z)
        labels2 = jnp.concatenate(
            [batch["labels"][:, 1:],
             jnp.full_like(batch["labels"][:, :1], -1)], axis=1)
        mtp = softmax_xent(mtp_logits, labels2)
        loss = loss + 0.3 * mtp
        metrics.update({"mtp": mtp, "loss": loss})
    return loss, metrics


def _moe_prefill(cfg: ModelConfig, params: Pytree, batch: Pytree,
                 max_seq: int):
    assert cfg.mla is not None or cfg.first_dense_layers == 0
    tokens = batch["tokens"]
    B, S = tokens.shape
    h = _embed(params, tokens)
    positions = _positions(tokens)

    def emit_cache(p, hn):
        if cfg.mla is not None:
            c_kv, k_rope = mla_mod._latents(cfg, p["attn"], hn, positions)
            pad = max_seq - S
            return (jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0))),
                    jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0))))
        dims = _dims(cfg)
        k = (hn @ p["attn"]["wk"]).reshape(B, S, dims.n_kv_heads, dims.hd)
        v = (hn @ p["attn"]["wv"]).reshape(B, S, dims.n_kv_heads, dims.hd)
        k = apply_rope(k, positions, cfg.rope_theta)
        pad = max_seq - S
        return (jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
                jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))))

    def dense_step(h, p):
        hn = rms_norm(h, p["ln1"], cfg.norm_eps)
        cache = emit_cache(p, hn)
        h = h + _moe_attn_apply(cfg, p["attn"], hn, positions)
        h = h + mlp_block(p["mlp"], rms_norm(h, p["ln2"], cfg.norm_eps))
        return h, cache

    def moe_step(h, p):
        hn = rms_norm(h, p["ln1"], cfg.norm_eps)
        cache = emit_cache(p, hn)
        h = h + _moe_attn_apply(cfg, p["attn"], hn, positions)
        hn2 = rms_norm(h, p["ln2"], cfg.norm_eps)
        y, _ = moe_mod.moe_ffn_serve(cfg, p["moe"], hn2)
        if cfg.dense_residual:
            y = y + mlp_block(p["dense_mlp"], hn2)
        return h + y, cache

    state = {"pos": jnp.full((B,), S, jnp.int32)}
    if cfg.first_dense_layers:
        h, dc = jax.lax.scan(dense_step, h, params["dense_blocks"])
        state["dense_cache"] = dc
    h, mc = jax.lax.scan(moe_step, h, params["moe_blocks"])
    state["moe_cache"] = mc
    if cfg.mla is not None:
        state["routed"] = _no_routing(cfg, B)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return _head(cfg, params, h[:, -1:]), state


def _moe_decode_state(cfg: ModelConfig, batch: int, max_seq: int) -> Pytree:
    dt = _dtype(cfg)
    n_moe = cfg.n_layers - cfg.first_dense_layers
    state = {"pos": jnp.zeros((batch,), jnp.int32)}

    def cache(n):
        if cfg.mla is not None:
            m = cfg.mla
            return (jnp.zeros((n, batch, max_seq, m.kv_lora_rank), dt),
                    jnp.zeros((n, batch, max_seq, m.qk_rope_head_dim), dt))
        dims = _dims(cfg)
        return (jnp.zeros((n, batch, max_seq, dims.n_kv_heads, dims.hd), dt),
                jnp.zeros((n, batch, max_seq, dims.n_kv_heads, dims.hd), dt))

    if cfg.first_dense_layers:
        state["dense_cache"] = cache(cfg.first_dense_layers)
    state["moe_cache"] = cache(n_moe)
    if cfg.mla is not None:
        state["routed"] = _no_routing(cfg, batch)
    return state


def _no_routing(cfg: ModelConfig, batch: int) -> jax.Array:
    """The ``routed`` leaf of an MLA decode state before any step: (n_moe,
    B, k) expert ids, -1 for none."""
    n_moe = cfg.n_layers - cfg.first_dense_layers
    return jnp.full((n_moe, batch, cfg.experts_per_token), -1, jnp.int32)


def routing_counts(cfg: ModelConfig, state: Pytree, slots
                   ) -> tuple[int, int] | None:
    """From the last step's ``routed`` leaf, for the slots ``slots``: the
    held experts that got at least one of their tokens, summed over MoE
    layers, and their token picks that landed on held experts. None for a
    state without routing."""
    if "routed" not in state:
        return None
    ids = np.asarray(state["routed"])[:, list(slots)]        # (n_moe, b, k)
    first, n = cfg.experts_held
    held = (ids >= first) & (ids < first + n)
    touched = sum(len(np.unique(layer[h])) for layer, h in zip(ids, held))
    return touched, int(held.sum())


def _gqa_moe_attn_decode(cfg: ModelConfig, p: Pytree, h, cache_pair, li,
                         pos):
    """One-layer GQA attention decode (arctic); returns (attn_out, updated
    (k_l, v_l))."""
    B = h.shape[0]
    bidx = jnp.arange(B)
    c1, c2 = cache_pair
    c1_l = jax.lax.dynamic_index_in_dim(c1, li, 0, keepdims=False)
    c2_l = jax.lax.dynamic_index_in_dim(c2, li, 0, keepdims=False)
    dims = _dims(cfg)
    q = (h @ p["wq"]).reshape(B, 1, dims.n_heads, dims.hd)
    k = (h @ p["wk"]).reshape(B, 1, dims.n_kv_heads, dims.hd)
    v = (h @ p["wv"]).reshape(B, 1, dims.n_kv_heads, dims.hd)
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k = apply_rope(k, pos[:, None], cfg.rope_theta)
    c1_l = c1_l.at[bidx, pos].set(k[:, 0])
    c2_l = c2_l.at[bidx, pos].set(v[:, 0])
    o = decode_attention(q, c1_l, c2_l, q_pos=pos)
    return o.reshape(B, 1, dims.n_heads * dims.hd) @ p["wo"], (c1_l, c2_l)


def _moe_decode(cfg: ModelConfig, params: Pytree, state: Pytree,
                tokens: jax.Array):
    if cfg.mla is not None:
        return _mla_decode(cfg, params, state, tokens)
    pos = state["pos"]
    h = _embed(params, tokens)
    new_state = {"pos": pos + 1}

    def step(carry, x):
        h, c1, c2 = carry
        p, li = x
        hn = rms_norm(h, p["ln1"], cfg.norm_eps)
        o, (c1_l, c2_l) = _gqa_moe_attn_decode(cfg, p["attn"], hn, (c1, c2),
                                               li, pos)
        h = h + o
        hn2 = rms_norm(h, p["ln2"], cfg.norm_eps)
        y, _ = moe_mod.moe_ffn_serve(cfg, p["moe"], hn2)
        if cfg.dense_residual:
            y = y + mlp_block(p["dense_mlp"], hn2)
        h = h + y
        c1 = jax.lax.dynamic_update_index_in_dim(c1, c1_l, li, 0)
        c2 = jax.lax.dynamic_update_index_in_dim(c2, c2_l, li, 0)
        return (h, c1, c2), None

    c1, c2 = state["moe_cache"]
    n_moe = cfg.n_layers - cfg.first_dense_layers
    (h, c1, c2), _ = jax.lax.scan(
        step, (h, c1, c2), (params["moe_blocks"], jnp.arange(n_moe)))
    new_state["moe_cache"] = (c1, c2)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return _head(cfg, params, h), new_state


def _mla_decode(cfg: ModelConfig, params: Pytree, state: Pytree,
                tokens: jax.Array):
    """One token for every slot over the latent pools. Each scan reads its
    pools and never writes them: a layer attends over the slot's cached
    latents plus the token's own, and emits the token's latents; after the
    scans, one update a slot writes them into the pools (in place where the
    caller donates the state). A slot whose token is negative holds no
    session: its cached length is set to 0 first. ``routed`` keeps each
    slot's experts at each MoE layer."""
    pos = jnp.where(tokens[:, 0] >= 0, state["pos"], 0)      # (B,)
    h = _embed(params, jnp.maximum(tokens, 0))               # (B,1,d)
    new_state = {"pos": pos + 1}
    kernel = _latent_kernel(cfg)

    def layers(h, blocks, pools, moe: bool):
        c_pool, r_pool = pools

        def step(h, x):
            p, li = x
            o, c_new, kr_new = mla_mod.mla_decode_pooled(
                cfg, p["attn"], rms_norm(h, p["ln1"], cfg.norm_eps), c_pool,
                r_pool, li, pos, kernel)
            h = h + o
            hn = rms_norm(h, p["ln2"], cfg.norm_eps)
            if not moe:
                return h + mlp_block(p["mlp"], hn), (c_new, kr_new, ())
            y, ids = moe_mod.moe_ffn_serve(cfg, p["moe"], hn)
            return h + y, (c_new, kr_new, ids[:, 0])

        n = jax.tree.leaves(blocks)[0].shape[0]
        h, (nc, nr, ids) = jax.lax.scan(step, h, (blocks, jnp.arange(n)))
        return h, (_write_tokens(c_pool, nc, pos),
                   _write_tokens(r_pool, nr, pos)), ids

    if cfg.first_dense_layers:
        h, new_state["dense_cache"], _ = layers(
            h, params["dense_blocks"], state["dense_cache"], False)
    h, new_state["moe_cache"], new_state["routed"] = layers(
        h, params["moe_blocks"], state["moe_cache"], True)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return _head(cfg, params, h), new_state


# ================================================================ hybrid (zamba2)
def _hybrid_layout(cfg: ModelConfig) -> tuple[int, int]:
    """(n_groups, n_tail): groups of (attn_every mamba + 1 shared attn)."""
    n_groups = cfg.n_layers // cfg.attn_every
    return n_groups, cfg.n_layers - n_groups * cfg.attn_every


def _hybrid_init(cfg: ModelConfig, key: jax.Array) -> Pytree:
    dt = _dtype(cfg)
    ke, kg, kt, ka = jax.random.split(key, 4)
    G, tail = _hybrid_layout(cfg)

    def mamba_layer(k):
        return {"norm": jnp.zeros((cfg.d_model,), dt),
                "mamba": ssm_mod.init_mamba2(k, cfg, dt, cfg.n_layers)}

    p = {"embed": _embed_init(cfg, ke),
         "groups": jax.vmap(lambda k: stacked_init(
             mamba_layer, k, cfg.attn_every))(jax.random.split(kg, G)),
         "shared_attn": {"ln": jnp.zeros((cfg.d_model,), dt),
                         "attn": init_attn(ka, _dims(cfg), dt, cfg.n_layers),
                         "ln2": jnp.zeros((cfg.d_model,), dt),
                         "mlp": init_mlp(jax.random.fold_in(ka, 1),
                                         cfg.d_model, cfg.d_ff, dt,
                                         cfg.n_layers)},
         "final_norm": jnp.zeros((cfg.d_model,), dt)}
    if tail:
        p["tail"] = stacked_init(mamba_layer, kt, tail)
    return p


def _hybrid_hidden(cfg: ModelConfig, params: Pytree, tokens: jax.Array,
                   *, build_cache: int = 0):
    h = _embed(params, tokens)
    positions = _positions(tokens)
    B, S = tokens.shape
    sa = params["shared_attn"]
    dims = _dims(cfg)

    mamba_body = _remat(lambda p, h: h + ssm_mod.mamba2_block(
        cfg, p["mamba"], rms_norm(h, p["norm"], cfg.norm_eps)))

    def attn_apply(h):
        hn = rms_norm(h, sa["ln"], cfg.norm_eps)
        q = (hn @ sa["attn"]["wq"]).reshape(B, S, dims.n_heads, dims.hd)
        k = (hn @ sa["attn"]["wk"]).reshape(B, S, dims.n_kv_heads, dims.hd)
        v = (hn @ sa["attn"]["wv"]).reshape(B, S, dims.n_kv_heads, dims.hd)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        o = attention(q, k, v, q_pos=positions, k_pos=positions, causal=True)
        h = h + o.reshape(B, S, dims.n_heads * dims.hd) @ sa["attn"]["wo"]
        h = h + mlp_block(sa["mlp"], rms_norm(h, sa["ln2"], cfg.norm_eps))
        return h, (k, v)

    def group_step(h, gp):
        h, _ = jax.lax.scan(lambda h, p: (mamba_body(p, h), None), h, gp)
        h, (k, v) = attn_apply(h)
        if build_cache:
            pad = build_cache - S
            return h, (jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
                       jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))))
        return h, None

    if not build_cache:
        # group-granular remat: residual carry saved 13x not 81x
        body = _remat(lambda gp, h: group_step(h, gp)[0])
        h, cache = jax.lax.scan(lambda h, gp: (body(gp, h), None), h,
                                params["groups"]), None
        h = h[0] if isinstance(h, tuple) else h
    else:
        h, cache = jax.lax.scan(group_step, h, params["groups"])
    if "tail" in params:
        h, _ = jax.lax.scan(lambda h, p: (mamba_body(p, h), None), h,
                            params["tail"])
    return rms_norm(h, params["final_norm"], cfg.norm_eps), cache


def _hybrid_train(cfg: ModelConfig, params: Pytree, batch: Pytree):
    h, _ = _hybrid_hidden(cfg, params, batch["tokens"])
    logits = _head(cfg, params, h)
    loss = _lm_loss(cfg, logits, batch["labels"])
    return loss, {"loss": loss}


def _hybrid_decode_state(cfg: ModelConfig, batch: int, max_seq: int) -> Pytree:
    dt = _dtype(cfg)
    G, tail = _hybrid_layout(cfg)
    dims = _dims(cfg)
    d_in, H, P, N = ssm_mod.ssm_dims(cfg)
    conv_ch = d_in + 2 * N

    def mamba_states(n):
        return {"conv": jnp.zeros((n, batch, cfg.ssm_conv - 1, conv_ch),
                                  jnp.float32),
                "ssm": jnp.zeros((n, batch, H, P, N), jnp.float32)}

    st = {"pos": jnp.zeros((batch,), jnp.int32),
          "groups": mamba_states(G * cfg.attn_every),
          "attn_k": jnp.zeros((G, batch, max_seq, dims.n_kv_heads, dims.hd), dt),
          "attn_v": jnp.zeros((G, batch, max_seq, dims.n_kv_heads, dims.hd), dt)}
    if tail:
        st["tail"] = mamba_states(tail)
    return st


def _hybrid_prefill(cfg: ModelConfig, params: Pytree, batch: Pytree,
                    max_seq: int):
    """Parallel (chunked-SSD) pass that also exports exact decode states:
    mamba2_block(return_state=True) yields the post-sequence conv/SSM states,
    and each shared-attention application emits its K/V cache."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    h = _embed(params, tokens)
    positions = _positions(tokens)
    sa = params["shared_attn"]
    dims = _dims(cfg)
    pad = max_seq - S

    def mamba_step(h, p):
        y, st = ssm_mod.mamba2_block(cfg, p["mamba"],
                                     rms_norm(h, p["norm"], cfg.norm_eps),
                                     return_state=True)
        return h + y, st

    def group_step(h, gp):
        h, states = jax.lax.scan(mamba_step, h, gp)
        hn = rms_norm(h, sa["ln"], cfg.norm_eps)
        q = (hn @ sa["attn"]["wq"]).reshape(B, S, dims.n_heads, dims.hd)
        k = (hn @ sa["attn"]["wk"]).reshape(B, S, dims.n_kv_heads, dims.hd)
        v = (hn @ sa["attn"]["wv"]).reshape(B, S, dims.n_kv_heads, dims.hd)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        o = attention(q, k, v, q_pos=positions, k_pos=positions, causal=True)
        h = h + o.reshape(B, S, dims.n_heads * dims.hd) @ sa["attn"]["wo"]
        h = h + mlp_block(sa["mlp"], rms_norm(h, sa["ln2"], cfg.norm_eps))
        return h, (states,
                   jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
                   jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))))

    G, tail = _hybrid_layout(cfg)
    h, (gstates, ck, cv) = jax.lax.scan(group_step, h, params["groups"])
    state = {"pos": jnp.full((B,), S, jnp.int32),
             "groups": jax.tree.map(
                 lambda a: a.reshape(G * cfg.attn_every, *a.shape[2:]),
                 gstates),
             "attn_k": ck, "attn_v": cv}
    if tail:
        h, tstates = jax.lax.scan(mamba_step, h, params["tail"])
        state["tail"] = tstates
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return _head(cfg, params, h[:, -1:]), state


def _hybrid_decode(cfg: ModelConfig, params: Pytree, state: Pytree,
                   tokens: jax.Array):
    B = tokens.shape[0]
    pos = state["pos"]
    dims = _dims(cfg)
    sa = params["shared_attn"]
    bidx = jnp.arange(B)
    G, tail = _hybrid_layout(cfg)
    A = cfg.attn_every

    def mamba_step(carry, x):
        h, conv, ssm = carry
        p, li = x
        cs = jax.lax.dynamic_index_in_dim(conv, li, 0, keepdims=False)
        ss = jax.lax.dynamic_index_in_dim(ssm, li, 0, keepdims=False)
        y, new = ssm_mod.mamba2_step(
            cfg, p["mamba"], {"conv": cs, "ssm": ss},
            rms_norm(h, p["norm"], cfg.norm_eps))
        h = h + y
        conv = jax.lax.dynamic_update_index_in_dim(conv, new["conv"], li, 0)
        ssm = jax.lax.dynamic_update_index_in_dim(ssm, new["ssm"], li, 0)
        return (h, conv, ssm), None

    def group_step(carry, x):
        h, conv, ssm, ak, av = carry
        gp, gi = x
        lids = gi * A + jnp.arange(A)
        (h, conv, ssm), _ = jax.lax.scan(mamba_step, (h, conv, ssm),
                                         (gp, lids))
        hn = rms_norm(h, sa["ln"], cfg.norm_eps)
        q = (hn @ sa["attn"]["wq"]).reshape(B, 1, dims.n_heads, dims.hd)
        k = (hn @ sa["attn"]["wk"]).reshape(B, 1, dims.n_kv_heads, dims.hd)
        v = (hn @ sa["attn"]["wv"]).reshape(B, 1, dims.n_kv_heads, dims.hd)
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)
        k_g = jax.lax.dynamic_index_in_dim(ak, gi, 0, keepdims=False)
        v_g = jax.lax.dynamic_index_in_dim(av, gi, 0, keepdims=False)
        k_g = k_g.at[bidx, pos].set(k[:, 0])
        v_g = v_g.at[bidx, pos].set(v[:, 0])
        o = decode_attention(q, k_g, v_g, q_pos=pos)
        h = h + o.reshape(B, 1, dims.n_heads * dims.hd) @ sa["attn"]["wo"]
        h = h + mlp_block(sa["mlp"], rms_norm(h, sa["ln2"], cfg.norm_eps))
        ak = jax.lax.dynamic_update_index_in_dim(ak, k_g, gi, 0)
        av = jax.lax.dynamic_update_index_in_dim(av, v_g, gi, 0)
        return (h, conv, ssm, ak, av), None

    h = _embed(params, tokens)
    carry = (h, state["groups"]["conv"], state["groups"]["ssm"],
             state["attn_k"], state["attn_v"])
    carry, _ = jax.lax.scan(group_step, carry,
                            (params["groups"], jnp.arange(G)))
    h, conv, ssm, ak, av = carry
    new_state = {"pos": pos + 1, "groups": {"conv": conv, "ssm": ssm},
                 "attn_k": ak, "attn_v": av}
    if tail:
        tconv, tssm = state["tail"]["conv"], state["tail"]["ssm"]
        (h, tconv, tssm), _ = jax.lax.scan(
            mamba_step, (h, tconv, tssm),
            (params["tail"], jnp.arange(tail)))
        new_state["tail"] = {"conv": tconv, "ssm": tssm}
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return _head(cfg, params, h), new_state


# ======================================================================= rwkv
def _rwkv_init(cfg: ModelConfig, key: jax.Array) -> Pytree:
    dt = _dtype(cfg)
    ke, kb = jax.random.split(key)

    def block(k):
        kb1, kb2 = jax.random.split(k)
        return {"ln1": jnp.zeros((cfg.d_model,), dt),
                "ln2": jnp.zeros((cfg.d_model,), dt),
                **rwkv_mod.init_rwkv_block(kb1, cfg, dt, cfg.n_layers)}

    return {"embed": _embed_init(cfg, ke),
            "blocks": stacked_init(block, kb, cfg.n_layers),
            "final_norm": jnp.zeros((cfg.d_model,), dt)}


def _rwkv_train(cfg: ModelConfig, params: Pytree, batch: Pytree):
    h = _embed(params, batch["tokens"])

    def body(p, h):
        out, _, _ = rwkv_mod.time_mix(cfg, p["tm"],
                                      rms_norm(h, p["ln1"], cfg.norm_eps))
        h = h + out
        out, _ = rwkv_mod.channel_mix(cfg, p["cm"],
                                      rms_norm(h, p["ln2"], cfg.norm_eps))
        return h + out

    body = _remat(body)
    h, _ = jax.lax.scan(lambda h, p: (body(p, h), None), h, params["blocks"])
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _head(cfg, params, h)
    loss = _lm_loss(cfg, logits, batch["labels"])
    return loss, {"loss": loss}


def _rwkv_decode_state(cfg: ModelConfig, batch: int, max_seq: int) -> Pytree:
    H, K = rwkv_mod.rwkv_dims(cfg)
    L, d = cfg.n_layers, cfg.d_model
    return {"pos": jnp.zeros((batch,), jnp.int32),
            "tm_x": jnp.zeros((L, batch, d), jnp.float32),
            "cm_x": jnp.zeros((L, batch, d), jnp.float32),
            "wkv": jnp.zeros((L, batch, H, K, K), jnp.float32)}


def _rwkv_forward_stateful(cfg: ModelConfig, params: Pytree, state: Pytree,
                           tokens: jax.Array):
    """Runs S tokens (S>=1) carrying recurrent state — decode AND prefill."""
    h = _embed(params, tokens)

    def step(carry, x):
        h, tmx, cmx, wkv = carry
        p, li = x
        tm_last = jax.lax.dynamic_index_in_dim(tmx, li, 0, keepdims=False)
        cm_last = jax.lax.dynamic_index_in_dim(cmx, li, 0, keepdims=False)
        S0 = jax.lax.dynamic_index_in_dim(wkv, li, 0, keepdims=False)
        out, tm_new, S1 = rwkv_mod.time_mix(
            cfg, p["tm"], rms_norm(h, p["ln1"], cfg.norm_eps),
            last_x=tm_last, state=S0)
        h = h + out
        out, cm_new = rwkv_mod.channel_mix(
            cfg, p["cm"], rms_norm(h, p["ln2"], cfg.norm_eps), last_x=cm_last)
        h = h + out
        tmx = jax.lax.dynamic_update_index_in_dim(
            tmx, tm_new.astype(jnp.float32), li, 0)
        cmx = jax.lax.dynamic_update_index_in_dim(
            cmx, cm_new.astype(jnp.float32), li, 0)
        wkv = jax.lax.dynamic_update_index_in_dim(wkv, S1, li, 0)
        return (h, tmx, cmx, wkv), None

    carry = (h, state["tm_x"], state["cm_x"], state["wkv"])
    carry, _ = jax.lax.scan(step, carry,
                            (params["blocks"], jnp.arange(cfg.n_layers)))
    h, tmx, cmx, wkv = carry
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    new_state = {"pos": state["pos"] + tokens.shape[1], "tm_x": tmx,
                 "cm_x": cmx, "wkv": wkv}
    return _head(cfg, params, h), new_state


def _rwkv_prefill(cfg: ModelConfig, params: Pytree, batch: Pytree,
                  max_seq: int):
    state = _rwkv_decode_state(cfg, batch["tokens"].shape[0], max_seq)
    logits, state = _rwkv_forward_stateful(cfg, params, state, batch["tokens"])
    return logits[:, -1:], state


def _rwkv_decode(cfg: ModelConfig, params: Pytree, state: Pytree,
                 tokens: jax.Array):
    return _rwkv_forward_stateful(cfg, params, state, tokens)


# ==================================================================== encdec
def _sinusoid(n: int, d: int) -> np.ndarray:
    pos = np.arange(n)[:, None]
    i = np.arange(d // 2)[None, :]
    ang = pos / (10000 ** (2 * i / d))
    return np.concatenate([np.sin(ang), np.cos(ang)], axis=-1).astype(np.float32)


def _encdec_init(cfg: ModelConfig, key: jax.Array) -> Pytree:
    dt = _dtype(cfg)
    ke, kenc, kdec = jax.random.split(key, 3)

    def enc_block(k):
        k1, k2 = jax.random.split(k)
        return {"ln1": jnp.zeros((cfg.d_model,), dt),
                "attn": init_attn(k1, _dims(cfg), dt, cfg.encoder_layers),
                "ln2": jnp.zeros((cfg.d_model,), dt),
                "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, dt,
                                cfg.encoder_layers, gated=False)}

    def dec_block(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"ln1": jnp.zeros((cfg.d_model,), dt),
                "attn": init_attn(k1, _dims(cfg), dt, cfg.n_layers),
                "lnx": jnp.zeros((cfg.d_model,), dt),
                "xattn": init_attn(k2, _dims(cfg), dt, cfg.n_layers),
                "ln2": jnp.zeros((cfg.d_model,), dt),
                "mlp": init_mlp(k3, cfg.d_model, cfg.d_ff, dt, cfg.n_layers,
                                gated=False)}

    return {"embed": _embed_init(cfg, ke),
            "enc_blocks": stacked_init(enc_block, kenc, cfg.encoder_layers),
            "enc_norm": jnp.zeros((cfg.d_model,), dt),
            "dec_blocks": stacked_init(dec_block, kdec, cfg.n_layers),
            "final_norm": jnp.zeros((cfg.d_model,), dt)}


def _encode(cfg: ModelConfig, params: Pytree, frames: jax.Array) -> jax.Array:
    B, F, d = frames.shape
    h = frames + jnp.asarray(_sinusoid(F, d), frames.dtype)[None]
    positions = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32)[None], (B, F))
    dims = _dims(cfg)

    def body(p, h):
        hn = rms_norm(h, p["ln1"], cfg.norm_eps)
        q = (hn @ p["attn"]["wq"]).reshape(B, F, dims.n_heads, dims.hd)
        k = (hn @ p["attn"]["wk"]).reshape(B, F, dims.n_kv_heads, dims.hd)
        v = (hn @ p["attn"]["wv"]).reshape(B, F, dims.n_kv_heads, dims.hd)
        o = attention(q, k, v, q_pos=positions, k_pos=positions, causal=False)
        h = h + o.reshape(B, F, dims.n_heads * dims.hd) @ p["attn"]["wo"]
        return h + mlp_block(p["mlp"], rms_norm(h, p["ln2"], cfg.norm_eps))

    body = _remat(body)
    h, _ = jax.lax.scan(lambda h, p: (body(p, h), None), h,
                        params["enc_blocks"])
    return rms_norm(h, params["enc_norm"], cfg.norm_eps)


def _encdec_train(cfg: ModelConfig, params: Pytree, batch: Pytree):
    enc_out = _encode(cfg, params, batch["frames"])
    tokens = batch["tokens"]
    B, S = tokens.shape
    h = _embed(params, tokens)
    positions = _positions(tokens)
    dims = _dims(cfg)

    def body(p, h):
        hn = rms_norm(h, p["ln1"], cfg.norm_eps)
        q = (hn @ p["attn"]["wq"]).reshape(B, S, dims.n_heads, dims.hd)
        k = (hn @ p["attn"]["wk"]).reshape(B, S, dims.n_kv_heads, dims.hd)
        v = (hn @ p["attn"]["wv"]).reshape(B, S, dims.n_kv_heads, dims.hd)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        o = attention(q, k, v, q_pos=positions, k_pos=positions, causal=True)
        h = h + o.reshape(B, S, dims.n_heads * dims.hd) @ p["attn"]["wo"]
        h = h + cross_attention_block(p["xattn"],
                                      rms_norm(h, p["lnx"], cfg.norm_eps),
                                      enc_out, dims)
        return h + mlp_block(p["mlp"], rms_norm(h, p["ln2"], cfg.norm_eps))

    body = _remat(body)
    h, _ = jax.lax.scan(lambda h, p: (body(p, h), None), h,
                        params["dec_blocks"])
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _head(cfg, params, h)
    loss = _lm_loss(cfg, logits, batch["labels"])
    return loss, {"loss": loss}


def _encdec_decode_state(cfg: ModelConfig, batch: int, max_seq: int) -> Pytree:
    dt = _dtype(cfg)
    dims = _dims(cfg)
    L = cfg.n_layers
    return {"pos": jnp.zeros((batch,), jnp.int32),
            "k": jnp.zeros((L, batch, max_seq, dims.n_kv_heads, dims.hd), dt),
            "v": jnp.zeros((L, batch, max_seq, dims.n_kv_heads, dims.hd), dt),
            "xk": jnp.zeros((L, batch, cfg.n_frames, dims.n_kv_heads,
                             dims.hd), dt),
            "xv": jnp.zeros((L, batch, cfg.n_frames, dims.n_kv_heads,
                             dims.hd), dt)}


def _encdec_prefill(cfg: ModelConfig, params: Pytree, batch: Pytree,
                    max_seq: int):
    """Encode frames, precompute cross K/V, then run the prompt through the
    decoder building the self-attn cache."""
    enc_out = _encode(cfg, params, batch["frames"])
    tokens = batch["tokens"]
    B, S = tokens.shape
    h = _embed(params, tokens)
    positions = _positions(tokens)
    dims = _dims(cfg)
    F = enc_out.shape[1]

    def body(h, p):
        hn = rms_norm(h, p["ln1"], cfg.norm_eps)
        q = (hn @ p["attn"]["wq"]).reshape(B, S, dims.n_heads, dims.hd)
        k = (hn @ p["attn"]["wk"]).reshape(B, S, dims.n_kv_heads, dims.hd)
        v = (hn @ p["attn"]["wv"]).reshape(B, S, dims.n_kv_heads, dims.hd)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        o = attention(q, k, v, q_pos=positions, k_pos=positions, causal=True)
        h = h + o.reshape(B, S, dims.n_heads * dims.hd) @ p["attn"]["wo"]
        hx = rms_norm(h, p["lnx"], cfg.norm_eps)
        xk = (enc_out @ p["xattn"]["wk"]).reshape(B, F, dims.n_kv_heads,
                                                  dims.hd)
        xv = (enc_out @ p["xattn"]["wv"]).reshape(B, F, dims.n_kv_heads,
                                                  dims.hd)
        h = h + cross_attention_block(p["xattn"], hx, enc_out, dims)
        h = h + mlp_block(p["mlp"], rms_norm(h, p["ln2"], cfg.norm_eps))
        pad = max_seq - S
        return h, (jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
                   jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))), xk, xv)

    h, (ck, cv, xk, xv) = jax.lax.scan(body, h, params["dec_blocks"])
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    state = {"pos": jnp.full((B,), S, jnp.int32), "k": ck, "v": cv,
             "xk": xk, "xv": xv}
    return _head(cfg, params, h[:, -1:]), state


def _encdec_decode(cfg: ModelConfig, params: Pytree, state: Pytree,
                   tokens: jax.Array):
    B = tokens.shape[0]
    pos = state["pos"]
    dims = _dims(cfg)
    bidx = jnp.arange(B)
    h = _embed(params, tokens)

    def step(carry, x):
        h, ck, cv = carry
        p, li, xk_l, xv_l = x
        hn = rms_norm(h, p["ln1"], cfg.norm_eps)
        q = (hn @ p["attn"]["wq"]).reshape(B, 1, dims.n_heads, dims.hd)
        k = (hn @ p["attn"]["wk"]).reshape(B, 1, dims.n_kv_heads, dims.hd)
        v = (hn @ p["attn"]["wv"]).reshape(B, 1, dims.n_kv_heads, dims.hd)
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)
        k_l = jax.lax.dynamic_index_in_dim(ck, li, 0, keepdims=False)
        v_l = jax.lax.dynamic_index_in_dim(cv, li, 0, keepdims=False)
        k_l = k_l.at[bidx, pos].set(k[:, 0])
        v_l = v_l.at[bidx, pos].set(v[:, 0])
        o = decode_attention(q, k_l, v_l, q_pos=pos)
        h = h + o.reshape(B, 1, dims.n_heads * dims.hd) @ p["attn"]["wo"]
        # cross attention against the precomputed encoder K/V
        hx = rms_norm(h, p["lnx"], cfg.norm_eps)
        qx = (hx @ p["xattn"]["wq"]).reshape(B, 1, dims.n_heads, dims.hd)
        F = xk_l.shape[1]
        ox = decode_attention(qx, xk_l, xv_l,
                              q_pos=jnp.full((B,), F - 1, jnp.int32))
        h = h + ox.reshape(B, 1, dims.n_heads * dims.hd) @ p["xattn"]["wo"]
        h = h + mlp_block(p["mlp"], rms_norm(h, p["ln2"], cfg.norm_eps))
        ck = jax.lax.dynamic_update_index_in_dim(ck, k_l, li, 0)
        cv = jax.lax.dynamic_update_index_in_dim(cv, v_l, li, 0)
        return (h, ck, cv), None

    (h, ck, cv), _ = jax.lax.scan(
        step, (h, state["k"], state["v"]),
        (params["dec_blocks"], jnp.arange(cfg.n_layers), state["xk"],
         state["xv"]))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    new_state = dict(state, pos=pos + 1, k=ck, v=cv)
    return _head(cfg, params, h), new_state


# ======================================================================== vlm
def _vlm_layout(cfg: ModelConfig) -> tuple[int, int]:
    """(n_groups, self_per_group): groups of (self×k + 1 cross)."""
    per = cfg.cross_every
    assert cfg.n_layers % per == 0
    return cfg.n_layers // per, per - 1


def _vlm_init(cfg: ModelConfig, key: jax.Array) -> Pytree:
    dt = _dtype(cfg)
    G, S_per = _vlm_layout(cfg)
    ke, ks, kx = jax.random.split(key, 3)

    def cross_block(k):
        k1, k2 = jax.random.split(k)
        return {"ln": jnp.zeros((cfg.d_model,), dt),
                "attn": init_attn(k1, _dims(cfg), dt, cfg.n_layers),
                "gate": jnp.zeros((), jnp.float32),
                "ln2": jnp.zeros((cfg.d_model,), dt),
                "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, dt, cfg.n_layers),
                "gate_mlp": jnp.zeros((), jnp.float32)}

    return {"embed": _embed_init(cfg, ke),
            "self_groups": jax.vmap(lambda k: stacked_init(
                partial(_dense_block_init, cfg), k, S_per))(
                    jax.random.split(ks, G)),
            "cross_blocks": stacked_init(cross_block, kx, G),
            "final_norm": jnp.zeros((cfg.d_model,), dt)}


def _vlm_hidden(cfg: ModelConfig, params: Pytree, tokens, patches):
    h = _embed(params, tokens)
    positions = _positions(tokens)
    dims = _dims(cfg)
    zero_w = jnp.zeros((), jnp.int32)

    self_body = _remat(lambda p, h: _gqa_layer(cfg, p, h, positions, zero_w))

    def group_body(gp, h):
        sp, xp = gp
        h, _ = jax.lax.scan(lambda h, p: (self_body(p, h), None), h, sp)
        hn = rms_norm(h, xp["ln"], cfg.norm_eps)
        xo = cross_attention_block(xp["attn"], hn, patches, dims)
        h = h + jnp.tanh(xp["gate"]).astype(h.dtype) * xo
        h = h + jnp.tanh(xp["gate_mlp"]).astype(h.dtype) * mlp_block(
            xp["mlp"], rms_norm(h, xp["ln2"], cfg.norm_eps))
        return h

    # remat at GROUP granularity: the scan carry (B,S,d) is saved once per
    # group (20x) instead of per layer (100x) — 5x cut on saved residuals.
    group_body = _remat(group_body)
    h, _ = jax.lax.scan(lambda h, gp: (group_body(gp, h), None), h,
                        (params["self_groups"], params["cross_blocks"]))
    return rms_norm(h, params["final_norm"], cfg.norm_eps)


def _vlm_train(cfg: ModelConfig, params: Pytree, batch: Pytree):
    h = _vlm_hidden(cfg, params, batch["tokens"], batch["patches"])
    logits = _head(cfg, params, h)
    loss = _lm_loss(cfg, logits, batch["labels"])
    return loss, {"loss": loss}


def _vlm_decode_state(cfg: ModelConfig, batch: int, max_seq: int) -> Pytree:
    dt = _dtype(cfg)
    dims = _dims(cfg)
    G, S_per = _vlm_layout(cfg)
    return {"pos": jnp.zeros((batch,), jnp.int32),
            "k": jnp.zeros((G, S_per, batch, max_seq, dims.n_kv_heads,
                            dims.hd), dt),
            "v": jnp.zeros((G, S_per, batch, max_seq, dims.n_kv_heads,
                            dims.hd), dt),
            "xk": jnp.zeros((G, batch, cfg.n_patches, dims.n_kv_heads,
                             dims.hd), dt),
            "xv": jnp.zeros((G, batch, cfg.n_patches, dims.n_kv_heads,
                             dims.hd), dt)}


def _vlm_prefill(cfg: ModelConfig, params: Pytree, batch: Pytree,
                 max_seq: int):
    tokens, patches = batch["tokens"], batch["patches"]
    B, S = tokens.shape
    h = _embed(params, tokens)
    positions = _positions(tokens)
    dims = _dims(cfg)
    pad = max_seq - S

    def group_step(h, gp):
        sp, xp = gp

        def self_step(hh, p):
            hh, (k, v) = _gqa_layer(cfg, p, hh, positions, 0,
                                    build_cache=max_seq)
            return hh, (k, v)

        h, (ks, vs) = jax.lax.scan(self_step, h, sp)       # (S_per, B, ...)
        hn = rms_norm(h, xp["ln"], cfg.norm_eps)
        xk = (patches @ xp["attn"]["wk"]).reshape(B, cfg.n_patches,
                                                  dims.n_kv_heads, dims.hd)
        xv = (patches @ xp["attn"]["wv"]).reshape(B, cfg.n_patches,
                                                  dims.n_kv_heads, dims.hd)
        xo = cross_attention_block(xp["attn"], hn, patches, dims)
        h = h + jnp.tanh(xp["gate"]).astype(h.dtype) * xo
        h = h + jnp.tanh(xp["gate_mlp"]).astype(h.dtype) * mlp_block(
            xp["mlp"], rms_norm(h, xp["ln2"], cfg.norm_eps))
        return h, (ks, vs, xk, xv)

    h, (ck, cv, xk, xv) = jax.lax.scan(
        group_step, h, (params["self_groups"], params["cross_blocks"]))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    state = {"pos": jnp.full((B,), S, jnp.int32), "k": ck, "v": cv,
             "xk": xk, "xv": xv}
    return _head(cfg, params, h[:, -1:]), state


def _vlm_decode(cfg: ModelConfig, params: Pytree, state: Pytree,
                tokens: jax.Array):
    B = tokens.shape[0]
    pos = state["pos"]
    dims = _dims(cfg)
    bidx = jnp.arange(B)
    G, S_per = _vlm_layout(cfg)
    h = _embed(params, tokens)

    def group_step(carry, x):
        h, ck, cv = carry
        sp, xp, gi, xk_g, xv_g = x

        def self_step(carry2, x2):
            h, ck, cv = carry2
            p, si = x2
            hn = rms_norm(h, p["ln1"], cfg.norm_eps)
            q = (hn @ p["attn"]["wq"]).reshape(B, 1, dims.n_heads, dims.hd)
            k = (hn @ p["attn"]["wk"]).reshape(B, 1, dims.n_kv_heads, dims.hd)
            v = (hn @ p["attn"]["wv"]).reshape(B, 1, dims.n_kv_heads, dims.hd)
            q = apply_rope(q, pos[:, None], cfg.rope_theta)
            k = apply_rope(k, pos[:, None], cfg.rope_theta)
            idx = gi * S_per + si
            k_l = jax.lax.dynamic_index_in_dim(
                ck.reshape(G * S_per, *ck.shape[2:]), idx, 0, keepdims=False)
            v_l = jax.lax.dynamic_index_in_dim(
                cv.reshape(G * S_per, *cv.shape[2:]), idx, 0, keepdims=False)
            k_l = k_l.at[bidx, pos].set(k[:, 0])
            v_l = v_l.at[bidx, pos].set(v[:, 0])
            o = decode_attention(q, k_l, v_l, q_pos=pos)
            h = h + o.reshape(B, 1, dims.n_heads * dims.hd) @ p["attn"]["wo"]
            h = h + mlp_block(p["mlp"], rms_norm(h, p["ln2"], cfg.norm_eps))
            ck = jax.lax.dynamic_update_index_in_dim(
                ck.reshape(G * S_per, *ck.shape[2:]), k_l, idx, 0
            ).reshape(ck.shape)
            cv = jax.lax.dynamic_update_index_in_dim(
                cv.reshape(G * S_per, *cv.shape[2:]), v_l, idx, 0
            ).reshape(cv.shape)
            return (h, ck, cv), None

        (h, ck, cv), _ = jax.lax.scan(self_step, (h, ck, cv),
                                      (sp, jnp.arange(S_per)))
        hx = rms_norm(h, xp["ln"], cfg.norm_eps)
        qx = (hx @ xp["attn"]["wq"]).reshape(B, 1, dims.n_heads, dims.hd)
        P = xk_g.shape[1]
        ox = decode_attention(qx, xk_g, xv_g,
                              q_pos=jnp.full((B,), P - 1, jnp.int32))
        h = h + jnp.tanh(xp["gate"]).astype(h.dtype) * (
            ox.reshape(B, 1, dims.n_heads * dims.hd) @ xp["attn"]["wo"])
        h = h + jnp.tanh(xp["gate_mlp"]).astype(h.dtype) * mlp_block(
            xp["mlp"], rms_norm(h, xp["ln2"], cfg.norm_eps))
        return (h, ck, cv), None

    (h, ck, cv), _ = jax.lax.scan(
        group_step, (h, state["k"], state["v"]),
        (params["self_groups"], params["cross_blocks"], jnp.arange(G),
         state["xk"], state["xv"]))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    new_state = dict(state, pos=pos + 1, k=ck, v=cv)
    return _head(cfg, params, h), new_state


# ================================================================== dispatch
_FAMILY = {
    "dense": (_dense_init, _dense_train, _dense_prefill, _dense_decode_state,
              _dense_decode),
    "localglobal": (_dense_init, _dense_train, _dense_prefill,
                    _dense_decode_state, _dense_decode),
    "moe": (_moe_init, _moe_train, _moe_prefill, _moe_decode_state,
            _moe_decode),
    "hybrid": (_hybrid_init, _hybrid_train, _hybrid_prefill,
               _hybrid_decode_state, _hybrid_decode),
    "rwkv": (_rwkv_init, _rwkv_train, _rwkv_prefill, _rwkv_decode_state,
             _rwkv_decode),
    "encdec": (_encdec_init, _encdec_train, _encdec_prefill,
               _encdec_decode_state, _encdec_decode),
    "vlm": (_vlm_init, _vlm_train, _vlm_prefill, _vlm_decode_state,
            _vlm_decode),
}


def init_params(cfg: ModelConfig, key: jax.Array) -> Pytree:
    cfg.validate()
    return _FAMILY[cfg.family][0](cfg, key)


def loss_fn(cfg: ModelConfig, params: Pytree, batch: Pytree):
    return _FAMILY[cfg.family][1](cfg, params, batch)


def prefill(cfg: ModelConfig, params: Pytree, batch: Pytree, max_seq: int):
    return _FAMILY[cfg.family][2](cfg, params, batch, max_seq)


def init_decode_state(cfg: ModelConfig, batch: int, max_seq: int) -> Pytree:
    return _FAMILY[cfg.family][3](cfg, batch, max_seq)


def decode_step(cfg: ModelConfig, params: Pytree, state: Pytree,
                tokens: jax.Array):
    """One token for every slot of ``state``; ``tokens`` (B, 1). A negative
    token marks a slot that holds no session, whose output is to be
    discarded; the pooled families (``dense``, ``localglobal``) and the MLA
    ``moe`` step read none of its cache."""
    if cfg.family not in _POOLED_FAMILIES and cfg.mla is None:
        tokens = jnp.maximum(tokens, 0)
    return _FAMILY[cfg.family][4](cfg, params, state, tokens)


def forward_logits(cfg: ModelConfig, params: Pytree,
                   tokens: jax.Array) -> jax.Array:
    """Logits at every position from one cache-free forward pass — the
    reference that cached decode is checked against. Dense-attention
    families only (``dense``, ``localglobal``)."""
    if cfg.family not in ("dense", "localglobal"):
        raise NotImplementedError(
            f"forward_logits: no cache-free forward for {cfg.family!r}")
    return _head(cfg, params, _dense_hidden(cfg, params, tokens))


# ------------------------------------------------------------------- counts
def param_count(cfg: ModelConfig) -> int:
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))


def active_param_count(cfg: ModelConfig) -> int:
    """Params touched per token (MoE: only k routed experts active)."""
    total = param_count(cfg)
    if not cfg.is_moe:
        return total
    n_moe_layers = cfg.n_layers - cfg.first_dense_layers
    per_expert = 3 * cfg.d_model * cfg.moe_d_ff
    inactive = (cfg.n_experts - cfg.experts_per_token) * per_expert \
        * n_moe_layers
    return total - int(inactive)
