"""Multi-head Latent Attention (DeepSeek-V3).

Train/prefill: the latent ``c_kv`` is expanded to per-head keys/values
(standard formulation). Decode: the **absorbed** formulation — queries are
folded through ``W_uk`` into latent space so the per-token cache is only
``kv_lora_rank + rope_dim`` floats (the whole point of MLA: a 576-wide cache
instead of H*(192+128)), and attention runs directly against the latent cache.

The rope part of queries and keys rotates the two halves of its 64 values
(``layers.apply_rope``), with YaRN where the config has it; the softmax scale
is ``qk_head_dim ** -0.5`` times YaRN's mscale squared. DeepSeek's published
weights pair rope values interleaved, (0, 1), (2, 3), ...: a server folds
that permutation into the rope columns of ``wq_b`` and ``wkv_a``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MLAConfig, ModelConfig
from repro.models.layers import (NEG_INF, apply_rope, attention, init_linear,
                                 rms_norm, yarn_softmax_factor)

Pytree = Any


def init_mla(key: jax.Array, cfg: ModelConfig, dtype, n_layers: int = 1) -> Pytree:
    m: MLAConfig = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 6)
    return {
        "wq_a": init_linear(ks[0], d, m.q_lora_rank, dtype),
        "q_norm": jnp.zeros((m.q_lora_rank,), dtype),
        "wq_b": init_linear(ks[1], m.q_lora_rank, H * m.qk_head_dim, dtype),
        "wkv_a": init_linear(ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim,
                             dtype),
        "kv_norm": jnp.zeros((m.kv_lora_rank,), dtype),
        "wkv_b": init_linear(ks[3], m.kv_lora_rank,
                             H * (m.qk_nope_head_dim + m.v_head_dim), dtype),
        "wo": init_linear(ks[4], H * m.v_head_dim, d, dtype,
                          scale=1.0 / np.sqrt(H * m.v_head_dim)
                          / np.sqrt(2.0 * n_layers)),
    }


def _queries(cfg: ModelConfig, p: Pytree, x: jax.Array, positions: jax.Array):
    m, H = cfg.mla, cfg.n_heads
    B, S, _ = x.shape
    cq = rms_norm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps)
    q = (cq @ p["wq_b"]).reshape(B, S, H, m.qk_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    return q_nope, q_rope


def _latents(cfg: ModelConfig, p: Pytree, x: jax.Array, positions: jax.Array):
    m = cfg.mla
    kv_a = x @ p["wkv_a"]
    c_kv, k_rope = jnp.split(kv_a, [m.kv_lora_rank], axis=-1)
    c_kv = rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                        cfg.rope_scaling)[:, :, 0, :]
    return c_kv, k_rope                        # (B,S,kv_lora), (B,S,rope)


def softmax_scale(cfg: ModelConfig) -> float:
    return cfg.mla.qk_head_dim ** -0.5 * yarn_softmax_factor(cfg.rope_scaling)


def mla_attention(cfg: ModelConfig, p: Pytree, x: jax.Array,
                  positions: jax.Array) -> jax.Array:
    """Full-sequence causal MLA (train / prefill)."""
    m, H = cfg.mla, cfg.n_heads
    B, S, _ = x.shape
    q_nope, q_rope = _queries(cfg, p, x, positions)
    c_kv, k_rope = _latents(cfg, p, x, positions)
    kv = (c_kv @ p["wkv_b"]).reshape(B, S, H, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_rope[:, :, None, :],
                                          (B, S, H, m.qk_rope_head_dim))],
                        axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    o = attention(q, k, v, q_pos=positions, k_pos=positions, causal=True,
                  softmax_scale=softmax_scale(cfg))
    return o.reshape(B, S, H * m.v_head_dim) @ p["wo"]


def mla_decode_pooled(cfg: ModelConfig, p: Pytree, x: jax.Array,
                      c_pool: jax.Array, r_pool: jax.Array, li: jax.Array,
                      pos: jax.Array, kernel=None):
    """Absorbed-form decode of one layer against the pooled latent cache,
    read where it lies. x: (B, 1, d); pools (L, B, S, kv_lora) and
    (L, B, S, rope); ``li`` the layer; ``pos`` (B,) each slot's cached
    length. Each slot attends over its cached positions plus the token's own
    latent, which is returned, (B, kv_lora) and (B, rope) in the pool's
    dtype, for the caller to write after its layers. ``kernel``: the Pallas
    kernel ``kernels.mla_decode.mla_decode_attention`` with its options
    bound, which reads each slot's live latent blocks once; None for XLA
    einsums over every position of the layer's pools.
    Returns (out (B, 1, d), c_new, kr_new)."""
    m, H = cfg.mla, cfg.n_heads
    B = x.shape[0]
    positions = pos[:, None]
    q_nope, q_rope = _queries(cfg, p, x, positions)      # (B,1,H,·)
    c_new, kr_new = _latents(cfg, p, x, positions)       # (B,1,·)
    c_new = c_new[:, 0].astype(c_pool.dtype)
    kr_new = kr_new[:, 0].astype(r_pool.dtype)

    wkv_b = p["wkv_b"].reshape(m.kv_lora_rank, H,
                               m.qk_nope_head_dim + m.v_head_dim)
    w_uk = wkv_b[:, :, : m.qk_nope_head_dim]             # (c, H, nope)
    w_uv = wkv_b[:, :, m.qk_nope_head_dim:]              # (c, H, v)
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope[:, 0], w_uk)
    q_r = q_rope[:, 0]
    if kernel is None:
        o_lat = _absorbed_xla(q_lat, q_r, c_pool, r_pool, c_new, kr_new, pos,
                              li, softmax_scale(cfg))
    else:
        o_lat = kernel(q_lat, q_r, c_pool, r_pool, c_new, kr_new, pos, li)
    o = jnp.einsum("bhc,chv->bhv", o_lat.astype(x.dtype), w_uv)   # (B,H,v)
    out = o.reshape(B, 1, H * m.v_head_dim) @ p["wo"]
    return out, c_new, kr_new


def _absorbed_xla(q_lat, q_r, c_pool, r_pool, c_new, kr_new, pos, li,
                  scale: float) -> jax.Array:
    """What ``mla_decode_attention`` computes, as XLA einsums over every
    position of layer ``li``'s pools, the uncached ones masked. Returns
    o_lat (B, H, kv_lora) in f32."""
    c_l = jax.lax.dynamic_index_in_dim(c_pool, li, 0, keepdims=False)
    r_l = jax.lax.dynamic_index_in_dim(r_pool, li, 0, keepdims=False)
    s = (jnp.einsum("bhc,bsc->bhs", q_lat, c_l,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhr,bsr->bhs", q_r, r_l,
                      preferred_element_type=jnp.float32)) * scale
    s_new = (jnp.einsum("bhc,bc->bh", q_lat, c_new,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhr,br->bh", q_r, kr_new,
                          preferred_element_type=jnp.float32)) * scale
    cached = jnp.arange(c_l.shape[1])[None, :] < pos[:, None]
    s = jnp.where(cached[:, None, :], s, NEG_INF)
    mx = jnp.maximum(s.max(-1), s_new)                   # (B, H)
    e = jnp.exp(s - mx[..., None])
    e_new = jnp.exp(s_new - mx)
    denom = e.sum(-1) + e_new
    o_lat = (jnp.einsum("bhs,bsc->bhc", e.astype(c_l.dtype), c_l,
                        preferred_element_type=jnp.float32)
             + e_new[..., None] * c_new[:, None, :].astype(jnp.float32))
    return o_lat / denom[..., None]
