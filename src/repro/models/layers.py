"""Model primitives shared by all 10 architectures.

Everything is functional: ``init_*`` builds param pytrees, ``apply``-style
functions are pure. Attention is computed in query chunks with the scores kept
at chunk × key size (flash-style memory behaviour under XLA); the Pallas
kernels in :mod:`repro.kernels` implement the same math for the TPU hot path
and are validated against these functions.

Dtype policy: params and activations in ``cfg.dtype`` (bf16), softmax/norm
statistics in f32 — the standard TPU mixed-precision recipe.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

Pytree = Any


# --------------------------------------------------------------------- init
def uniform_scale_init(key: jax.Array, shape: tuple[int, ...], dtype,
                       scale: float = 0.02) -> jax.Array:
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def init_linear(key: jax.Array, d_in: int, d_out: int, dtype,
                scale: float | None = None) -> jax.Array:
    s = scale if scale is not None else 1.0 / np.sqrt(d_in)
    return uniform_scale_init(key, (d_in, d_out), dtype, s)


# --------------------------------------------------------------------- norm
def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    h = x.astype(jnp.float32)
    var = jnp.mean(h * h, axis=-1, keepdims=True)
    return ((h * jax.lax.rsqrt(var + eps)).astype(x.dtype)
            * (1.0 + gamma.astype(x.dtype)))


# --------------------------------------------------------------------- rope
def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_softmax_factor(yarn) -> float:
    """What YaRN multiplies the attention softmax scale by (1 without it)."""
    if yarn is None or not yarn.mscale_all_dim:
        return 1.0
    return _yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2


def rope_frequencies(hd: int, theta: float, yarn=None) -> jax.Array:
    """Inverse frequencies for the even half of the head dim (f32). With
    ``yarn`` (``configs.base.YaRN``), those whose wavelength exceeds the
    original context are divided by its factor, with a linear ramp between
    the ``beta_fast`` and ``beta_slow`` correction dimensions."""
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if yarn is None:
        return inv

    def dim(rotations):
        return (hd * math.log(yarn.original_max_position
                              / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(dim(yarn.beta_fast)), 0)
    hi = min(math.ceil(dim(yarn.beta_slow)), hd - 1)
    ramp = np.clip((np.arange(half) - lo) / max(hi - lo, 1e-3), 0, 1)
    keep = jnp.asarray(1.0 - ramp, jnp.float32)     # 1: not interpolated
    return inv / yarn.factor * (1 - keep) + inv * keep


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn=None) -> jax.Array:
    """Rotate (…, S, H, hd) by per-position angles. ``positions``: (…, S).
    Pairs are the two halves of each head; ``yarn`` scales as
    :func:`rope_frequencies` says, and cos and sin by its mscale ratio."""
    hd = x.shape[-1]
    inv = rope_frequencies(hd, theta, yarn)                 # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., S, hd/2)
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]
    if yarn is not None:
        m = (_yarn_mscale(yarn.factor, yarn.mscale)
             / _yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        sin, cos = sin * m, cos * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- attention
NEG_INF = -1e30


def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, *, causal: bool,
               window: jax.Array | int) -> jax.Array:
    """Additive mask bias (f32) of shape (…, Sq, Sk).

    ``window`` may be a traced scalar (per-layer value fed through
    ``lax.scan`` for the gemma local:global pattern); ``window <= 0`` means
    unwindowed, handled branchlessly.
    """
    dq = q_pos[..., :, None]
    dk = k_pos[..., None, :]
    w = jnp.asarray(window)
    ok = jnp.ones(jnp.broadcast_shapes(dq.shape, dk.shape), bool)
    if causal:
        ok &= dk <= dq
    ok &= (w <= 0) | (dq - dk < w)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              q_pos: jax.Array, k_pos: jax.Array, causal: bool = True,
              window: jax.Array | int = 0, q_chunk: int = 512,
              softmax_scale: float | None = None) -> jax.Array:
    """GQA attention, computed in query chunks (flash-style memory under XLA).

    q: (B, Sq, Hq, hd) — k/v: (B, Sk, Hkv, hd), Hq % Hkv == 0.
    positions are absolute (decode passes an offset q_pos).

    KV heads are expanded to Hq before the einsums so the whole computation
    shards on the model axis per q-head (a grouped (Hkv, G) layout cannot
    carry a 'model' sharding when Hkv < model; the Pallas kernel path keeps
    the grouped form on real TPUs). Sharding hints are no-ops without an
    active dist.hints.sharding_rules context.
    """
    from repro.dist.hints import hint, tp_divides  # no cycle at module load
    B, Sq, Hq, hd = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    if not tp_divides(Hq):
        # heads can't shard on model -> q shards on SEQ ('sq' below). The
        # chunk loop would reshape/rescatter that sharding every iteration
        # (measured: +4 TB of per-chunk K/V gathers on arctic train_4k), so
        # compute attention in one seq-sharded piece instead.
        q_chunk = max(q_chunk, Sq)

    # 'sq': when heads do not divide the model axis (arctic: 56 heads vs 16)
    # attention shards over the query-seq dim instead of replicating 16x.
    q = hint(q, "dp", "sq", "tp", None)
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    k = hint(k, "dp", "sp", "tp", None)
    v = hint(v, "dp", "sp", "tp", None)

    def chunk_attn(q_c: jax.Array, qp_c: jax.Array) -> jax.Array:
        # q_c: (B, C, Hq, hd) -> scores (B, Hq, C, Sk) in f32
        s = jnp.einsum("bchd,bshd->bhcs", q_c, k,
                       preferred_element_type=jnp.float32) * scale
        s = hint(s, "dp", "tp", "sq", None)
        s = s + _mask_bias(qp_c, k_pos, causal=causal, window=window
                           )[:, None, :, :]
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhcs,bshd->bchd", p.astype(v.dtype), v)
        return hint(o, "dp", "sq", "tp", None)

    if Sq <= q_chunk:
        out = chunk_attn(q, q_pos)
    else:
        n = Sq // q_chunk
        rem = Sq - n * q_chunk
        qs = q[:, : n * q_chunk].reshape(B, n, q_chunk, Hq, hd)
        ps = q_pos[:, : n * q_chunk].reshape(B, n, q_chunk)
        outs = jax.lax.map(lambda t: chunk_attn(t[0], t[1]),
                           (qs.transpose(1, 0, 2, 3, 4),
                            ps.transpose(1, 0, 2)))
        out = outs.transpose(1, 0, 2, 3, 4).reshape(B, n * q_chunk, Hq, -1)
        if rem:
            tail = chunk_attn(q[:, n * q_chunk:], q_pos[:, n * q_chunk:])
            out = jnp.concatenate([out, tail], axis=1)
    return out.reshape(B, Sq, Hq, v.shape[-1])  # v head dim (MLA: != q dim)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, *,
                     q_pos: jax.Array, window: jax.Array | int = 0,
                     softmax_scale: float | None = None) -> jax.Array:
    """Single-position attention against a (possibly longer) KV cache.

    q: (B, 1, Hq, hd); caches: (B, S, Hkv, hd); q_pos: (B,) absolute position.
    Entries with k_pos > q_pos (unwritten cache slots) are masked out.
    """
    from repro.dist.hints import hint, tp_divides
    B, _, Hq, hd = q.shape
    S = k_cache.shape[1]
    Hkv = k_cache.shape[2]
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    k_pos = jnp.arange(S)[None, :]
    kv_tp = tp_divides(Hkv)   # can the STORED cache shard its kv heads?
    # Grouped form throughout — no kv expansion (a jnp.repeat here would
    # materialize 2 extra cache-sized buffers PER LAYER: 8 GB/layer on the
    # gemma long_500k cell).
    qg = q.reshape(B, Hkv, G, hd)
    if kv_tp:
        # heads-local attention: cache kv->model, sweep seq locally
        qg = hint(qg, "dp", "tp", None, None)
        k_cache = hint(k_cache, "dp", "sp", "tp", None)
        v_cache = hint(v_cache, "dp", "sp", "tp", None)
    else:
        # kv heads don't divide the model axis: the cache lives seq-sharded
        # over (model, dp) [dist.sharding._cache_spec] — keep the WHOLE sweep
        # in that layout (scores seq-sharded, psum the tiny (B,H,hd) output)
        # instead of re-gathering the cache (measured: 2×1.9 GiB all-gather
        # per layer per token on gemma3-12b long_500k).
        qg = hint(qg, "dp", None, None, None)
        k_cache = hint(k_cache, "dp", "seq", None, None)
        v_cache = hint(v_cache, "dp", "seq", None, None)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s = hint(s, "dp", "tp", None, None) if kv_tp \
        else hint(s, "dp", None, None, "seq")
    w = jnp.asarray(window)
    ok = k_pos <= q_pos[:, None]
    ok &= (w <= 0) | (q_pos[:, None] - k_pos < w)
    s = s + jnp.where(ok, 0.0, NEG_INF)[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache)
    return o.reshape(B, 1, Hq, v_cache.shape[-1])


# --------------------------------------------------------------------- GQA block
@dataclasses.dataclass(frozen=True)
class AttnDims:
    d_model: int
    n_heads: int
    n_kv_heads: int
    hd: int


def init_attn(key: jax.Array, dims: AttnDims, dtype, n_layers: int = 1) -> Pytree:
    ks = jax.random.split(key, 4)
    d, H, Hkv, hd = dims.d_model, dims.n_heads, dims.n_kv_heads, dims.hd
    out_scale = 1.0 / np.sqrt(H * hd) / np.sqrt(2.0 * n_layers)
    return {
        "wq": init_linear(ks[0], d, H * hd, dtype),
        "wk": init_linear(ks[1], d, Hkv * hd, dtype),
        "wv": init_linear(ks[2], d, Hkv * hd, dtype),
        "wo": init_linear(ks[3], H * hd, d, dtype, scale=out_scale),
    }


def attn_qkv(p: Pytree, x: jax.Array, dims: AttnDims
             ) -> tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, dims.n_heads, dims.hd)
    k = (x @ p["wk"]).reshape(B, S, dims.n_kv_heads, dims.hd)
    v = (x @ p["wv"]).reshape(B, S, dims.n_kv_heads, dims.hd)
    return q, k, v


def self_attention_block(p: Pytree, x: jax.Array, dims: AttnDims, *,
                         positions: jax.Array, theta: float,
                         causal: bool = True, window: int = 0) -> jax.Array:
    B, S, _ = x.shape
    q, k, v = attn_qkv(p, x, dims)
    q = apply_rope(q, positions, theta)
    k = apply_rope(k, positions, theta)
    o = attention(q, k, v, q_pos=positions, k_pos=positions,
                  causal=causal, window=window)
    return o.reshape(B, S, dims.n_heads * dims.hd) @ p["wo"]


def cross_attention_block(p: Pytree, x: jax.Array, kv_src: jax.Array,
                          dims: AttnDims) -> jax.Array:
    """Encoder-decoder / VLM cross attention (no rope, no mask)."""
    B, S, _ = x.shape
    Sk = kv_src.shape[1]
    q = (x @ p["wq"]).reshape(B, S, dims.n_heads, dims.hd)
    k = (kv_src @ p["wk"]).reshape(B, Sk, dims.n_kv_heads, dims.hd)
    v = (kv_src @ p["wv"]).reshape(B, Sk, dims.n_kv_heads, dims.hd)
    qp = jnp.zeros((B, S), jnp.int32)
    kp = jnp.zeros((B, Sk), jnp.int32)
    o = attention(q, k, v, q_pos=qp, k_pos=kp, causal=False)
    return o.reshape(B, S, dims.n_heads * dims.hd) @ p["wo"]


# ----------------------------------------------------------------------- MLP
def init_mlp(key: jax.Array, d: int, d_ff: int, dtype, n_layers: int = 1,
             gated: bool = True) -> Pytree:
    ks = jax.random.split(key, 3)
    out_scale = 1.0 / np.sqrt(d_ff) / np.sqrt(2.0 * n_layers)
    p = {"w1": init_linear(ks[0], d, d_ff, dtype),
         "w2": init_linear(ks[1], d_ff, d, dtype, scale=out_scale)}
    if gated:
        p["w3"] = init_linear(ks[2], d, d_ff, dtype)
    return p


def mlp_block(p: Pytree, x: jax.Array) -> jax.Array:
    from repro.dist.hints import hint
    roles = (("dp",) + (None,) * (x.ndim - 2)) + ("tp",)
    if "w3" in p:
        h = hint(jax.nn.silu(x @ p["w1"]) * (x @ p["w3"]), *roles)
        return h @ p["w2"]
    h = hint(jax.nn.gelu(x @ p["w1"]), *roles)
    return h @ p["w2"]


# ------------------------------------------------------------------ embedding
def init_embed(key: jax.Array, cfg: ModelConfig, dtype) -> Pytree:
    k1, k2 = jax.random.split(key)
    p = {"tok": uniform_scale_init(k1, (cfg.vocab, cfg.d_model), dtype)}
    if not cfg.tie_embeddings:
        p["head"] = init_linear(k2, cfg.d_model, cfg.vocab, dtype)
    return p


def embed_tokens(p: Pytree, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["tok"], tokens, axis=0)


def unembed(p: Pytree, h: jax.Array) -> jax.Array:
    w = p["head"] if "head" in p else p["tok"].T
    return h @ w


# -------------------------------------------------------------------- losses
def softmax_xent(logits: jax.Array, labels: jax.Array,
                 ignore_id: int = -1) -> jax.Array:
    """Mean next-token cross entropy in f32; ``labels`` already shifted."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits.astype(jnp.float32),
                               labels[..., None].clip(0), axis=-1)[..., 0]
    mask = (labels != ignore_id).astype(jnp.float32)
    return jnp.sum((lse - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ---------------------------------------------------------------- kv caches
def init_kv_cache(batch: int, max_seq: int, n_kv: int, hd: int, n_layers: int,
                  dtype) -> Pytree:
    shape = (n_layers, batch, max_seq, n_kv, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_update(cache_k: jax.Array, cache_v: jax.Array, k: jax.Array,
                 v: jax.Array, pos: jax.Array
                 ) -> tuple[jax.Array, jax.Array]:
    """Write one step (B, 1, Hkv, hd) at per-batch position ``pos`` (B,)."""
    B = k.shape[0]
    bidx = jnp.arange(B)
    ck = cache_k.at[bidx, pos].set(k[:, 0])
    cv = cache_v.at[bidx, pos].set(v[:, 0])
    return ck, cv
