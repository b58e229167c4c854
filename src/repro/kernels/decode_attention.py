"""Single-token decode attention vs a long KV cache — Pallas TPU kernel.

The decode_32k / long_500k hot spot: one query row per (batch, head) against
S cache entries. Memory-bound by design (roofline: ~2·S·hd bytes of cache per
head at ~0 reuse), so the kernel's job is to stream k/v blocks through VMEM at
full HBM bandwidth while keeping the softmax state in registers/VMEM.

Grid = (B, Hkv, S/BK) — the cache sweep is the sequential dim; each step
scores a kv head's whole query group against one cache block as a
(group, hd) x (hd, BK) matmul, with the caches laid out head-major so the
blocks tile as the TPU requires. Online-softmax state (m, l, acc) persists
in VMEM scratch. Per-batch ``lengths`` masks unseen
cache slots; sliding-window archs pass ``window`` so dead blocks are skipped
with pl.when (compute-free predication — on real TPUs the bandwidth win comes
from shrinking the swept region; see ops.window_slice below).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BK = 512


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale: float, window: int, bk: int, group: int):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[pl.program_id(0)]                  # this batch's valid entries
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (group, bk), 1)

    live = (ik * bk) < length
    if window > 0:
        live &= (ik * bk + bk - 1) >= (length - window)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32)              # (group, hd)
        k = k_ref[...].astype(jnp.float32)              # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        ok = k_pos < length
        if window > 0:
            ok &= (length - 1 - k_pos) < window
        s = jnp.where(ok, s, NEG_INF)                   # (group, bk)
        m_prev = m_scr[...]                             # (group, 1)
        m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(ok, jnp.exp(s - m_cur), 0.0)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        v = v_ref[...].astype(jnp.float32)              # (bk, hd)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_cur

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softmax_scale", "block_k", "interpret"))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array, *, window: int = 0,
                     softmax_scale: float | None = None,
                     block_k: int = DEFAULT_BK,
                     interpret: bool = False) -> jax.Array:
    """q: (B, Hq, hd); caches: (B, S, Hkv, hd); lengths: (B,) int32.

    Returns (B, Hq, hd). The query sits at absolute position lengths-1.
    """
    B, Hq, hd = q.shape
    _, S, Hkv, _ = k_cache.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5

    bk = min(block_k, max(S, 8))
    s_pad = (-S) % bk
    hd_pad = (-hd) % 128
    # head-major: one block per kv head holds its whole query group, so the
    # last two block dims are (group, hd) and (bk, hd) — whole or tiled
    q = jnp.pad(q, ((0, 0), (0, 0), (0, hd_pad))).reshape(B, Hkv, group, -1)
    k_cache, v_cache = (
        jnp.pad(c, ((0, 0), (0, s_pad), (0, 0), (0, hd_pad))
                ).transpose(0, 2, 1, 3) for c in (k_cache, v_cache))
    Sp, hdp = S + s_pad, hd + hd_pad

    grid = (B, Hkv, Sp // bk)
    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               bk=bk, group=group)
    q_spec = pl.BlockSpec((None, None, group, hdp), lambda b, h, ik: (b, h, 0, 0))
    kv_spec = pl.BlockSpec((None, None, bk, hdp), lambda b, h, ik: (b, h, ik, 0))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # lengths, whole array
            q_spec, kv_spec, kv_spec,
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, hdp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),     # running max
            pltpu.VMEM((group, 1), jnp.float32),     # running denom
            pltpu.VMEM((group, hdp), jnp.float32),   # running accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k_cache, v_cache)
    return out.reshape(B, Hq, hdp)[:, :, :hd]
