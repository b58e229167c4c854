"""Blockwise fused attention (flash) — Pallas TPU kernel.

TPU-native design (not a CUDA port):
  * q/k/v are laid out head-major, (B, H, S, hd), so every block is one
    head's (rows, hd) tile — the last two block dims are then (8k, 128k) as
    the TPU's tiling requires (a head axis of block 1 in the second-minor
    position is refused by the chip's compiler).
  * grid = (B, Hq, Sq/BQ, Sk/BK); the LAST grid dim is sequential on TPU, so
    the online-softmax running state (m, l, acc) lives in VMEM scratch and
    persists across the k-block sweep — no atomics, no shared-memory tiling.
  * BQ = BK = 128 default: MXU-shaped (128×128) matmuls; the full working set
    (q, k, v blocks + f32 scores + f32 acc) is ~0.6 MB << 16 MB VMEM, leaving
    room for the compiler's double buffering of HBM->VMEM streams.
  * GQA: the kv-head index is derived from the q-head grid coordinate
    (h // group), so each kv block is loaded once per q-head group sweep.
  * causal + sliding-window masks are applied from absolute positions;
    fully-masked (q-block, k-block) pairs are skipped with pl.when (the
    sequential grid makes this a cheap predicated no-op).

VMEM math (BQ=BK=128, hd=256 padded, bf16 in / f32 state):
  q 64 KB + k 64 KB + v 64 KB + s 64 KB + acc 128 KB + m/l 1 KB ≈ 0.4 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BQ = 128
DEFAULT_BK = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int, q_offset: int,
                  sq_valid: int, sk_valid: int, bq: int, bk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = q_offset + iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # block-level skip: does this (q-block, k-block) pair hold any live key?
    live = (ik * bk) < sk_valid
    if causal:
        live &= (ik * bk) <= (q_offset + iq * bq + bq - 1)
    if window > 0:
        live &= (ik * bk + bk - 1) >= (q_offset + iq * bq - window + 1)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32)            # (bq, hd)
        k = k_ref[...].astype(jnp.float32)            # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        ok = (k_pos < sk_valid) & (q_pos < q_offset + sq_valid)
        if causal:
            ok &= k_pos <= q_pos
        if window > 0:
            ok &= (q_pos - k_pos) < window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...]                           # (bq, 1)
        m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(ok, jnp.exp(s - m_cur), 0.0)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        v = v_ref[...].astype(jnp.float32)            # (bk, hd)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_cur

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "softmax_scale",
                     "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0, q_offset: int = 0,
                    softmax_scale: float | None = None,
                    block_q: int = DEFAULT_BQ, block_k: int = DEFAULT_BK,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Sq, Hq, hd); k/v: (B, Sk, Hkv, hd). Returns (B, Sq, Hq, hd).

    The kernel sees head-major copies, (B, H, S, hd): a block is then one
    head's (rows, hd) tile, whose last two dims the TPU tiles as (8k, 128k).
    Pads Sq/Sk to block multiples and hd to a multiple of 128 (MXU lane
    width); padded keys are masked, padded queries discarded on slice-out.
    """
    B, Sq, Hq, hd = q.shape
    _, Sk, Hkv, _ = k.shape
    assert Hq % Hkv == 0, "GQA requires Hq % Hkv == 0"
    group = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5

    bq = min(block_q, max(Sq, 8))
    bk = min(block_k, max(Sk, 8))
    sq_pad = (-Sq) % bq
    sk_pad = (-Sk) % bk
    hd_pad = (-hd) % 128

    def head_major(x, s_pad):
        return jnp.pad(x, ((0, 0), (0, s_pad), (0, 0), (0, hd_pad))
                       ).transpose(0, 2, 1, 3)

    q, k, v = head_major(q, sq_pad), head_major(k, sk_pad), head_major(v, sk_pad)
    Sqp, Skp, hdp = Sq + sq_pad, Sk + sk_pad, hd + hd_pad

    grid = (B, Hq, Sqp // bq, Skp // bk)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, sq_valid=Sq, sk_valid=Sk, bq=bq, bk=bk)
    q_spec = pl.BlockSpec((None, None, bq, hdp),
                          lambda b, h, iq, ik: (b, h, iq, 0))
    kv_spec = pl.BlockSpec((None, None, bk, hdp),
                           lambda b, h, iq, ik, g=group: (b, h // g, ik, 0))

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sqp, hdp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),      # running max
            pltpu.VMEM((bq, 1), jnp.float32),      # running denom
            pltpu.VMEM((bq, hdp), jnp.float32),    # running accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq, :hd].transpose(0, 2, 1, 3)
