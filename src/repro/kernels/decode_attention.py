"""Decode attention over the pooled KV cache, read where it lies — Pallas TPU.

The serving engine keeps every slot's cache in one pool each for K and V,
laid out ``(L, B, S, Hkv*hd)``: layer, slot, position, and the kv heads side
by side in the minor dimension. A block of ``bk`` positions of one layer and
one slot is then a lane-dense ``(bk, Hkv*hd)`` tile that DMAs straight from
HBM, with no pad, transpose or slice copy. One call is one layer of one
decode step for every slot:

* grid ``(B, S/bk)``. The layer index, each slot's length and the layer's
  window are scalar-prefetched, and the index map clamps the block index to
  the slot's live blocks: past the last one the index repeats, so Pallas does
  not fetch the block again, and ``pl.when`` skips its compute. Dead blocks
  cost neither HBM traffic nor MXU time, only their grid steps;
* the query is laid out block-diagonally, ``(Hq, Hkv*hd)`` with each query
  head's ``hd`` values at its kv head's lanes and zeros elsewhere. Scores are
  then one ``(Hq, bk)`` MXU product and ``p @ v`` one ``(Hq, Hkv*hd)``
  product, and each query head reads its own kv head's lanes of the result;
* the current token's own key and value are not in the pool yet: they are
  one more score column, from which the online softmax starts. A slot of
  length 0 attends to that token alone and stays finite.

The pool stays in its dtype (bf16 on the serving path); scores, the softmax
statistics and the accumulator are f32, and ``p`` enters its product in the
pool's dtype, as in ``layers.decode_attention``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BK = 512


def block_size(S: int, block_k: int = DEFAULT_BK) -> int:
    """The positions of one block: the largest multiple of 16 (a bf16 tile's
    rows) that divides ``S`` and is at most ``block_k``; ``S`` itself where
    there is none."""
    for bk in range(min(block_k, S) // 16 * 16, 0, -16):
        if S % bk == 0:
            return bk
    return S


def _live_blocks(length, window, bk: int, S: int, xp=jnp):
    """First and last block of the keys a query at position ``length`` reads
    from the pool: positions ``< length`` and, for ``window > 0``, above
    ``length - window``. ``xp``: ``jnp`` for traced scalars, ``np`` on the
    host."""
    n = xp.minimum(length, S)
    last = xp.maximum((n + bk - 1) // bk - 1, 0)
    lo = xp.where(window > 0, xp.maximum(length - window + 1, 0), 0)
    return xp.minimum(lo // bk, last), last


def blocks_fetched(lengths, S: int, block_k: int = DEFAULT_BK,
                   window: int = 0) -> int:
    """Blocks of K (as many again of V) that one call fetches for these
    per-slot lengths: every slot's live blocks, and one for a slot with
    none (its first index is fetched, though no key in it is read)."""
    first, last = _live_blocks(np.asarray(lengths, np.int64), window,
                               block_size(S, block_k), S, np)
    return int((last - first + 1).sum())


def _kernel(layer_ref, len_ref, win_ref, q_ref, kn_ref, vn_ref, k_ref, v_ref,
            o_ref, m_scr, l_scr, acc_scr, *, scale: float, bk: int, S: int):
    b, ik = pl.program_id(0), pl.program_id(1)
    length, window = len_ref[b], win_ref[0]
    first, last = _live_blocks(length, window, bk, S)
    q = q_ref[...]                                      # (Hq, C)

    @pl.when(ik == 0)
    def _init():                                        # the current token
        s = jnp.sum(q.astype(jnp.float32) * kn_ref[...].astype(jnp.float32),
                    axis=1, keepdims=True) * scale      # (Hq, 1)
        m_scr[...] = s
        l_scr[...] = jnp.ones_like(s)
        acc_scr[...] = jnp.broadcast_to(vn_ref[...].astype(jnp.float32),
                                        acc_scr.shape)

    @pl.when((ik >= first) & (ik <= last) & (length > 0))
    def _compute():
        k = k_ref[...]                                  # (bk, C)
        s = jax.lax.dot_general(q.astype(k.dtype), k,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = (k_pos < length) & ((window <= 0) | (length - k_pos < window))
        s = jnp.where(ok, s, NEG_INF)                   # (Hq, bk)
        m_prev = m_scr[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)                          # masked: exactly 0
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        v = v_ref[...]
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_cur

    @pl.when(ik == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("softmax_scale", "block_k", "interpret"))
def decode_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                     k_new: jax.Array, v_new: jax.Array, lengths: jax.Array,
                     layer: jax.Array | int, window: jax.Array | int = 0, *,
                     softmax_scale: float | None = None,
                     block_k: int = DEFAULT_BK,
                     interpret: bool = False) -> jax.Array:
    """One layer's decode attention for every slot of the pool.

    q: (B, Hq, hd), the query of the token at position ``lengths`` of each
    slot; pools: (L, B, S, Hkv*hd), of which the call reads layer ``layer``
    at positions ``< lengths`` (and, where ``window > 0``, ``> lengths -
    window``); k_new, v_new: (B, Hkv*hd), the token's own key and value,
    which attend as well. ``layer`` and ``window`` may be traced scalars.
    Returns (B, Hq, hd).
    """
    B, Hq, hd = q.shape
    _, _, S, C = k_pool.shape
    Hkv = C // hd
    assert Hkv * hd == C and Hq % Hkv == 0, (q.shape, k_pool.shape)
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    bk = block_size(S, block_k)
    eye = jnp.eye(Hkv, dtype=q.dtype)
    q_bd = (q.reshape(B, Hkv, G, 1, hd) * eye[None, :, None, :, None]
            ).reshape(B, Hq, C)

    def kv_index(b, ik, layer_ref, len_ref, win_ref):
        first, last = _live_blocks(len_ref[b], win_ref[0], bk, S)
        return layer_ref[0], b, jnp.minimum(jnp.maximum(ik, first), last), 0

    row = pl.BlockSpec((None, Hq, C), lambda b, ik, *_: (b, 0, 0))
    new = pl.BlockSpec((None, 1, C), lambda b, ik, *_: (b, 0, 0))
    pool = pl.BlockSpec((None, None, bk, C), kv_index)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bk=bk, S=S),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, S // bk),
            in_specs=[row, new, new, pool, pool],
            out_specs=row,
            scratch_shapes=[pltpu.VMEM((Hq, 1), jnp.float32),   # running max
                            pltpu.VMEM((Hq, 1), jnp.float32),   # denominator
                            pltpu.VMEM((Hq, C), jnp.float32)]),  # accumulator
        out_shape=jax.ShapeDtypeStruct((B, Hq, C), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.reshape(window, (1,)).astype(jnp.int32), q_bd,
      k_new.reshape(B, 1, C), v_new.reshape(B, 1, C), k_pool, v_pool)
    # each query head's own lanes: the diagonal of (kv head, lane group)
    out = out.reshape(B, Hkv, G, Hkv, hd) * eye[None, :, None, :, None]
    return out.sum(axis=3).reshape(B, Hq, hd)
