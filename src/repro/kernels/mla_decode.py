"""MLA's absorbed decode attention over the pooled latent cache, read where
it lies — Pallas TPU.

DeepSeek-V3's serving engine keeps one pool for the latent ``c``, laid out
``(L, B, S, kv_lora)``, and one for the rope part of the keys,
``(L, B, S, rope)``. In the absorbed form every query head scores against
the one latent: its key is ``[c, r]`` and its value is ``c`` itself. One
call is one layer of one decode step for every slot:

* grid ``(B, S/bk)``, with the layer index and each slot's length
  scalar-prefetched. The index maps clamp the block index to the slot's live
  blocks (``decode_attention._live_blocks``), so dead blocks fetch nothing
  and ``pl.when`` skips their compute;
* per block, the scores ``q_lat·cᵀ + q_r·rᵀ`` are two ``(H, bk)`` f32 MXU
  products, then the online softmax, then ``p @ c`` from the same ``c``
  tile: each latent block is fetched once and serves as key and value;
* the rope pool is read as ``(L, B, rope, S)``, a transposed view: a TPU
  keeps an array whose minor dimension is 64 wide with the positions minor
  (``{2,3,1,0}``), so that view is the buffer as it lies, and a block is a
  lane-dense ``(rope, bk)`` tile. Read as ``(bk, rope)`` tiles, it would
  be relaid out, padded to 128 lanes, on every call;
* the token's own latent is not in the pool yet: it is one more score
  column, from which the online softmax starts. A slot of length 0 attends
  to that token alone and stays finite.

The pools stay in their dtype (bf16 on the serving path); scores, the
softmax statistics and the accumulator are f32, and ``p`` enters its
product in the pool's dtype, as in the XLA path of
``models.mla.mla_decode_pooled``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import NEG_INF, _live_blocks, block_size

DEFAULT_BK = 1024


def _kernel(layer_ref, len_ref, ql_ref, qr_ref, cn_ref, rn_ref, c_ref, r_ref,
            o_ref, m_scr, l_scr, acc_scr, *, scale: float, bk: int, S: int):
    b, ik = pl.program_id(0), pl.program_id(1)
    length = len_ref[b]
    first, last = _live_blocks(length, 0, bk, S)
    ql, qr = ql_ref[...], qr_ref[...]                   # (H, C), (H, R)

    @pl.when(ik == 0)
    def _init():                                        # the current token
        cn = cn_ref[...].astype(jnp.float32)            # (1, C)
        s = (jnp.sum(ql.astype(jnp.float32) * cn, axis=1, keepdims=True)
             + jnp.sum(qr.astype(jnp.float32) * rn_ref[...].astype(
                 jnp.float32), axis=1, keepdims=True)) * scale  # (H, 1)
        m_scr[...] = s
        l_scr[...] = jnp.ones_like(s)
        acc_scr[...] = jnp.broadcast_to(cn, acc_scr.shape)

    @pl.when((ik >= first) & (ik <= last) & (length > 0))
    def _compute():
        c, r = c_ref[...], r_ref[...]                   # (bk, C), (R, bk)
        s = (jax.lax.dot_general(ql.astype(c.dtype), c,
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr.astype(r.dtype), r,
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
             ) * scale                                  # (H, bk)
        k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < length, s, NEG_INF)
        m_prev = m_scr[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)                          # masked: exactly 0
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_cur

    @pl.when(ik == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("softmax_scale", "block_k", "interpret"))
def mla_decode_attention(q_lat: jax.Array, q_r: jax.Array, c_pool: jax.Array,
                         r_pool: jax.Array, c_new: jax.Array,
                         kr_new: jax.Array, lengths: jax.Array,
                         layer: jax.Array | int, *, softmax_scale: float,
                         block_k: int = DEFAULT_BK,
                         interpret: bool = False) -> jax.Array:
    """One layer's absorbed MLA decode attention for every slot of the pool.

    q_lat: (B, H, C), the queries folded through ``W_uk``; q_r: (B, H, R),
    their rope part; pools: (L, B, S, C) and (L, B, S, R), of which the call
    reads layer ``layer`` at positions ``< lengths``; c_new, kr_new: (B, C)
    and (B, R), the token's own latent, which attends as well. ``layer``
    may be a traced scalar. Returns the latent output o_lat (B, H, C) in
    ``q_lat``'s dtype, before ``W_uv``.
    """
    B, H, C = q_lat.shape
    _, _, S, R = r_pool.shape
    assert c_pool.shape[:3] == r_pool.shape[:3] and c_pool.shape[3] == C
    bk = block_size(S, block_k)

    def block(b, ik, len_ref):
        first, last = _live_blocks(len_ref[b], 0, bk, S)
        return jnp.minimum(jnp.maximum(ik, first), last)

    def c_index(b, ik, layer_ref, len_ref):
        return layer_ref[0], b, block(b, ik, len_ref), 0

    def r_index(b, ik, layer_ref, len_ref):
        return layer_ref[0], b, 0, block(b, ik, len_ref)

    def row(n):
        return pl.BlockSpec((None, H, n), lambda b, ik, *_: (b, 0, 0))

    def new(n):
        return pl.BlockSpec((None, 1, n), lambda b, ik, *_: (b, 0, 0))

    return pl.pallas_call(
        functools.partial(_kernel, scale=softmax_scale, bk=bk, S=S),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, S // bk),
            in_specs=[row(C), row(R), new(C), new(R),
                      pl.BlockSpec((None, None, bk, C), c_index),
                      pl.BlockSpec((None, None, R, bk), r_index)],
            out_specs=row(C),
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),   # running max
                            pltpu.VMEM((H, 1), jnp.float32),   # denominator
                            pltpu.VMEM((H, C), jnp.float32)]),  # accumulator
        out_shape=jax.ShapeDtypeStruct((B, H, C), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lengths.astype(jnp.int32),
      q_lat, q_r, c_new.reshape(B, 1, C), kr_new.reshape(B, 1, R), c_pool,
      jnp.swapaxes(r_pool, 2, 3))
