"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import (block_size, blocks_fetched,
                                            decode_attention)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mla_decode import DEFAULT_BK as MLA_BK
from repro.kernels.mla_decode import mla_decode_attention
from repro.models.layers import decode_attention as decode_attention_xla
from repro.models.mla import _absorbed_xla

RNG = np.random.default_rng(42)


def mk(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape), dtype)


def max_err(a, b):
    return float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())


FLASH_CASES = [
    # B, Sq, Sk, Hq, Hkv, hd, causal, window, off
    (2, 128, 128, 4, 2, 64, True, 0, 0),
    (1, 100, 100, 4, 4, 72, True, 0, 0),       # unaligned seq + head dim
    (2, 64, 192, 8, 2, 64, True, 0, 128),      # suffix prefill offset
    (2, 256, 256, 4, 2, 64, True, 64, 0),      # sliding window (gemma local)
    (1, 96, 160, 2, 2, 48, False, 0, 0),       # bidirectional (encoder)
    (1, 64, 64, 8, 1, 128, True, 0, 0),        # MQA
    (2, 80, 80, 6, 3, 240, True, 0, 0),        # gemma3-12b head dim
]


@pytest.mark.parametrize("case", FLASH_CASES,
                         ids=[f"flash{i}" for i in range(len(FLASH_CASES))])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_matches_ref(case, dtype):
    B, Sq, Sk, Hq, Hkv, hd, causal, win, off = case
    q, k, v = (mk((B, Sq, Hq, hd), dtype), mk((B, Sk, Hkv, hd), dtype),
               mk((B, Sk, Hkv, hd), dtype))
    out = flash_attention(q, k, v, causal=causal, window=win, q_offset=off,
                          interpret=True, block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=win,
                                   q_offset=off)
    tol = 0.05 if dtype == jnp.bfloat16 else 2e-5
    assert max_err(out, want) < tol


@pytest.mark.parametrize("block_q,block_k", [(16, 16), (32, 64), (128, 128)])
def test_flash_attention_block_shape_invariance(block_q, block_k):
    q, k, v = (mk((1, 130, 4, 64), jnp.float32),
               mk((1, 130, 2, 64), jnp.float32),
               mk((1, 130, 2, 64), jnp.float32))
    out = flash_attention(q, k, v, causal=True, interpret=True,
                          block_q=block_q, block_k=block_k)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    assert max_err(out, want) < 2e-5


def row_rel_err(got, want) -> float:
    """Max over rows (one head's hd values) of max|got - want| / max|want|
    in that row: an attention output's scale falls with the keys it
    averages, so one bound on the whole array would be set by short rows."""
    g = np.asarray(jnp.asarray(got, jnp.float32))
    w = np.asarray(jnp.asarray(want, jnp.float32))
    scale = np.maximum(np.abs(w).max(axis=-1, keepdims=True), 1e-30)
    return float((np.abs(g - w) / scale).max())


def pooled_xla(q, k_pool, v_pool, k_new, v_new, lengths, layer, window=0):
    """What the pooled decode step computes off the TPU: the layer's cache
    with the token written at ``lengths``, through layers.decode_attention.
    """
    B, Hq, hd = q.shape
    _, _, S, C = k_pool.shape
    bidx = jnp.arange(B)

    def cache(pool, new):
        return pool[layer].at[bidx, lengths].set(new).reshape(B, S, -1, hd)

    return decode_attention_xla(q[:, None], cache(k_pool, k_new),
                                cache(v_pool, v_new), q_pos=lengths,
                                window=window)[:, 0]


def pooled_inputs(L, B, S, Hq, Hkv, hd, dtype):
    C = Hkv * hd
    return (mk((B, Hq, hd), dtype), mk((L, B, S, C), dtype),
            mk((L, B, S, C), dtype), mk((B, C), dtype), mk((B, C), dtype))


# per-row relative error of a kernel output against the XLA path: bf16 is
# two roundings of the output and of p (2^-8 each) apart; f32 is not
DECODE_TOL = {jnp.bfloat16: 0.03, jnp.float32: 2e-5}

DECODE_CASES = [
    # L, B, S, Hq, Hkv, hd, window, block_k
    (2, 2, 256, 4, 2, 64, 0, 64),
    (2, 2, 300, 8, 8, 80, 0, 64),     # no 16-multiple block divides S
    (3, 3, 512, 4, 2, 64, 128, 64),   # sliding window
    (2, 1, 64, 2, 1, 32, 16, 16),
    (1, 2, 1024, 16, 2, 128, 0, 128),  # long cache, high group count
]


@pytest.mark.parametrize("case", DECODE_CASES,
                         ids=[f"dec{i}" for i in range(len(DECODE_CASES))])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_decode_attention_matches_ref(case, dtype):
    L, B, S, Hq, Hkv, hd, win, block_k = case
    q, kp, vp, kn, vn = pooled_inputs(L, B, S, Hq, Hkv, hd, dtype)
    lengths = jnp.asarray(RNG.integers(0, S, (B,)), jnp.int32)
    layer = L - 1
    out = decode_attention(q, kp, vp, kn, vn, lengths, layer, win,
                           block_k=block_k, interpret=True)
    want = pooled_xla(q, kp, vp, kn, vn, lengths, layer, win)
    assert out.shape == want.shape and out.dtype == want.dtype
    assert row_rel_err(out, want) < DECODE_TOL[dtype]


BK, S_RAGGED = 32, 128
RAGGED = [0, 1, BK, BK + 1, S_RAGGED - 1]   # empty, one key, block edges, full


@pytest.mark.parametrize("window", [0, 5, BK + 3])
def test_decode_ragged_lengths_traced_layer_and_window(window):
    """Ragged slots in one call, the layer and window traced as the model's
    scan traces them; each slot reads its own keys and the new token."""
    L, Hq, Hkv, hd = 3, 4, 2, 64
    q, kp, vp, kn, vn = pooled_inputs(L, len(RAGGED), S_RAGGED, Hq, Hkv, hd,
                                      jnp.bfloat16)
    lengths = jnp.asarray(RAGGED, jnp.int32)
    run = jax.jit(lambda li, w: decode_attention(
        q, kp, vp, kn, vn, lengths, li, w, block_k=BK, interpret=True))
    for layer in range(L):
        out = run(jnp.int32(layer), jnp.int32(window))
        want = pooled_xla(q, kp, vp, kn, vn, lengths, layer, window)
        assert np.isfinite(np.asarray(out, np.float32)).all()
        assert row_rel_err(out, want) < DECODE_TOL[jnp.bfloat16]


def test_decode_empty_slot_attends_to_its_own_token():
    """A slot of length 0 (an idle slot of the engine) stays finite: its
    output is the new token's value, whatever the pool holds."""
    q, kp, vp, kn, vn = pooled_inputs(1, 2, 64, 4, 2, 32, jnp.float32)
    kp = kp.at[:, 0].set(jnp.nan)           # the empty slot's stale cache
    vp = vp.at[:, 0].set(jnp.nan)
    out = decode_attention(q, kp, vp, kn, vn, jnp.asarray([0, 17], jnp.int32),
                           0, block_k=16, interpret=True)
    own = jnp.repeat(vn[0].reshape(2, 32), 2, axis=0)      # G = 2 per kv head
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(own), rtol=1e-6)
    assert np.isfinite(np.asarray(out)).all()


def _fault_readings():
    """The check's reading (per-row relative error of the kernel against
    the XLA path) when the XLA path is computed with a planted fault."""
    L, Hq, Hkv, hd = 1, 4, 2, 64
    lens = [1, BK + 1, 2 * BK + 3, S_RAGGED - 1]
    q, kp, vp, kn, vn = pooled_inputs(L, len(lens), S_RAGGED, Hq, Hkv, hd,
                                      jnp.bfloat16)
    lengths = jnp.asarray(lens, jnp.int32)
    out = decode_attention(q, kp, vp, kn, vn, lengths, 0, block_k=BK,
                           interpret=True)
    good = row_rel_err(out, pooled_xla(q, kp, vp, kn, vn, lengths, 0))
    B, S = len(lens), S_RAGGED
    bidx = jnp.arange(B)

    def xla(kc, vc, q_pos):
        return decode_attention_xla(q[:, None], kc.reshape(B, S, Hkv, hd),
                                    vc.reshape(B, S, Hkv, hd),
                                    q_pos=q_pos)[:, 0]

    # lengths ignored: every position of the pool attends
    full = xla(kp[0].at[bidx, lengths].set(kn), vp[0].at[bidx, lengths]
               .set(vn), jnp.full_like(lengths, S - 1))
    # the last live block skipped: the token sits where that block starts
    start = (lengths - 1) // BK * BK
    skip = xla(kp[0].at[bidx, start].set(kn), vp[0].at[bidx, start].set(vn),
               start)
    # the new token left out: only the pool's positions < length attend
    no_new = xla(kp[0], vp[0], lengths - 1)
    return good, {"lengths ignored": row_rel_err(out, full),
                  "last live block skipped": row_rel_err(out, skip),
                  "new token left out": row_rel_err(out, no_new)}


@pytest.mark.parametrize("fault", ["lengths ignored",
                                   "last live block skipped",
                                   "new token left out"])
def test_decode_check_sees_planted_faults(fault):
    good, faults = _fault_readings()
    assert good < DECODE_TOL[jnp.bfloat16]
    assert faults[fault] > DECODE_TOL[jnp.bfloat16], faults


@pytest.mark.parametrize("lens,window,want", [
    ([0, 0], 0, 2),                   # an empty slot still fetches one block
    ([1, BK, BK + 1], 0, 1 + 1 + 2),
    ([S_RAGGED - 1], 0, 4),
    ([S_RAGGED - 1], 5, 1),           # the window's blocks only
    ([2 * BK + 3], BK + 3, 2),
])
def test_blocks_fetched_counts_live_blocks(lens, window, want):
    assert blocks_fetched(lens, S_RAGGED, BK, window) == want


def test_block_size_divides_the_pool():
    assert block_size(4096) == 512 and block_size(4096, 1024) == 1024
    assert block_size(96) == 96 and block_size(300, 64) == 300
    assert block_size(1024, 128) == 128


# ------------------------------------------------------------- MLA decode
MLA_SCALE = 0.1      # about DeepSeek-V3's 192 ** -0.5 times YaRN's mscale^2
MLA_RAGGED = [0, 1, BK - 1, BK, BK + 1, S_RAGGED]   # empty .. a full pool


def mla_inputs(L, B, S, H, C, R, dtype):
    """q_lat, q_r, the latent and rope pools, and the token's own latent."""
    return (mk((B, H, C), dtype), mk((B, H, R), dtype),
            mk((L, B, S, C), dtype), mk((L, B, S, R), dtype),
            mk((B, C), dtype), mk((B, R), dtype))


def mla_xla(q_lat, q_r, c_pool, r_pool, c_new, kr_new, lengths, layer):
    """The XLA path of ``mla.mla_decode_pooled``, in q_lat's dtype."""
    return _absorbed_xla(q_lat, q_r, c_pool, r_pool, c_new, kr_new, lengths,
                         layer, MLA_SCALE).astype(q_lat.dtype)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_mla_decode_matches_xla_path(dtype):
    """Ragged slots in one call (empty, one latent, either side of a block
    edge, a full pool), the layer traced as the model's scan traces it."""
    L, H, C, R = 3, 4, 128, 32
    args = mla_inputs(L, len(MLA_RAGGED), S_RAGGED, H, C, R, dtype)
    lengths = jnp.asarray(MLA_RAGGED, jnp.int32)
    run = jax.jit(lambda li: mla_decode_attention(
        *args, lengths, li, softmax_scale=MLA_SCALE, block_k=BK,
        interpret=True))
    for layer in range(L):
        out = run(jnp.int32(layer))
        want = mla_xla(*args, lengths, layer)
        assert out.shape == want.shape and out.dtype == want.dtype
        assert np.isfinite(np.asarray(out, np.float32)).all()
        assert row_rel_err(out, want) < DECODE_TOL[dtype]


def test_mla_decode_empty_slot_attends_to_its_own_latent():
    """A slot of length 0 stays finite: every head's output is the token's
    own latent, whatever the pools hold."""
    q_lat, q_r, cp, rp, cn, rn = mla_inputs(1, 2, 64, 4, 128, 32,
                                            jnp.float32)
    cp, rp = cp.at[:, 0].set(jnp.nan), rp.at[:, 0].set(jnp.nan)
    out = mla_decode_attention(q_lat, q_r, cp, rp, cn, rn,
                               jnp.asarray([0, 17], jnp.int32), 0,
                               softmax_scale=MLA_SCALE, block_k=16,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(out[0]),
                               np.broadcast_to(np.asarray(cn[0]), (4, 128)),
                               rtol=1e-6)
    assert np.isfinite(np.asarray(out)).all()


def _mla_fault_readings():
    """The check's reading (per-row relative error of the MLA kernel
    against the XLA path) when the XLA path is computed with a planted
    fault."""
    lens = [1, BK + 1, 2 * BK + 3, S_RAGGED - 1]
    args = mla_inputs(1, len(lens), S_RAGGED, 4, 128, 32, jnp.bfloat16)
    q_lat, q_r, cp, rp, cn, rn = args
    lengths = jnp.asarray(lens, jnp.int32)
    out = mla_decode_attention(*args, lengths, 0, softmax_scale=MLA_SCALE,
                               block_k=BK, interpret=True)
    good = row_rel_err(out, mla_xla(*args, lengths, 0))
    full = jnp.full_like(lengths, S_RAGGED)
    return good, {
        # every position of the pool attends
        "lengths ignored": row_rel_err(out, mla_xla(*args, full, 0)),
        # the last live block is not read
        "last live block skipped": row_rel_err(out, mla_xla(
            *args, (lengths - 1) // BK * BK, 0)),
        # the scores leave out q_r . r
        "rope part dropped": row_rel_err(out, mla_xla(
            q_lat, q_r, cp, jnp.zeros_like(rp), cn, jnp.zeros_like(rn),
            lengths, 0)),
    }


@pytest.mark.parametrize("fault", ["lengths ignored",
                                   "last live block skipped",
                                   "rope part dropped"])
def test_mla_decode_check_sees_planted_faults(fault):
    good, faults = _mla_fault_readings()
    assert good < DECODE_TOL[jnp.bfloat16]
    assert faults[fault] > DECODE_TOL[jnp.bfloat16], faults


MLA_S = 8192                          # the deepseek-v3 cell's latent pool


@pytest.mark.parametrize("lens,want", [
    ([0, 0], 2),                      # an empty slot still fetches one block
    ([1, MLA_BK, MLA_BK + 1], 1 + 1 + 2),
    ([MLA_S - 1, MLA_S], 2 * MLA_S // MLA_BK),
    ([1587] * 3, 3 * -(-1587 // MLA_BK)),
])
def test_blocks_fetched_counts_live_latent_blocks(lens, want):
    """The MLA kernel's block size divides the cell's pool, and the count
    is of those blocks."""
    assert block_size(MLA_S, MLA_BK) == MLA_BK
    assert blocks_fetched(lens, MLA_S, MLA_BK) == want
