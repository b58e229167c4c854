"""Jit'd public wrapper around the flash-attention Pallas kernel.

``attention_op`` picks the implementation:
  * ``impl="pallas"``  — the TPU kernel (real hardware path),
  * ``impl="interpret"`` — same kernel, interpret mode (CPU validation),
  * ``impl="xla"``     — the pure-jnp reference (CPU container default; also
    what the dry-run lowers, since Pallas TPU kernels cannot compile for the
    host-CPU placeholder devices).

Decode attention has no such wrapper: on a TPU the pooled decode step
(``models.model``) calls ``decode_attention.decode_attention`` for GQA
(``dense``, ``localglobal``) and ``mla_decode.mla_decode_attention`` for
MLA's latent pool (deepseek-v3); elsewhere ``layers.decode_attention`` and
the XLA einsums of ``mla.mla_decode_pooled`` attend.
"""

from __future__ import annotations

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash_pallas

IMPLS = ("xla", "pallas", "interpret")


def attention_op(q, k, v, *, causal: bool = True, window: int = 0,
                 q_offset: int = 0, softmax_scale: float | None = None,
                 impl: str = "xla"):
    if impl == "xla":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       q_offset=q_offset,
                                       softmax_scale=softmax_scale)
    return _flash_pallas(q, k, v, causal=causal, window=window,
                         q_offset=q_offset, softmax_scale=softmax_scale,
                         interpret=(impl == "interpret"))
