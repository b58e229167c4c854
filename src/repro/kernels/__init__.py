"""Pallas TPU kernels for the attention hot spots + pure-jnp oracles.

flash_attention.py / decode_attention.py / mla_decode.py: pl.pallas_call +
BlockSpec VMEM tiling; ops.py: the flash kernel's jit wrapper; ref.py:
oracles. Validated in interpret mode on CPU (TPU is the target, not the
runtime).
"""

from repro.kernels.ops import attention_op

__all__ = ["attention_op"]
