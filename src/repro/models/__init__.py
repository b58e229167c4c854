"""Model zoo: 10 assigned architectures behind one functional API."""

from repro.models.model import (active_param_count, decode_step,
                                forward_logits, init_decode_state,
                                init_params, loss_fn, param_count,
                                padded_vocab, prefill)

__all__ = ["init_params", "loss_fn", "prefill", "init_decode_state",
           "decode_step", "forward_logits", "param_count",
           "active_param_count", "padded_vocab"]
