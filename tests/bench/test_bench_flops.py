"""bench/flops.py against the program's parameters and by hand."""

import jax
import numpy as np
import pytest

from bench import flops
from repro.configs import get_smoke
from repro.models import init_params
from repro.models.model import padded_vocab


def _as_hf(mc) -> dict:
    return {"hidden_size": mc.d_model, "num_hidden_layers": mc.n_layers,
            "num_attention_heads": mc.n_heads,
            "num_key_value_heads": mc.n_kv_heads, "head_dim": mc.hd,
            "intermediate_size": mc.d_ff, "vocab_size": mc.vocab,
            "tie_word_embeddings": mc.tie_embeddings}


def test_param_count_matches_init_params_at_the_smoke_config():
    mc = get_smoke("granite-3-2b")
    shapes = jax.eval_shape(lambda k: init_params(mc, k),
                            jax.ShapeDtypeStruct((2,), np.uint32))
    held = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert flops.param_count(_as_hf(mc), vocab=padded_vocab(mc)) == held


# d=4, L=2, Hq=2, Hkv=1, hd=2, F=8, V=10
TINY = {"hidden_size": 4, "num_hidden_layers": 2, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 2, "intermediate_size": 8,
        "vocab_size": 10}


def test_decode_bytes_by_hand():
    # per layer: q 4*4 + k,v 2*(4*2) + o 4*4 + mlp 3*4*8 = 16+16+16+96 = 144
    assert flops.layer_matmul_params(TINY) == 144
    # weights: 2 * (144 + 2 norms * 4) + head 4*10 + final norm 4 = 348
    #   at 2 bytes = 696; embedding rows 2 tokens * 4 * 2 = 16
    # kv per token: 2 layers * (k + v) * 1 head * 2 dims * 2 bytes = 16,
    #   read 3 + 5 cached, write 2 new: 16 * 10 = 160
    assert flops.decode_bytes(TINY, [3, 5]) == 696 + 16 + 160


def test_decode_and_prefill_flops_by_hand():
    # one token at context 3: 2*2*144 + 2*4*10 + 2 layers * 2 heads * 4 * 2 * 4
    assert flops.decode_flops(TINY, [3]) == 576 + 80 + 128
    # prompt of 3: 2*3*2*144 + 2 layers*2 heads*4*2*(3*4/2) + head 2*4*10
    assert flops.prefill_flops(TINY, 3) == 1728 + 192 + 80


def test_train_flops_per_token():
    n = 2 * 144 + 4 * 10
    attn = 2 * 2 * 4 * 2 * (4 * 5 / 2) / 4          # fwd per token at seq 4
    assert flops.train_flops_per_token(TINY, 4) == pytest.approx(6 * n
                                                                 + 3 * attn)
