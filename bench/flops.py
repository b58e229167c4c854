"""Operations and bytes a dense GQA decoder's steps need, from its shapes.

Every count here is what the algorithm needs, computed from the
configuration file's sizes (Hugging Face key names), never from a compiled
program: a roofline or an MFU then reads the same work whatever implements
it. Matrix multiplications count 2 operations per multiply-add. Causal
attention counts only the query-key pairs the mask keeps.

Shapes (``c`` is a configuration dict): ``hidden_size`` d,
``num_hidden_layers`` L, ``num_attention_heads`` Hq, ``num_key_value_heads``
Hkv, ``head_dim`` hd, ``intermediate_size`` F (gated MLP: three d x F
matrices), ``vocab_size`` V, ``tie_word_embeddings``.
"""

from __future__ import annotations

from typing import Iterable, Mapping

Config = Mapping[str, object]


def _dims(c: Config) -> tuple[int, int, int, int, int, int, int]:
    d = int(c["hidden_size"])
    hq = int(c["num_attention_heads"])
    hd = int(c.get("head_dim") or d // hq)
    return (d, int(c["num_hidden_layers"]), hq, int(c["num_key_value_heads"]),
            hd, int(c["intermediate_size"]), int(c["vocab_size"]))


def layer_matmul_params(c: Config) -> int:
    """Weights one decoder layer multiplies by: q, k, v, o and the gated MLP."""
    d, _, hq, hkv, hd, f, _ = _dims(c)
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f


def param_count(c: Config, vocab: int | None = None) -> int:
    """All parameters: layers with their two norms, the token embedding, the
    output head (absent when tied) and the final norm. ``vocab`` overrides
    ``vocab_size`` (a program that pads its vocabulary holds the padded
    rows)."""
    d, L, *_ = _dims(c)
    v = int(vocab if vocab is not None else c["vocab_size"])
    tables = 1 if c.get("tie_word_embeddings") else 2
    return L * (layer_matmul_params(c) + 2 * d) + tables * v * d + d


def _attn_pairs_causal(s: int) -> int:
    """Query-key pairs a causal mask keeps in a sequence of ``s`` tokens."""
    return s * (s + 1) // 2


def prefill_flops(c: Config, s: int) -> float:
    """One prompt of ``s`` tokens: every layer over every token, causal
    attention, and the head for the last position only (the first token)."""
    d, L, hq, _, hd, _, v = _dims(c)
    matmul = 2.0 * s * L * layer_matmul_params(c)
    attn = L * hq * 4.0 * hd * _attn_pairs_causal(s)   # QK^T and PV
    return matmul + attn + 2.0 * d * v


def decode_flops(c: Config, context: Iterable[int]) -> float:
    """One decode step for sequences holding ``context`` tokens each in the
    cache: one new token per sequence, attending to context + itself."""
    d, L, hq, _, hd, _, v = _dims(c)
    per_token = 2.0 * L * layer_matmul_params(c) + 2.0 * d * v
    total = 0.0
    for n in context:
        total += per_token + L * hq * 4.0 * hd * (n + 1)
    return total


def decode_bytes(c: Config, context: Iterable[int], *, weight_bytes: int = 2,
                 kv_bytes: int = 2) -> float:
    """Bytes one decode step has to move for sequences holding ``context``
    tokens each: every weight once (layers, norms, head, final norm), the
    embedding rows of the new tokens, each sequence's cached keys and values
    read once, and the new token's keys and values written once."""
    d, L, _, hkv, hd, _, v = _dims(c)
    context = list(context)
    b = len(context)
    weights = (L * (layer_matmul_params(c) + 2 * d) + d * v + d) * weight_bytes
    embed_rows = b * d * weight_bytes
    kv_token = L * 2 * hkv * hd * kv_bytes
    return float(weights + embed_rows + kv_token * (sum(context) + b))


def train_flops_per_token(c: Config, seq: int) -> float:
    """Forward and backward per token at sequence length ``seq``: 6 per
    weight multiply (the embedding lookup is none) plus three times the
    forward's causal attention per token. Recomputation does not count."""
    d, L, hq, _, hd, _, v = _dims(c)
    n = L * layer_matmul_params(c) + d * v
    attn_fwd = L * hq * 4.0 * hd * _attn_pairs_causal(seq) / seq
    return 6.0 * n + 3.0 * attn_fwd
