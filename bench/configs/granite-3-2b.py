"""granite-3-2b: the weights the benchmark makes, the plain reference it
compares the served tokens with, its lower-precision control, and the
program's configuration built from ``granite-3-2b.json``.

The benchmark makes the weights of the published model from the seed
(the four muP multipliers, RMSNorm eps 1e-5), with one change: the head is
its own random matrix, not the embedding's transpose, because greedy
decoding under a head tied to a random embedding repeats one token, which
would leave the comparison blind to the cache. The program has no
multipliers of its own, so the served weights fold them in (``fold``): the
embedding times ``embedding_multiplier``, the head over
``logits_scaling``, the query projection times ``attention_multiplier``
over the program's ``head_dim ** -0.5``, and both output projections times
``residual_multiplier``. The program then computes the published model,
rounded to bfloat16 once more where a factor is not a power of two.

The reference is plain ``jax.numpy`` in float32 at ``HIGHEST`` matmul
precision and imports nothing of the program. After the window it makes the
published weights from the seed again (bfloat16, read as float32) and runs
the published equations layer by layer: RMSNorm with weight (1 + g), rotary
embedding on the two halves of each head, causal grouped attention with
scale ``attention_multiplier``, a SiLU-gated MLP, residual branches times
``residual_multiplier``, and the head over ``logits_scaling``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_CHUNK = 512            # reference attention: query rows per block
SEQ_BUCKET = 512         # reference sequences are padded to this multiple
ROW_BUCKET = 128         # logit rows are gathered in blocks of this many
EMBED_STD = 0.02         # spread of the embedding as the first layer sees it

# Widest gap, in logits, by which a served token lies below the reference's
# best token at its position. On a TPU v5e sound runs read at most 0.133
# over 36 seeds of both serving cells, the float8 control at least 0.530
# over 6 (PERF.md, "Limits"): the limit sits 2.3x above the one and 1.8x
# below the other.
LIMITS = {"logit_gap": 0.3}


# ------------------------------------------------------------------ shapes
def dims(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    hq = int(cfg["num_attention_heads"])
    pad = int(cfg.get("program_vocab_padding", 1))
    v = int(cfg["vocab_size"])
    return {"d": d, "L": int(cfg["num_hidden_layers"]), "hq": hq,
            "hkv": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or d // hq),
            "f": int(cfg["intermediate_size"]), "v": v,
            "vp": int(math.ceil(v / pad) * pad),
            "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"]),
            "scale": float(cfg["attention_multiplier"]),
            "emb": float(cfg["embedding_multiplier"]),
            "res": float(cfg["residual_multiplier"]),
            "logit": float(cfg["logits_scaling"])}


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for this configuration. Refuses a file
    that asks for something the weights cannot fold in."""
    from repro.configs.base import ModelConfig

    for key, want in {"hidden_act": "silu",
                      "tie_word_embeddings": False}.items():
        if cfg[key] != want:
            raise ValueError(f"{cfg['name']}: the benchmark folds only "
                             f"{key}={want!r}, the file asks for {cfg[key]!r}")
    k = dims(cfg)
    return ModelConfig(name=cfg["name"], family="dense", n_layers=k["L"],
                       d_model=k["d"], n_heads=k["hq"], n_kv_heads=k["hkv"],
                       head_dim=k["hd"], d_ff=k["f"], vocab=k["v"],
                       rope_theta=k["theta"], norm_eps=k["eps"],
                       tie_embeddings=False, dtype=cfg["torch_dtype"])


# ----------------------------------------------------------------- weights
def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed (64 bits of it)."""
    s = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        np.array([s >> 32, s & 0xFFFFFFFF], np.uint32))


def _published(cfg: dict):
    """key -> the published model's weights, bfloat16.

    Spreads are chosen so that the folded weights the program serves are
    those of a plain decoder at its usual initialisation: the embedding
    times its multiplier has spread ``EMBED_STD``, queries scored at
    ``attention_multiplier`` as at ``head_dim ** -0.5``, output projections
    times ``residual_multiplier`` at ``1/sqrt(fan_in * 2 * layers)``, the
    head over ``logits_scaling`` at ``1/sqrt(hidden_size)``, which gives
    the logits a spread of about 1 whatever the width."""
    k = dims(cfg)
    d, L, hq, hkv, hd, f, vp = (k["d"], k["L"], k["hq"], k["hkv"], k["hd"],
                                k["f"], k["vp"])
    dt = jnp.dtype(cfg["torch_dtype"])
    q_std = d ** -0.5 * hd ** -0.5 / k["scale"]
    o_attn = 1.0 / math.sqrt(hq * hd) / math.sqrt(2.0 * L) / k["res"]
    o_mlp = 1.0 / math.sqrt(f) / math.sqrt(2.0 * L) / k["res"]

    def normal(key, shape, std):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dt)

    def build(key):
        ks = jax.random.split(key, 12)
        return {
            "embed": normal(ks[0], (vp, d), EMBED_STD / k["emb"]),
            "head": normal(ks[1], (d, vp), k["logit"] * d ** -0.5),
            "blocks": {
                "ln1": normal(ks[2], (L, d), 0.1),
                "attn": {"wq": normal(ks[3], (L, d, hq * hd), q_std),
                         "wk": normal(ks[4], (L, d, hkv * hd), d ** -0.5),
                         "wv": normal(ks[5], (L, d, hkv * hd), d ** -0.5),
                         "wo": normal(ks[6], (L, hq * hd, d), o_attn)},
                "ln2": normal(ks[7], (L, d), 0.1),
                "mlp": {"w1": normal(ks[8], (L, d, f), d ** -0.5),
                        "w2": normal(ks[9], (L, f, d), o_mlp),
                        "w3": normal(ks[10], (L, d, f), d ** -0.5)}},
            "final_norm": normal(ks[11], (d,), 0.1),
        }

    return build


def fold(cfg: dict, pub):
    """The published weights in the program's layout, multipliers folded
    in (see the module's docstring)."""
    k = dims(cfg)
    dt = jnp.dtype(cfg["torch_dtype"])

    def times(x, c):
        return (x.astype(jnp.float32) * c).astype(dt)

    e, b = pub["embed"], pub["blocks"]
    return {
        "embed": {"tok": times(e, k["emb"]),
                  "head": times(pub["head"], 1 / k["logit"])},
        "blocks": {
            "ln1": b["ln1"],
            "attn": dict(b["attn"], wq=times(b["attn"]["wq"],
                                             k["scale"] * math.sqrt(k["hd"])),
                         wo=times(b["attn"]["wo"], k["res"])),
            "ln2": b["ln2"],
            "mlp": dict(b["mlp"], w2=times(b["mlp"]["w2"], k["res"]))},
        "final_norm": pub["final_norm"],
    }


def make_published(cfg: dict, seed: int):
    """The published model's weights, made on the device in one jitted call
    from ``seed``, in bfloat16: what the reference reads."""
    return jax.jit(_published(cfg))(seed_key(seed))


def make_weights(cfg: dict, seed: int):
    """The served weights in the program's layout, made on the device in one
    jitted call from ``seed``, in bfloat16."""
    build = _published(cfg)
    return jax.jit(lambda key: fold(cfg, build(key)))(seed_key(seed))


# --------------------------------------------------------------- reference
def _fp8(x: jax.Array) -> jax.Array:
    """``x`` rounded to float8 e4m3 with one scale per tensor, back in f32."""
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8: bool):
    if fp8:
        x, w = _fp8(x), _fp8(w)
    return jnp.matmul(x, w, precision=HIGHEST)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + g)


def _rope(x, pos, theta):
    """x: (S, H, hd); rotate the two halves of each head by pos * freq."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv                 # (S, half)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _layer_fn(k: dict, fp8: bool):
    hq, hkv, hd = k["hq"], k["hkv"], k["hd"]
    g = hq // hkv

    def layer(blocks, li, h):
        w = jax.tree.map(lambda x: jax.lax.dynamic_index_in_dim(
            x, li, 0, keepdims=False).astype(jnp.float32), blocks)
        S = h.shape[0]
        pos = jnp.arange(S)
        x = _norm(h, w["ln1"], k["eps"])
        q = _rope(_mm(x, w["attn"]["wq"], fp8).reshape(S, hq, hd), pos,
                  k["theta"])
        kk = _rope(_mm(x, w["attn"]["wk"], fp8).reshape(S, hkv, hd), pos,
                   k["theta"])
        v = _mm(x, w["attn"]["wv"], fp8).reshape(S, hkv, hd)
        kk, v = jnp.repeat(kk, g, axis=1), jnp.repeat(v, g, axis=1)
        outs = []
        for c0 in range(0, S, Q_CHUNK):
            qc = q[c0:c0 + Q_CHUNK]
            s = jnp.einsum("qhd,khd->hqk", qc, kk,
                           precision=HIGHEST) * k["scale"]
            keep = pos[None, :] <= (c0 + jnp.arange(qc.shape[0]))[:, None]
            s = jnp.where(keep[None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            outs.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST))
        o = jnp.concatenate(outs, 0).reshape(S, hq * hd)
        h = h + k["res"] * _mm(o, w["attn"]["wo"], fp8)
        x = _norm(h, w["ln2"], k["eps"])
        m = jax.nn.silu(_mm(x, w["mlp"]["w1"], fp8)) * _mm(x, w["mlp"]["w3"],
                                                           fp8)
        return h + k["res"] * _mm(m, w["mlp"]["w2"], fp8)

    return jax.jit(layer)


def _head_fn(k: dict, fp8: bool):
    def head(weights, h, rows):
        x = _norm(h[rows], weights["final_norm"].astype(jnp.float32), k["eps"])
        w = weights["head"][:, :k["v"]].astype(jnp.float32)
        return _mm(x, w, fp8) / k["logit"]

    return jax.jit(head)


class Reference:
    """Logits of the published model's plain forward pass at chosen
    positions of a sequence, in float32 (or, as the control, with every
    weight and matmul input rounded to float8), over the weights of
    ``make_published``."""

    def __init__(self, cfg: dict, weights, *, fp8: bool = False) -> None:
        self.k = dims(cfg)
        self.weights = weights
        self._layer = _layer_fn(self.k, fp8)
        self._head = _head_fn(self.k, fp8)
        emb = self.k["emb"]
        self._embed = jax.jit(lambda e, t: emb * e[t].astype(jnp.float32))

    def logits(self, seq, rows) -> np.ndarray:
        """(len(rows), vocab) float32 logits at positions ``rows`` of
        ``seq``."""
        seq = np.asarray(seq, np.int32)
        rows = np.asarray(rows, np.int32)
        sb = -(-len(seq) // SEQ_BUCKET) * SEQ_BUCKET
        rb = -(-len(rows) // ROW_BUCKET) * ROW_BUCKET
        toks = np.zeros(sb, np.int32)
        toks[:len(seq)] = seq
        r = np.zeros(rb, np.int32)
        r[:len(rows)] = rows
        h = self._embed(self.weights["embed"], toks)
        blocks = self.weights["blocks"]
        for li in range(self.k["L"]):
            h = self._layer(blocks, np.int32(li), h)
        out = self._head(self.weights, h, r)
        return np.asarray(out, np.float32)[:len(rows)]


def served_rows(prompt_len: int, n_served: int) -> np.ndarray:
    """Positions whose logits chose the served tokens: the prompt's last
    position chose the first, each served token's position the next."""
    return np.arange(prompt_len - 1, prompt_len - 1 + n_served)


def widest_gap(ref: Reference, prompt, served) -> float:
    """Widest gap by which a served token's reference logit lies below the
    reference's best at its position."""
    served = np.asarray(served)
    if ((served < 0) | (served >= ref.k["v"])).any():
        return float("inf")                  # not a token of the vocabulary
    seq = list(prompt) + list(served[:-1])
    lg = ref.logits(seq, served_rows(len(prompt), len(served)))
    picked = lg[np.arange(len(served)), served]
    return float((lg.max(-1) - picked).max())


def control_gap(ref: Reference, control: Reference, prompt, served) -> float:
    """The same reading for the control: at each position of the same
    prompt and served tokens, the gap of the token the control puts first."""
    seq = list(prompt) + list(served[:-1])
    rows = served_rows(len(prompt), len(served))
    lg = ref.logits(seq, rows)
    first = control.logits(seq, rows).argmax(-1)
    return float((lg.max(-1) - lg[np.arange(len(served)), first]).max())
