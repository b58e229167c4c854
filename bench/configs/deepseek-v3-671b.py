"""deepseek-v3-671b: one chip's share of the published model (EP32 for the
routed experts, TP4 for attention): the weights the benchmark makes, the
plain reference it compares the served tokens with, its lower-precision
control, the program's configuration built from ``deepseek-v3-671b.json``,
and the bytes and operations a decode step needs.

The benchmark makes the published model's weights from the seed, in the
published layout: the rope values of ``q_b`` and ``kv_a`` interleaved in
pairs (0, 1), (2, 3), ..., and RMSNorm weights that multiply. The program
rotates the two halves of a rope head and scales a norm by (1 + g), so the
served weights (``fold``) take the rope columns de-interleaved and the norm
weights less 1; queries and keys are permuted alike, so the scores are the
published ones.

The reference is plain ``jax.numpy`` in float32 at ``HIGHEST`` matmul
precision and imports nothing of the program. After the window it runs the
published equations layer by layer, making each layer's published weights
from the seed again as it runs it (each layer has a key of its own, so one
layer is made alone exactly as among all of them): MLA with the latent
expanded to per-head keys and values (not absorbed), rope on interleaved pairs at YaRN's frequencies, the softmax
scale ``qk_head_dim ** -0.5`` times YaRN's mscale squared; the sigmoid
router choosing by score + bias within the best 4 of 8 groups, weighting by
the chosen scores normalised times 2.5; the chip's held experts for the
tokens that chose them and the shared expert; the dense layer's SiLU-gated
MLP. Its departures from the published model are the program's: this
chip's share of heads, experts, vocabulary and layers, and no MTP.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_CHUNK = 512            # reference attention: query rows per block
SEQ_BUCKET = 1024        # reference sequences are padded to this multiple
ROW_BUCKET = 128         # logit rows are gathered in blocks of this many
EMBED_STD = 0.02
NORM_STD = 0.1           # spread of the RMSNorm weights around 1
BIAS_STD = 0.1           # spread of e_score_correction_bias

# The mean gap, in logits, by which a session's served tokens lie below the
# reference's best token at their positions, the widest over the checked
# sessions (``widest_gap``). On a TPU v5e sound runs read at most 0.0141
# over 11 seeds, the float8 control at least 0.263 over 8 (PERF.md, section
# 2): the limit sits 7x above the one and 2.6x below the other.
LIMITS = {"logit_gap": 0.1}


# ------------------------------------------------------------------ shapes
def dims(cfg: dict) -> dict:
    pad = int(cfg.get("program_vocab_padding", 1))
    v = int(cfg["vocab_size"])
    rs = cfg["rope_scaling"]
    L = int(cfg["num_hidden_layers"])
    n_dense = int(cfg["first_k_dense_replace"])
    return {"d": int(cfg["hidden_size"]), "L": L, "n_dense": n_dense,
            "n_moe": L - n_dense, "h": int(cfg["num_attention_heads"]),
            "q_lora": int(cfg["q_lora_rank"]),
            "kv_lora": int(cfg["kv_lora_rank"]),
            "nope": int(cfg["qk_nope_head_dim"]),
            "rope": int(cfg["qk_rope_head_dim"]),
            "vd": int(cfg["v_head_dim"]), "f": int(cfg["intermediate_size"]),
            "ff": int(cfg["moe_intermediate_size"]),
            "held": int(cfg["n_routed_experts"]),
            "experts": int(cfg["published"]["n_routed_experts"]),
            "first": int(cfg["deployment"]["ep_rank"])
            * int(cfg["n_routed_experts"]),
            "k": int(cfg["num_experts_per_tok"]),
            "groups": int(cfg["n_group"]), "topk_groups": int(cfg["topk_group"]),
            "scale": float(cfg["routed_scaling_factor"]),
            "shared": int(cfg["n_shared_experts"]),
            "v": v, "vp": int(math.ceil(v / pad) * pad),
            "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"]),
            "yarn": {"factor": float(rs["factor"]),
                     "original": int(rs["original_max_position_embeddings"]),
                     "beta_fast": float(rs["beta_fast"]),
                     "beta_slow": float(rs["beta_slow"]),
                     "mscale": float(rs["mscale"]),
                     "mscale_all_dim": float(rs["mscale_all_dim"])}}


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for this configuration. Refuses a file
    that asks for what the program does not compute."""
    from repro.configs.base import MLAConfig, ModelConfig, YaRN

    for key, want in {"hidden_act": "silu", "scoring_func": "sigmoid",
                      "topk_method": "noaux_tc", "norm_topk_prob": True,
                      "tie_word_embeddings": False, "moe_layer_freq": 1,
                      "num_nextn_predict_layers": 0}.items():
        if cfg[key] != want:
            raise ValueError(f"{cfg['name']}: the program computes only "
                             f"{key}={want!r}, the file asks for {cfg[key]!r}")
    k = dims(cfg)
    y = k["yarn"]
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=k["L"], d_model=k["d"],
        n_heads=k["h"], n_kv_heads=k["h"], d_ff=k["f"], vocab=k["v"],
        n_experts=k["experts"], experts_per_token=k["k"], moe_d_ff=k["ff"],
        n_shared_experts=k["shared"], first_dense_layers=k["n_dense"],
        router_score="sigmoid", n_groups=k["groups"],
        topk_groups=k["topk_groups"], routed_scale=k["scale"],
        ep_size=k["experts"] // k["held"], ep_rank=k["first"] // k["held"],
        mla=MLAConfig(q_lora_rank=k["q_lora"], kv_lora_rank=k["kv_lora"],
                      qk_nope_head_dim=k["nope"], qk_rope_head_dim=k["rope"],
                      v_head_dim=k["vd"]),
        mtp_depth=0, rope_theta=k["theta"],
        rope_scaling=YaRN(factor=y["factor"],
                          original_max_position=y["original"],
                          beta_fast=y["beta_fast"], beta_slow=y["beta_slow"],
                          mscale=y["mscale"],
                          mscale_all_dim=y["mscale_all_dim"]),
        norm_eps=k["eps"], tie_embeddings=False, dtype=cfg["torch_dtype"])


# ------------------------------------------------- what a decode step needs
def _attn_params(k: dict) -> int:
    """Weights of one layer's attention share: q_a, q_b, kv_a, kv_b, o."""
    qk = k["nope"] + k["rope"]
    return (k["d"] * k["q_lora"] + k["q_lora"] * k["h"] * qk
            + k["d"] * (k["kv_lora"] + k["rope"])
            + k["kv_lora"] * k["h"] * (k["nope"] + k["vd"])
            + k["h"] * k["vd"] * k["d"])


def _expert_params(k: dict) -> int:
    return 3 * k["d"] * k["ff"]


def step_need(cfg: dict, *, tokens: float, cached: float, touched: float,
              held_picks: float) -> tuple[float, float]:
    """Bytes and operations one decode step needs for ``tokens`` live
    sequences holding ``cached`` positions between them, where the routing
    touched ``touched`` held experts (summed over MoE layers) with
    ``held_picks`` token picks.

    Bytes: every weight the step must read once (attention, norms, the dense
    MLP, the shared experts, the router and its bias, the head, the final
    norm), the touched experts' weights, the embedding rows of the tokens,
    the live latents read once and the new ones written once. Operations:
    2 per multiply-add of the absorbed step (kv_b applied to queries and
    outputs, not to the cache), the scores over 576 latent values and the
    weighted sum over 512 per cached position and head, the routed experts
    for their picks, and the head."""
    k = dims(cfg)
    d, L, h = k["d"], k["L"], k["h"]
    wb = 2                                         # bfloat16
    latent = (k["kv_lora"] + k["rope"]) * wb       # bytes per position/layer
    dense_mlp = 3 * d * k["f"]
    shared = k["shared"] * _expert_params(k)
    router = d * k["experts"]
    fixed = (L * (_attn_params(k) + 2 * d + k["q_lora"] + k["kv_lora"])
             + k["n_dense"] * dense_mlp + k["n_moe"] * shared
             + d * k["vp"] + d) * wb \
        + k["n_moe"] * (router + k["experts"]) * 4
    need_b = (fixed + touched * _expert_params(k) * wb + tokens * d * wb
              + (cached + tokens) * L * latent)
    per_token = 2.0 * (L * _attn_params(k) + k["n_dense"] * dense_mlp
                       + k["n_moe"] * (shared + router) + d * k["v"])
    attn = (cached + tokens) * L * h * 2.0 * (
        k["kv_lora"] + k["rope"] + k["kv_lora"])
    need_f = tokens * per_token + attn + held_picks * 2.0 * _expert_params(k)
    return float(need_b), float(need_f)


# ----------------------------------------------------------------- weights
def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed (64 bits of it)."""
    s = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        np.array([s >> 32, s & 0xFFFFFFFF], np.uint32))


def _layer_key(key: jax.Array, moe: bool, li) -> jax.Array:
    """The key a layer's weights are made from: layer ``li`` of the dense
    layers, or of the MoE layers."""
    return jax.random.fold_in(jax.random.fold_in(key, 1 + int(moe)), li)


def _published_parts(cfg: dict):
    """(top, layer): ``top(key)`` makes the embedding, the head and the final
    norm; ``layer(key, moe)`` one layer's weights for this chip's share,
    bfloat16 (the router bias float32). Fan-ins are the published model's,
    so a share's output is the part it adds to the whole layer's."""
    k = dims(cfg)
    d, h, qk = k["d"], k["h"], k["nope"] + k["rope"]
    pub_h = int(cfg["published"]["num_attention_heads"])
    dt = jnp.dtype(cfg["torch_dtype"])
    out_div = math.sqrt(2.0 * k["L"])
    E, ff = k["held"], k["ff"]
    fs = ff * k["shared"]

    def normal(key, shape, std, dtype=dt):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    def norm_w(key, shape):
        return (1.0 + NORM_STD * jax.random.normal(key, shape, jnp.float32)
                ).astype(dt)

    def mlp(key, shape_in, shape_out, f):
        ks = jax.random.split(key, 3)
        return {"gate": normal(ks[0], shape_in, d ** -0.5),
                "up": normal(ks[1], shape_in, d ** -0.5),
                "down": normal(ks[2], shape_out, f ** -0.5 / out_div)}

    def top(key):
        ks = jax.random.split(jax.random.fold_in(key, 0), 3)
        return {"embed": normal(ks[0], (k["vp"], d), EMBED_STD),
                "head": normal(ks[1], (d, k["vp"]), d ** -0.5),
                "final_norm": norm_w(ks[2], (d,))}

    def layer(key, moe: bool):
        ks = jax.random.split(key, 12)
        w = {"ln1": norm_w(ks[0], (d,)), "ln2": norm_w(ks[1], (d,)),
             "attn": {"q_a": normal(ks[2], (d, k["q_lora"]), d ** -0.5),
                      "q_a_norm": norm_w(ks[3], (k["q_lora"],)),
                      "q_b": normal(ks[4], (k["q_lora"], h * qk),
                                    k["q_lora"] ** -0.5),
                      "kv_a": normal(ks[5], (d, k["kv_lora"] + k["rope"]),
                                     d ** -0.5),
                      "kv_a_norm": norm_w(ks[6], (k["kv_lora"],)),
                      "kv_b": normal(ks[7], (k["kv_lora"],
                                             h * (k["nope"] + k["vd"])),
                                     k["kv_lora"] ** -0.5),
                      "o": normal(ks[8], (h * k["vd"], d),
                                  (pub_h * k["vd"]) ** -0.5 / out_div)}}
        if not moe:
            w["mlp"] = mlp(ks[9], (d, k["f"]), (k["f"], d), k["f"])
            return w
        kr = jax.random.split(ks[9], 2)
        w["router"] = normal(kr[0], (d, k["experts"]), d ** -0.5)
        w["bias"] = normal(kr[1], (k["experts"],), BIAS_STD, jnp.float32)
        w["experts"] = mlp(ks[10], (E, d, ff), (E, ff, d), ff)
        w["shared"] = mlp(ks[11], (d, fs), (fs, d), fs)
        return w

    return top, layer


def _published(cfg: dict):
    """key -> the published model's weights for this chip's share, layers
    stacked: ``dense`` (the leading dense layers) and ``moe``."""
    k = dims(cfg)
    top, layer = _published_parts(cfg)

    def build(key):
        def stack(moe, n):
            return jax.vmap(lambda i: layer(_layer_key(key, moe, i), moe))(
                jnp.arange(n))
        return dict(top(key), dense=stack(False, k["n_dense"]),
                    moe=stack(True, k["n_moe"]))

    return build


def _deinterleave(w: jax.Array, heads: int, rope: int) -> jax.Array:
    """The last ``rope`` columns of each of ``heads`` column groups of ``w``
    from interleaved pairs (0, 1), (2, 3), ... to halves: column j of the
    result's rope part is column perm[j], evens first, then odds."""
    perm = np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])
    lead = w.shape[:-1]
    g = w.reshape(*lead, heads, w.shape[-1] // heads)
    width = g.shape[-1]
    idx = np.concatenate([np.arange(width - rope), width - rope + perm])
    return g[..., idx].reshape(w.shape)


def fold(cfg: dict, pub):
    """The published weights in the program's layout (see the module's
    docstring)."""
    k = dims(cfg)
    dt = jnp.dtype(cfg["torch_dtype"])

    def less1(x):
        return (x.astype(jnp.float32) - 1.0).astype(dt)

    def attn(a):
        return {"wq_a": a["q_a"], "q_norm": less1(a["q_a_norm"]),
                "wq_b": _deinterleave(a["q_b"], k["h"], k["rope"]),
                "wkv_a": _deinterleave(a["kv_a"], 1, k["rope"]),
                "kv_norm": less1(a["kv_a_norm"]), "wkv_b": a["kv_b"],
                "wo": a["o"]}

    def mlp(m):
        return {"w1": m["gate"], "w3": m["up"], "w2": m["down"]}

    dn, mo = pub["dense"], pub["moe"]
    return {
        "embed": {"tok": pub["embed"], "head": pub["head"]},
        "dense_blocks": {"ln1": less1(dn["ln1"]), "attn": attn(dn["attn"]),
                         "ln2": less1(dn["ln2"]), "mlp": mlp(dn["mlp"])},
        "moe_blocks": {"ln1": less1(mo["ln1"]), "attn": attn(mo["attn"]),
                       "ln2": less1(mo["ln2"]),
                       "moe": dict(mlp(mo["experts"]),
                                   router=mo["router"].astype(jnp.float32),
                                   router_bias=mo["bias"],
                                   shared=mlp(mo["shared"]))},
        "final_norm": less1(pub["final_norm"]),
    }


def make_published(cfg: dict, seed: int):
    """The published model's weights as the key they are made from: the
    reference makes each layer's weights from it on the device when it runs
    that layer, so that at most one layer is there at a time beside what
    the serving loop still holds."""
    return seed_key(seed)


def make_weights(cfg: dict, seed: int):
    """The served weights in the program's layout, made on the device in one
    jitted call from ``seed``."""
    build = _published(cfg)
    return jax.jit(lambda key: fold(cfg, build(key)))(seed_key(seed))


# --------------------------------------------------------------- reference
def _fp8(x: jax.Array) -> jax.Array:
    """``x`` rounded to float8 e4m3 with one scale per tensor, back in f32."""
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8: bool):
    """x @ w in float32 at HIGHEST; ``w`` is read as float32 here, so that a
    layer's weights stay in their dtype on the device."""
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x), _fp8(w)
    return jnp.matmul(x, w, precision=HIGHEST)


def _norm(x, w, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * w.astype(jnp.float32))


def _yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(k: dict) -> np.ndarray:
    """DeepSeek-V3's YaRN inverse frequencies for the rope dims (float64)."""
    y, dim, base = k["yarn"], k["rope"], k["theta"]
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / y["factor"]

    def corr(rot):
        return (dim * math.log(y["original"] / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    lo = max(math.floor(corr(y["beta_fast"])), 0)
    hi = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def _rope(x, pos, k: dict):
    """x: (S, H, rope), pairs interleaved: (x[2i], x[2i+1]) rotated by
    pos * inv_freq[i], cos and sin times YaRN's mscale ratio."""
    y = k["yarn"]
    m = (_yarn_mscale(y["factor"], y["mscale"])
         / _yarn_mscale(y["factor"], y["mscale_all_dim"]))
    inv = jnp.asarray(yarn_inv_freq(k), jnp.float32)
    ang = pos[:, None].astype(jnp.float32) * inv                 # (S, r/2)
    c, s = (m * jnp.cos(ang))[:, None, :], (m * jnp.sin(ang))[:, None, :]
    xr = x.reshape(*x.shape[:-1], -1, 2)
    a, b = xr[..., 0], xr[..., 1]
    return jnp.stack([a * c - b * s, a * s + b * c], -1).reshape(x.shape)


def _route(k: dict, w: dict, x, fp8: bool):
    """(T, d) -> (T, E) weight of every routed expert for every token: the
    published noaux_tc rule, zero where not chosen."""
    logits = _mm(x, w["router"], fp8)
    scores = jax.nn.sigmoid(logits)
    choice = scores + w["bias"].astype(jnp.float32)
    T, E = choice.shape
    g = choice.reshape(T, k["groups"], E // k["groups"])
    gs = jnp.sort(g, -1)[..., -2:].sum(-1)                        # (T, G)
    g_rank = jnp.argsort(jnp.argsort(-gs, -1), -1)
    keep = g_rank < k["topk_groups"]
    choice = jnp.where(keep[:, :, None], g, -jnp.inf).reshape(T, E)
    rank = jnp.argsort(jnp.argsort(-choice, -1), -1)
    chosen = rank < k["k"]
    wsel = jnp.where(chosen, scores, 0.0)
    return k["scale"] * wsel / wsel.sum(-1, keepdims=True)


def _mlp(m: dict, x, fp8: bool):
    return _mm(jax.nn.silu(_mm(x, m["gate"], fp8)) * _mm(x, m["up"], fp8),
               m["down"], fp8)


def _layer_fn(cfg: dict, fp8: bool, moe: bool):
    k = dims(cfg)
    h_, nope, rope, vd = k["h"], k["nope"], k["rope"], k["vd"]
    qk = nope + rope
    y = k["yarn"]
    scale = qk ** -0.5 * _yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2
    make = _published_parts(cfg)[1]

    def layer(key, li, hid):
        # the layer's weights, made here and read in float32 by each matmul
        w = make(_layer_key(key, moe, li), moe)
        a = w["attn"]
        S = hid.shape[0]
        pos = jnp.arange(S)
        x = _norm(hid, w["ln1"], k["eps"])
        q = _mm(_norm(_mm(x, a["q_a"], fp8), a["q_a_norm"], k["eps"]),
                a["q_b"], fp8).reshape(S, h_, qk)
        kv = _mm(x, a["kv_a"], fp8)
        c = _norm(kv[:, :k["kv_lora"]], a["kv_a_norm"], k["eps"])
        k_pe = _rope(kv[:, None, k["kv_lora"]:], pos, k)          # (S,1,r)
        kvb = _mm(c, a["kv_b"], fp8).reshape(S, h_, nope + vd)
        keys = jnp.concatenate(
            [kvb[..., :nope], jnp.broadcast_to(k_pe, (S, h_, rope))], -1)
        vals = kvb[..., nope:]
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, k)], -1)
        outs = []
        for c0 in range(0, S, Q_CHUNK):
            qc = q[c0:c0 + Q_CHUNK]
            s = jnp.einsum("qhd,khd->hqk", qc, keys, precision=HIGHEST) * scale
            ok = pos[None, :] <= (c0 + jnp.arange(qc.shape[0]))[:, None]
            s = jnp.where(ok[None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            outs.append(jnp.einsum("hqk,khd->qhd", p, vals, precision=HIGHEST))
        o = jnp.concatenate(outs, 0).reshape(S, h_ * vd)
        hid = hid + _mm(o, a["o"], fp8)
        x = _norm(hid, w["ln2"], k["eps"])
        if not moe:
            return hid + _mlp(w["mlp"], x, fp8)
        weight = _route(k, w, x, fp8)                             # (S, E)
        out = _mlp(w["shared"], x, fp8)
        for e in range(k["held"]):
            m = jax.tree.map(lambda t: t[e], w["experts"])
            out = out + weight[:, k["first"] + e, None] * _mlp(m, x, fp8)
        return hid + out

    return jax.jit(layer)


def _head_fn(k: dict, fp8: bool):
    def head(top, hid, rows):
        x = _norm(hid[rows], top["final_norm"].astype(jnp.float32), k["eps"])
        w = top["head"][:, :k["v"]].astype(jnp.float32)
        return _mm(x, w, fp8)

    return jax.jit(head)


class Reference:
    """Logits of the published model's plain forward pass (this chip's
    share) at chosen positions of a sequence, in float32 (or, as the
    control, with every weight and matmul input rounded to float8), over
    the weights made from the key of ``make_published``."""

    def __init__(self, cfg: dict, key, *, fp8: bool = False) -> None:
        self.k = dims(cfg)
        self.key = key
        self.top = jax.jit(_published_parts(cfg)[0])(key)
        self._dense = _layer_fn(cfg, fp8, moe=False)
        self._moe = _layer_fn(cfg, fp8, moe=True)
        self._head = _head_fn(self.k, fp8)
        self._embed = jax.jit(lambda e, t: e[t].astype(jnp.float32))

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
        h = self._embed(self.top["embed"], toks)
        for li in range(self.k["n_dense"]):
            h = self._dense(self.key, np.int32(li), h)
        for li in range(self.k["n_moe"]):
            h = self._moe(self.key, np.int32(li), h)
        out = self._head(self.top, h, r)
        return np.asarray(out, np.float32)[:len(rows)]


def served_rows(prompt_len: int, n_served: int) -> np.ndarray:
    """Positions whose logits chose the served tokens: the prompt's last
    position chose the first, each served token's position the next."""
    return np.arange(prompt_len - 1, prompt_len - 1 + n_served)


def _gaps(ref: Reference, prompt, served, chosen=None) -> np.ndarray:
    """Per served position, how far the reference's logit of the token
    chosen there (the served one, or ``chosen``) lies below its best."""
    served = np.asarray(served)
    seq = list(prompt) + list(served[:-1])
    lg = ref.logits(seq, served_rows(len(prompt), len(served)))
    pick = served if chosen is None else chosen(seq, len(prompt), len(served))
    return lg.max(-1) - lg[np.arange(len(served)), pick]


def widest_gap(ref: Reference, prompt, served) -> float:
    """The mean gap, over a session's served tokens, by which a served
    token's reference logit lies below the reference's best at its position
    (the serving loop judges the widest over the sessions it checks).

    Not the widest single token's gap, as for a dense model: with random
    weights the sigmoid router's scores hold near-ties, bfloat16 rounding of
    its input flips a few choices that float32 makes otherwise, and a token
    whose held experts change moves by up to 1.7 logits (PERF.md, "Limits").
    A fault, or a lower precision throughout, moves most tokens."""
    served = np.asarray(served)
    if ((served < 0) | (served >= ref.k["v"])).any():
        return float("inf")                  # not a token of the vocabulary
    return float(_gaps(ref, prompt, served).mean())


def control_gap(ref: Reference, control: Reference, prompt, served) -> float:
    """The same reading for the control: at each position of the same
    prompt and served tokens, the gap of the token the control puts first."""
    def first(seq, n_prompt, n):
        return control.logits(seq, served_rows(n_prompt, n)).argmax(-1)

    return float(_gaps(ref, prompt, served, first).mean())
