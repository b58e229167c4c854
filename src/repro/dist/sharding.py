"""Divisibility-aware sharding rules for the production meshes.

Axis semantics (launch/mesh.py): ``pod`` = cross-pod DP over DCN, ``data`` =
in-pod DP + FSDP, ``model`` = TP/EP over ICI. Every rule here goes through
:func:`_check`, which drops any mesh axis that does not divide its dim —
assignments degrade to replication instead of failing at XLA lowering. All
functions accept either a concrete ``Mesh`` or an ``AbstractMesh`` (axis sizes
without devices), so the 16×16 / 2×16×16 rules are testable on one CPU.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

Pytree = Any

_DP_NAMES = ("pod", "data")


# ----------------------------------------------------------------- mesh intro
def mesh_axes(mesh) -> dict[str, int]:
    return dict(mesh.shape)


def axis_size(mesh, ax) -> int:
    """Size of one mesh axis or the product over a tuple of axes."""
    if ax is None:
        return 1
    if isinstance(ax, (tuple, list)):
        n = 1
        for a in ax:
            n *= axis_size(mesh, a)
        return n
    return mesh_axes(mesh)[ax]


def dp_axes(mesh) -> tuple[str, ...]:
    """Data-parallel axes in major-to-minor order (pod before data)."""
    names = tuple(mesh.axis_names)
    return tuple(a for a in _DP_NAMES if a in names)


def tp_axis(mesh) -> str | None:
    return "model" if "model" in tuple(mesh.axis_names) else None


# -------------------------------------------------------------- divisibility
def _fit(mesh, dim: int, candidates, used: set) -> str | tuple | None:
    """Greedily assign unused mesh axes to ``dim`` while the product divides.

    Returns a single axis name, a tuple of names, or None (replicate)."""
    if candidates is None:
        return None
    if not isinstance(candidates, (tuple, list)):
        candidates = (candidates,)
    sizes = mesh_axes(mesh)
    kept: list[str] = []
    prod = 1
    for a in candidates:
        if a is None or a in used or a not in sizes:
            continue                 # unknown axis: degrade, don't KeyError
        size = sizes[a]
        if size > 0 and dim % (prod * size) == 0:
            kept.append(a)
            prod *= size
            used.add(a)
    if not kept:
        return None
    return kept[0] if len(kept) == 1 else tuple(kept)


def _check(mesh, shape: Sequence[int], spec) -> P:
    """Validate a proposed spec against ``shape``: indivisible axes are
    dropped (replicated), and no mesh axis is used twice."""
    entries = tuple(spec)
    entries = entries + (None,) * (len(shape) - len(entries))
    used: set = set()
    return P(*[_fit(mesh, dim, ax, used) for dim, ax in zip(shape, entries)])


def named(mesh, specs: Pytree) -> Pytree:
    """PartitionSpec tree -> NamedSharding tree on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))


def _dp_entry(mesh):
    dp = dp_axes(mesh)
    return dp if len(dp) > 1 else (dp[0] if dp else None)


def _leaf_names(path) -> list[str]:
    return [str(k.key) for k in path if isinstance(k, jax.tree_util.DictKey)]


# ------------------------------------------------------------- param specs
# (in, out) matrices: shard the contraction dim on "data" (FSDP — the AD
# transpose of the all-gather is a reduce-scatter of the grads) and the output
# dim on "model" (TP).  Row-parallel outputs (wo, w2) are the reverse.
_COL = ("data", "model")
_ROW = ("model", "data")

# Expert-stacked weights (E, d, ff) / (E, ff, d): experts on "model" (EP),
# FSDP on ff — d is the first einsum's contraction dim and must stay whole.
_EXPERT_RULES = {
    "w1": ("model", None, "data"),
    "w3": ("model", None, "data"),
    "w2": ("model", "data", None),
}

_MATRIX_RULES = {
    # embeddings: vocab on model (TP logits), d on data
    "tok": ("model", "data"),
    "head": ("data", "model"),
    # attention / projections
    "wq": _COL, "wk": _COL, "wv": _COL, "wg": _COL, "wr": _COL,
    "wo": _ROW,
    # MLA low-rank factors
    "wq_a": _COL, "wq_b": _COL, "wkv_a": _COL, "wkv_b": _COL,
    # MLPs
    "w1": _COL, "w3": _COL, "w2": _ROW,
    # mamba2
    "in_proj": _COL, "out_proj": _ROW, "conv_w": (None, "model"),
    # rwkv loras
    "tm_w1": _COL, "w_w1": _COL, "w_w2": _ROW, "tm_w2": (None, None, "data"),
    # deepseek MTP fuse projection
    "proj": _COL,
    # router stays replicated (tiny, f32, read by every token)
    "router": (None, None),
}


def _param_template(names: list[str], leaf) -> tuple:
    name = names[-1] if names else ""
    if name in _EXPERT_RULES and "moe" in names and "shared" not in names:
        return _EXPERT_RULES[name]
    if name == "wv" and "cm" in names:        # rwkv channel-mix output proj
        return _ROW
    if leaf.ndim >= 2 and name in _MATRIX_RULES:
        return _MATRIX_RULES[name]
    return ()                                  # vectors / norms: replicate


def param_specs(cfg: ModelConfig, shapes: Pytree, mesh) -> Pytree:
    """PartitionSpec tree matching a param (shape) tree.

    Templates are right-aligned: leading stacked-layer/group dims replicate.
    Every assignment is divisibility-checked against ``mesh``."""

    def spec_for(path, leaf):
        tpl = _param_template(_leaf_names(path), leaf)
        full = (None,) * (leaf.ndim - len(tpl)) + tuple(tpl)
        return _check(mesh, leaf.shape, full)

    return jax.tree_util.tree_map_with_path(spec_for, shapes)


# ------------------------------------------------------------- batch specs
def batch_specs(cfg: ModelConfig, batch: Pytree, mesh) -> Pytree:
    """Inputs shard their leading (global-batch) dim over all DP axes; an
    indivisible batch (e.g. long_500k's batch=1) replicates."""

    def spec_for(path, leaf):
        tpl = (_dp_entry(mesh),) + (None,) * (leaf.ndim - 1)
        return _check(mesh, leaf.shape, tpl)

    return jax.tree_util.tree_map_with_path(spec_for, batch)


# ------------------------------------------------------- decode-state specs
def _cache_spec(mesh, shape, *, b_dim: int | None, s_dim: int | None,
                h_dim: int | None, heads: int | None = None) -> P:
    """The decode-cache rule, in priority order:

      1. heads (or the channel dim standing in for them) take "model" if they
         divide it — heads-local attention, no cross-chip KV traffic. Where
         the channel dim holds ``heads`` heads side by side, the heads must
         divide it: a head is never split;
      2. batch takes the DP axes;
      3. the sequence dim sweeps up whatever is left ("model" first — the
         kv<model GQA fallback — then unused DP axes when batch=1).
    """
    used: set = set()
    out: list = [None] * len(shape)
    tp = tp_axis(mesh)
    if h_dim is not None and tp is not None:
        out[h_dim] = _fit(mesh, heads or shape[h_dim], (tp,), used)
    if b_dim is not None:
        out[b_dim] = _fit(mesh, shape[b_dim], dp_axes(mesh), used)
    if s_dim is not None:
        rest = ((tp,) if tp else ()) + dp_axes(mesh)
        out[s_dim] = _fit(mesh, shape[s_dim], rest, used)
    return P(*out)


def decode_state_specs(cfg: ModelConfig, state: Pytree, mesh) -> Pytree:
    """Specs for a decode-state pytree (any family's ``init_decode_state``)."""
    from repro.models.model import pooled_cache_axes
    pooled = pooled_cache_axes(cfg)

    def spec_for(path, leaf):
        names = _leaf_names(path)
        name = names[-1] if names else ""
        nd = leaf.ndim
        if name == "pos" or nd == 1:
            return _check(mesh, leaf.shape, (_dp_entry(mesh),))
        if name in ("k", "v") and pooled is not None:
            return _cache_spec(mesh, leaf.shape, **pooled)
        if name in ("k", "v", "attn_k", "attn_v", "xk", "xv"):
            # (L, B, S, Hkv, hd) — vlm stacks an extra group dim in front
            return _cache_spec(mesh, leaf.shape, b_dim=nd - 4, s_dim=nd - 3,
                               h_dim=nd - 2)
        if name in ("moe_cache", "dense_cache"):
            if nd == 4:            # MLA latent cache (L, B, S, c)
                return _cache_spec(mesh, leaf.shape, b_dim=1, s_dim=2,
                                   h_dim=None)
            return _cache_spec(mesh, leaf.shape, b_dim=1, s_dim=2, h_dim=3)
        if name == "wkv":          # rwkv state (L, B, H, K, K): heads split
            return _cache_spec(mesh, leaf.shape, b_dim=1, s_dim=None, h_dim=2)
        if name == "ssm":          # mamba2 state (n, B, H, P, N)
            return _cache_spec(mesh, leaf.shape, b_dim=1, s_dim=None, h_dim=2)
        if name == "conv":         # conv window (n, B, w, ch): ch on model
            return _cache_spec(mesh, leaf.shape, b_dim=1, s_dim=None, h_dim=3)
        if name in ("tm_x", "cm_x"):   # rwkv shift state (L, B, d)
            return _cache_spec(mesh, leaf.shape, b_dim=1, s_dim=None, h_dim=2)
        if nd >= 2:                # unknown state: shard batch-ish dim only
            return _cache_spec(mesh, leaf.shape, b_dim=1, s_dim=None,
                               h_dim=None)
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(spec_for, state)
