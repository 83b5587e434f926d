"""Partition rules: parameter PartitionSpecs per model family.

Rules are *name-based* and use negative dim indices, so they apply uniformly
to unstacked, (L, ...)-stacked and (G, per, ...)-stacked leaves.  The model
axis shards: attention heads (qkv out-dim / o in-dim), MLP hidden, MoE
experts, SSM inner channels, vocab (embedding d_model / head vocab).
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

PyTree = Any

# (path-suffix match) -> dim (negative index) to shard over 'model'
_COL_NAMES = {"wq", "wk", "wv", "wg", "wi", "cm_k", "in_proj"}   # last dim
_ROW_NAMES = {"wo", "out_proj", "cm_v"}                          # dim -2
_VEC_LAST = {"conv_w", "conv_b", "A_log", "D_skip", "dt_bias", "u",
             "w_base", "ln_w", "ln_b"}


def _path_names(path) -> list[str]:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "name"):
            out.append(str(p.name))
        else:
            out.append(str(p))
    return out


def leaf_pspec(path, leaf) -> P:
    names = _path_names(path)
    ndim = leaf.ndim
    spec = [None] * ndim

    def set_dim(neg_idx):
        if ndim + neg_idx >= 0:
            spec[neg_idx] = "model"

    if "moe" in names:
        # router replicated; expert tensors sharded on E (dim -3)
        if names[-1] in ("wg", "wi", "wo"):
            set_dim(-3)
        return P(*spec)
    if "embed" in names:
        set_dim(-1)          # (V, D): shard d_model -> local token gather
        return P(*spec)
    if "lm_head" in names:
        set_dim(-1)          # (D, V): vocab-parallel logits
        return P(*spec)
    last = names[-1]
    parent = names[-2] if len(names) >= 2 else ""
    if last == "w" and parent in _COL_NAMES:
        set_dim(-1)
    elif last == "w" and parent in _ROW_NAMES:
        set_dim(-2)
    elif last == "b" and parent in _COL_NAMES:
        set_dim(-1)
    elif last in _COL_NAMES and ndim >= 2:      # rwkv raw arrays
        set_dim(-1)
    elif last in _ROW_NAMES and ndim >= 2:
        set_dim(-2)
    elif last in _VEC_LAST:
        if last == "u":
            set_dim(-2)
        elif last in ("w_base", "ln_w", "ln_b"):
            pass             # small per-channel vectors: replicate
        else:
            set_dim(-1)
    elif parent == "norm" and last == "w":
        # mamba gated-norm over sharded d_in
        set_dim(-1)
    return P(*spec)


def param_pspecs(params: PyTree, two_d: bool = False,
                 dp_axis: str = "data") -> PyTree:
    """Standard: model-axis TP only (replicated over dp — required for the
    per-worker gradient semantics of DCSGD-ASSS).

    ``two_d=True`` (serving only): additionally shard the largest
    still-replicated dim of every big leaf over ``dp_axis`` — per-chip
    weights drop from P/|model| to P/(|model|*|dp|) at the cost of a
    per-layer weight all-gather (XLA inserts it inside the layer scan).
    This is what lets llama3-405b fit a single v5e pod for serving.
    """
    specs = jax.tree_util.tree_map_with_path(leaf_pspec, params)
    if not two_d:
        return specs

    def widen(leaf, spec):
        if not hasattr(leaf, "ndim") or leaf.ndim < 2 or leaf.size < 2**20:
            return spec
        entries = list(spec) + [None] * (leaf.ndim - len(spec))
        # largest unsharded dim divisible by 16
        cand = [(leaf.shape[i], i) for i in range(leaf.ndim)
                if entries[i] is None and leaf.shape[i] % 16 == 0]
        if not cand:
            return spec
        _, dim = max(cand)
        entries[dim] = dp_axis
        return P(*entries)

    return jax.tree.map(widen, params, specs)


def param_shardings(params: PyTree, mesh) -> PyTree:
    """Training-path parameter placement (serving uses param_pspecs)."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_pspecs(params))


def dp_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")


def cache_pspecs(cache, dp, seq_axes) -> Any:
    """Decode-cache shardings (path-aware).

    * KV caches ``(..., B, S, H, hd)``: B over dp, S over ``seq_axes``
      (('model',) normally; every mesh axis when global batch = 1).
    * SSM states ``(..., B, H, hd, N)`` / RWKV wkv ``(..., B, H, hd, hd)``:
      B over dp, heads over 'model'.
    * conv states ``(..., B, K, C)``: B over dp, channels over 'model'.
    """
    dp_spec = (dp if len(dp) > 1 else dp[0]) if dp else None
    seq_spec = seq_axes if len(seq_axes) > 1 else seq_axes[0]

    def one(path, leaf):
        if not hasattr(leaf, "ndim"):
            return P()
        names = _path_names(path)
        ndim = leaf.ndim
        spec = [None] * ndim

        def put(i, v):
            if ndim + i >= 0 and v is not None:
                spec[i] = v

        leafname = names[-1] if names else ""
        if leafname in ("tm_prev", "cm_prev"):
            put(-2, dp_spec)
        elif leafname == "conv" or "conv" in names:
            put(-3, dp_spec)
            put(-1, "model")
        elif "kv" in names or "cross_kv" in names:
            put(-4, dp_spec)
            put(-3, seq_spec)
        elif "ssm" in names or "wkv" in names:
            put(-4, dp_spec)
            put(-3, "model")
        return P(*spec)

    return jax.tree_util.tree_map_with_path(one, cache)
