"""Mixture-of-Experts layer: top-k token-choice routing, capacity buffers,
expert-parallel einsums.

Routing is sort-based (no (T, E, C) one-hot dispatch tensor — that would be
O(T*E*C) memory): tokens are replicated k ways, sorted by expert id, and
scattered into a ``(E, C, D)`` capacity buffer which is what the experts'
batched einsums consume.  The expert dimension is tensor-parallel
(``hint(..., TP)``), so the scatter/gather lower to all-to-all-style
collectives under pjit — expert parallelism.

Overflow beyond capacity ``C = ceil(T*k/E * capacity_factor)`` is dropped
(standard GShard/Switch behaviour); the router aux loss keeps loads balanced.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.utils import DP, TP, hint
from .layers import he_init


def _maybe_expert_parallel(p, x, cfg: ModelConfig, no_drop: bool):
    """Expert-parallel dispatch under an explicit shard_map (§Perf pair B).

    Key observation: the token activations are already replicated across
    the model axis (TP keeps the residual stream replicated), so expert
    parallelism needs NO token exchange at all — each model shard routes
    the full local token set, builds capacity buffers for its E/|model|
    local experts, runs the expert FFNs, and contributes a partial (T, D)
    output; a single activation-sized ``psum`` over 'model' combines.
    Returns None when no mesh/model axis is active (CPU smoke path).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if "model" not in mesh.axis_names:
        return None
    manual = set(mesh.manual_axes)
    if "model" in manual:
        return None
    n_shards = mesh.shape["model"]
    if cfg.n_experts % n_shards:
        return None

    from jax.sharding import PartitionSpec as P

    B, S, D = x.shape
    # Manualize the batch over the still-auto dp axes too (when divisible):
    # otherwise the router argsort over the dp-sharded token dim makes the
    # partitioner gather all tokens.  In the train path dp is already
    # manual (outer shard_map) and this is a no-op.
    import math as _m
    dp = [a for a in mesh.axis_names
          if a != "model" and a not in manual]
    dp_size = _m.prod(mesh.shape[a] for a in dp) if dp else 1
    if not dp or B % dp_size:
        dp = []
    xspec = P(tuple(dp) if len(dp) > 1 else (dp[0] if dp else None),
              None, None)
    wspec = P("model", None, None)    # (E, D, F) sharded on experts

    def body(xb, router_w, wg, wi, wo):
        shard = jax.lax.axis_index("model")
        E_loc = wg.shape[0]
        y, aux = _moe_local(xb, router_w, wg, wi, wo, cfg,
                            e_offset=shard * E_loc, no_drop=no_drop)
        if dp:
            aux = jax.lax.pmean(aux, tuple(dp))
        return jax.lax.psum(y, "model"), aux

    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, P(), wspec, wspec, wspec),
        out_specs=(xspec, P()),
        axis_names={"model"} | set(dp), check_vma=False)
    return f(x, p["router"]["w"], p["wg"], p["wi"], p["wo"])


def _moe_local(x, router_w, wg, wi, wo, cfg: ModelConfig, e_offset,
               no_drop: bool):
    """Routing + capacity dispatch + FFN for a LOCAL slice of experts.

    x: (B, S, D) local tokens; wg/wi/wo: (E_loc, ...) local expert weights.
    Tokens routed to non-local experts contribute nothing here (their
    output comes from the owning shard via the caller's psum).
    """
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    E_loc = wg.shape[0]
    T = B * S
    xt = x.reshape(T, D)

    logits = xt.astype(jnp.float32) @ router_w                 # (T, E) full
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, eids = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True) + 1e-9)

    density = jnp.mean(jax.nn.one_hot(eids[:, 0], E), axis=0)
    aux = jnp.sum(density * jnp.mean(probs, axis=0)) * E * cfg.router_aux_coef

    C = T if no_drop else min(T, max(1, int(-(-T * k // E)
                                            * cfg.capacity_factor)))
    flat_e = eids.reshape(-1) - e_offset                        # local ids
    flat_g = gate_vals.reshape(-1)
    tok_id = jnp.repeat(jnp.arange(T), k)
    is_local = (flat_e >= 0) & (flat_e < E_loc)
    sort_key = jnp.where(is_local, flat_e, E_loc)               # strangers last
    order = jnp.argsort(sort_key)
    se, sg, st = sort_key[order], flat_g[order], tok_id[order]
    keep_local = se < E_loc
    counts = jnp.bincount(jnp.where(is_local, flat_e, E_loc), length=E_loc + 1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * k) - starts[jnp.minimum(se, E_loc)]
    keep = keep_local & (pos < C)
    se_c = jnp.minimum(se, E_loc - 1)
    pos_c = jnp.clip(pos, 0, C - 1)

    buf = jnp.zeros((E_loc, C, D), xt.dtype)
    buf = buf.at[se_c, pos_c].add(jnp.where(keep[:, None], xt[st], 0))
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg.astype(buf.dtype))) \
        * jnp.einsum("ecd,edf->ecf", buf, wi.astype(buf.dtype))
    out_buf = jnp.einsum("ecf,efd->ecd", h, wo.astype(buf.dtype))

    expert_out = out_buf[se_c, pos_c]
    w = jnp.where(keep, sg, 0.0)[:, None].astype(expert_out.dtype)
    y = jnp.zeros((T, D), expert_out.dtype).at[st].add(expert_out * w)
    return y.reshape(B, S, D), aux


def init_moe(key, cfg: ModelConfig, dtype):
    E, D, F = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 4)
    return {
        "router": {"w": he_init(ks[0], (D, E), jnp.float32)},
        "wg": he_init(ks[1], (E, D, F), dtype),
        "wi": he_init(ks[2], (E, D, F), dtype),
        "wo": he_init(ks[3], (E, F, D), dtype),
    }


def moe_block(p, x, cfg: ModelConfig, no_drop: bool = False):
    """x: (B, S, D) -> (y, aux_loss).

    ``no_drop=True`` (decode path) sets capacity C=T so no token is ever
    dropped — at decode T = batch, so the buffers stay tiny and serving is
    exact w.r.t. the routing decision.

    With ``cfg.moe_expert_parallel`` and an active mesh, dispatch runs under
    an explicit expert-parallel shard_map (see ``moe_block_ep``): the
    auto-partitioner otherwise lowers the buffer scatter/gather into
    full-activation all-reduces per layer (measured 2 x 68 GB/layer on
    qwen3-moe prefill — §Perf pair B).
    """
    if cfg.moe_expert_parallel:
        out = _maybe_expert_parallel(p, x, cfg, no_drop)
        if out is not None:
            return out
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    T = B * S
    xt = x.reshape(T, D)

    logits = (xt.astype(jnp.float32) @ p["router"]["w"])        # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, eids = jax.lax.top_k(probs, k)                   # (T, k)
    gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True) + 1e-9)

    # Switch-style load-balance aux loss.
    density = jnp.mean(jax.nn.one_hot(eids[:, 0], E), axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * E * cfg.router_aux_coef

    # ---- sort-based dispatch into (E, C, D) capacity buffers ----
    C = T if no_drop else min(T, max(1, int(-(-T * k // E)
                                            * cfg.capacity_factor)))
    flat_e = eids.reshape(-1)                                   # (T*k,)
    flat_g = gate_vals.reshape(-1)
    tok_id = jnp.repeat(jnp.arange(T), k)

    order = jnp.argsort(flat_e)                                 # stable
    se, sg, st = flat_e[order], flat_g[order], tok_id[order]
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * k) - starts[se]                        # pos in expert
    keep = pos < C
    pos_c = jnp.minimum(pos, C - 1)

    buf = jnp.zeros((E, C, D), xt.dtype)
    gathered = jnp.where(keep[:, None], xt[st], 0)
    buf = buf.at[se, pos_c].add(gathered)
    buf = hint(buf, TP, None, None)                             # expert-parallel

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(buf.dtype))) \
        * jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(buf.dtype))
    h = hint(h, TP, None, None)
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(buf.dtype))
    out_buf = hint(out_buf, TP, None, None)

    # ---- combine: weighted gather back to tokens ----
    expert_out = out_buf[se, pos_c]                             # (T*k, D)
    w = jnp.where(keep, sg, 0.0)[:, None].astype(expert_out.dtype)
    y = jnp.zeros((T, D), expert_out.dtype).at[st].add(expert_out * w)
    return hint(y.reshape(B, S, D), DP, None, None), aux
