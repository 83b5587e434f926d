"""Per-leaf selection/encode math shared by every transport.

``repro.core.dcsgd`` historically owned these helpers privately; the
gossip transport (repro/comm/gossip.py) needs the identical selection,
scatter, and EF-residual primitives but must not import dcsgd (dcsgd
imports ``repro.comm``, which imports gossip — a cycle).  This module is
the neutral home: pure leaf math with no knowledge of any collective
schedule.  dcsgd re-exports these under its old underscore names, so the
numerics — and therefore the transport parity contracts — are untouched.

:func:`select_and_encode` is the whole-tree selection stage of the
bucketed wire pipeline (DESIGN.md §11 steps before the gather): fused or
unfused per-leaf compression at the static budget, per-round valid
counts (§9), and the ``(vals, idx, counts)`` rows ``encode_buckets``
consumes.  Both the bucketed all_gather transport and the gossip
ppermute transport run this exact stage, which is what makes their EF
memories and byte counters bit-identical on identical inputs.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.kernels import ops
from .compression import Compressor, block_extract_sparse

AxisNames = Sequence[str] | str


def dp_size(dp_axes: AxisNames):
    return jax.lax.axis_size(dp_axes)


def dp_index(dp_axes: AxisNames):
    """This worker's row in the all-gathered leading axis (lax.axis_index
    handles axis tuples row-major, matching all_gather's stacking order)."""
    axes = dp_axes if isinstance(dp_axes, str) else tuple(dp_axes)
    return jax.lax.axis_index(axes)


def per_layer_topk(acc2d: jax.Array, k: int):
    """Batched exact top-k over the last axis. acc2d: (L, d)."""
    mag = jnp.abs(acc2d)
    _, idx = jax.lax.top_k(mag, k)                     # (L, k)
    vals = jnp.take_along_axis(acc2d, idx, axis=1)     # (L, k)
    return vals, idx.astype(jnp.int32)


def scatter_layers(vals: jax.Array, idx: jax.Array, L: int, d: int,
                   dtype) -> jax.Array:
    """Scatter (L, k) or gathered (W, L, k) sparse pairs into a dense
    (L, d) accumulator — the W axis (workers), when present, sums into
    the same layer rows."""
    if vals.ndim not in (2, 3):
        raise ValueError(f"expected (L, k) or (W, L, k), got {vals.shape}")
    vals = vals.reshape(-1, L, vals.shape[-1])
    idx = idx.reshape(vals.shape)
    W, _, k = vals.shape
    lidx = jnp.broadcast_to(jnp.arange(L)[None, :, None], (W, L, k))
    dense = jnp.zeros((L, d), dtype)
    return dense.at[lidx, idx].add(vals.astype(dtype))


def leaf_2d(x: jax.Array, stacked: bool) -> jax.Array:
    """(L, d) per-layer view of a leaf (L = 1 when unstacked)."""
    if stacked and x.ndim >= 2:
        return x.reshape(x.shape[0], -1)
    return x.reshape(1, -1)


def compress_leaf(acc: jax.Array, comp: Compressor, stacked: bool):
    """Per-leaf sparse compression. Returns (vals, idx, (L, d)) flat layout."""
    flat = leaf_2d(acc, stacked)
    L, d = flat.shape
    if comp.method == "block_topk" and d >= comp.min_compress_size:
        # block-local selection, batched over layers
        vals, idx = block_extract_sparse(flat, comp)
        return vals, idx, (L, d)
    vals, idx = per_layer_topk(flat, comp.k_for(d))
    return vals, idx, (L, d)


def leaf_count(comp: Compressor, spec, gamma_t, d: int):
    """Per-round valid count for one leaf's rows (DESIGN.md §9): the
    per-block ``k_b_t`` for block-local rows, the row ``k_t`` for flat
    rows.  None for non-ragged specs."""
    if not spec.ragged:
        return None
    return comp.block_k_t(gamma_t) if spec.local \
        else comp.k_t_for(d, gamma_t)


@dataclasses.dataclass
class Selection:
    """Whole-tree selection-stage outputs, indexed by leaf position.

    Entries are ``None`` for leaves the field doesn't apply to (dense
    leaves everywhere; ``acc2`` on the fused path, ``sent``/``resid`` on
    the unfused path).
    """

    use_fused: bool
    g2f: list          # (L, d) f32 gradient views (compressed leaves)
    acc2: list         # unfused: (L, d) f32 accumulator
    sent: list         # fused: kept entries ...
    resid: list        # ... and EF residual pair
    leaf_g_sq: list
    leaf_acc_sq: list
    enc_rows: list     # (vals, idx, counts) per compressed leaf
    counts: list       # scalar per-round count (ragged specs)


def select_and_encode(flat_g, flat_m, flat_s, eta, comp: Compressor,
                      gamma_t, plan) -> Selection:
    """The batched selection stage every bucketed-wire transport shares
    (DESIGN.md §11): ONE fused-EF two-pass launch pair over every
    kernel-path leaf, per-leaf selection at the static budget, per-round
    valid counts, and the encode rows for ``encode_buckets``.  Selection
    is per leaf BY DESIGN — the contraction constant is per layer row;
    only the collective schedule differs between transports.
    """
    with jax.named_scope("csgd_ef"):
        use_fused = comp.method == "block_topk" and comp.use_kernel
        lanes = plan.leaves
        n = len(lanes)
        comp_ids = list(plan.compressed_ids)
        sel = Selection(use_fused, *([None] * n for _ in range(8)))
        pairs = {}
        if use_fused and comp_ids:
            ms = [leaf_2d(flat_m[i], flat_s[i]).astype(jnp.float32)
                  for i in comp_ids]
            gs = [leaf_2d(flat_g[i], flat_s[i]).astype(jnp.float32)
                  for i in comp_ids]
            # one pass-1 + one pass-2 launch for ALL leaves; thresholds stay
            # at the BUDGET level exactly as in the per-leaf path.  Pass 1
            # ranks each block and hands out its k_b wire pairs: the same
            # entries and order as a block top-k_b of |sent| (ties past k_b
            # stay in sent, off the wire, and land in m' through
            # sent - own_dense), so no sort or gather runs here
            outs = ops.fused_ef_compress_batched(
                ms, gs, eta, comp.geometry_gamma, comp.block, telemetry=True)
            for i, g2, out in zip(comp_ids, gs, outs):
                sel.g2f[i], sel.sent[i], sel.resid[i] = g2, out.sent, \
                    out.resid
                pairs[i] = out.vals, out.idx
                # NB: the batched kernel's per-leaf outputs are bit-identical
                # to per-leaf launches, but THIS reduce may fuse differently
                # in the two programs — XLA does not pin f32 reduction order
                # across program shapes, so telemetry parity is a few-ulp
                # contract while every other output is bit-exact (DESIGN §11)
                sel.leaf_g_sq[i] = jnp.sum(out.moments[:, 0])
                sel.leaf_acc_sq[i] = jnp.sum(out.moments[:, 1])
        for i in comp_ids:
            lane = lanes[i]
            if use_fused:
                vals, idx = pairs[i]
            else:
                g2 = leaf_2d(flat_g[i], flat_s[i]).astype(jnp.float32)
                a2 = leaf_2d(flat_m[i], flat_s[i]).astype(jnp.float32) \
                    + eta * g2
                sel.g2f[i], sel.acc2[i] = g2, a2
                sel.leaf_g_sq[i] = jnp.sum(g2 * g2)
                sel.leaf_acc_sq[i] = jnp.sum(a2 * a2)
                vals, idx, _ = compress_leaf(a2, comp, flat_s[i])
            sel.counts[i] = leaf_count(comp, lane.spec, gamma_t, lane.d)
            sel.enc_rows[i] = (vals, idx,
                               None if sel.counts[i] is None
                               else jnp.broadcast_to(sel.counts[i], (lane.L,)))
        return sel
