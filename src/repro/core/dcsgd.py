"""DCSGD-ASSS — distributed building blocks (paper Algorithm 3, appendix §VIII).

These functions run *inside* a ``jax.shard_map`` body that is manual over the
data-parallel mesh axes (``('pod','data')`` or ``('data',)``) and auto over
``'model'``.  Each data-parallel worker:

  1. computes its local gradient (done by the caller),
  2. runs its own Armijo search on its local batch -> per-worker ``eta^(k)``,
  3. forms ``acc = m^(k) + eta^(k) * grad^(k)`` per leaf,
  4. compresses ``acc`` to a (values, indices) pair and encodes it into a
     bit-packed ``uint32`` payload (repro/comm/wire.py, DESIGN.md §8),
  5. **all-gathers the packed payload** over the dp axes (this replaces the
     dense all-reduce; the payload's byte length IS ``wire_bytes`` — the
     paper's communication saving made physically real),
  6. decodes every worker's payload and applies the dense mean of the
     contributions,
  7. keeps ``m^(k) = acc - decode(own payload)`` locally (step 7 of
     Algorithm 3) — so wire quantization error and tie-dropped entries are
     recycled through the error feedback.

Leaves below the compression size threshold are aggregated densely
(``pmean``), matching §IV-A ("layers with less than 1000 parameters are not
compressed").

Scan-stacked leaves (leading axis = layers) are compressed **per layer**
(axis-0-batched top_k), matching the paper's per-layer compression.

Transport is **bucketed** by default (DESIGN.md §11): steps 4-6 coalesce
across the whole pytree into one flat packed all_gather, one batched
pack/unpack launch per bucket section, one batched fused-EF launch pair,
and one pmean of the concatenated dense leaves — the per-leaf schedule
above survives as ``transport="perleaf"``, the bit-exact reference.
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.comm import faults
from repro.comm import wire as wire_fmt
from repro.comm.bucket import (build_bucket_plan, decode_buckets,
                               encode_buckets)
from repro.comm.exchange import (check_bucket_payload, check_payload,
                                 gather_packed)
from repro.comm.transport import get_transport, register_transport
from repro.kernels import ops
from .compression import Compressor
from .leafmath import compress_leaf, select_and_encode
# the leaf math lives in repro.core.leafmath (shared with the gossip
# transport); the historical underscore names stay importable from here
from .leafmath import (dp_size as _dp_size, dp_index as _dp_index,
                       per_layer_topk as _per_layer_topk,
                       scatter_layers as _scatter_layers,
                       leaf_2d as _leaf_2d, leaf_count as _leaf_count)
from .telemetry import CompressionTelemetry, TelemetrySums, sparse_own_sums

PyTree = Any
AxisNames = Sequence[str] | str


def worker_compress_aggregate(
    grads: PyTree,
    memory: PyTree,
    eta: jax.Array,
    comp: Compressor,
    dp_axes: AxisNames,
    stacked_mask: PyTree | None = None,
    gamma_t: jax.Array | None = None,
    telemetry_axes: AxisNames | None = None,
    transport: str = "bucketed",
    transport_ctx: Any | None = None,
    downlink_ctx: Any | None = None,
) -> tuple:
    """Steps 3-7 of Algorithm 3 for a whole gradient pytree.

    Returns ``(mean_update, new_memory, wire_bytes, effective_wire_bytes,
    telemetry)`` where ``mean_update`` is the dense averaged compressed
    update (to subtract from params), ``wire_bytes`` counts this worker's
    transmitted payload-buffer bytes this step (the static budget), and
    ``telemetry`` is this worker's :class:`CompressionTelemetry` for the
    round (EF backlog, decode cosine, relative decode error, empirical
    contraction — DESIGN.md §10).  Its dense reductions are fused into the
    Pallas EF block-stats pass on the kernel path; the decoded-side sums
    touch only the k wire entries.

    ``transport`` (DESIGN.md §11): ``"bucketed"`` (default) coalesces the
    exchange into ONE flat packed ``all_gather`` for every compressed
    leaf, one batched ``wire_pack``/``wire_unpack`` launch per bucket
    field section, one batched fused-EF two-pass launch pair for every
    kernel-path leaf, and ONE ``pmean`` of the concatenated dense small
    leaves.  ``"perleaf"`` is the reference schedule (one collective and
    one launch set per leaf) the bucketed path is regression-pinned
    against: updates, memory, and byte counters bit-exact, telemetry to
    <= 8 ulp (XLA reduction order across programs — DESIGN.md §11).

    ``telemetry_axes``: extra manual mesh axes this call's inputs are
    sharded over WITHOUT being separate dp workers (the nested
    shard-local-topk 'model' region): the telemetry sums are psum'd over
    them before the ratios form, so the returned telemetry describes the
    worker's whole gradient, not one shard's slice.  The updates/memory/
    byte outputs are unaffected (selection stays shard-local by design).

    ``gamma_t`` (adaptive compressors, DESIGN.md §9): this worker's traced
    per-round compression level.  Selection still runs at the static
    ``k_max`` budget — the all-gathered buffer never changes shape — but
    entries ranked beyond ``k_t`` are masked behind the payload's
    valid-count header, receivers decode only the valid prefix (workers
    may carry *different* k_t), the masked entries recycle through the EF
    residual, and ``effective_wire_bytes`` reports what a ragged
    collective would have shipped.  For non-adaptive compressors the two
    byte counts coincide.

    ``transport_ctx``: transport-specific context, REQUIRED by stateful
    transports (``"gossip"``: a :class:`repro.comm.gossip.GossipCtx`) and
    rejected by stateless ones.  Stateful transports make this function
    return a SIXTH element, the transport's new carried state.

    ``downlink_ctx`` (DESIGN.md §15): a
    :class:`repro.comm.downlink.DownlinkCtx` carrying the server-side EF
    state — the replicated decoded mean is re-compressed through the same
    §8/§9 wire format before workers apply it (``decode(downlink
    payload)`` instead of the dense mean), with no extra collective.
    Only composes with the stateless global-aggregate transports
    (bucketed/perleaf); appends a trailing
    :class:`~repro.comm.downlink.DownlinkResult` element ``(new server
    state, downlink wire bytes, downlink effective bytes)``.
    """
    tp = get_transport(transport)
    if tp.stateful and transport_ctx is None:
        raise ValueError(f"transport {transport!r} is stateful and needs "
                         "transport_ctx")
    if not tp.stateful and transport_ctx is not None:
        raise ValueError(f"transport {transport!r} is stateless; "
                         "transport_ctx must be None")
    if downlink_ctx is not None and tp.stateful:
        raise ValueError(
            f"downlink_ctx needs a replicated global aggregate to "
            f"re-compress; transport {transport!r} is stateful "
            "(gossip/overlap have no single server-side mean)")
    W = _dp_size(dp_axes)
    flat_g, treedef = jax.tree.flatten(grads)
    flat_m = treedef.flatten_up_to(memory)
    if stacked_mask is None:
        flat_s = [leaf.ndim >= 2 for leaf in flat_g]
    else:
        flat_s = treedef.flatten_up_to(stacked_mask)

    if comp.adaptive and gamma_t is None:
        gamma_t = jnp.float32(comp.gamma)
    if tp.stateful:
        updates, new_mem, wire, eff_wire, sums, new_state = tp.exchange(
            flat_g, flat_m, flat_s, eta, comp, dp_axes, gamma_t, W,
            ctx=transport_ctx)
    else:
        updates, new_mem, wire, eff_wire, sums = tp.exchange(
            flat_g, flat_m, flat_s, eta, comp, dp_axes, gamma_t, W)
    if telemetry_axes is not None:
        # sums are additive; ratios are not — reduce BEFORE finalizing
        sums = jax.tree.map(lambda x: jax.lax.psum(x, telemetry_axes), sums)
    dl_result = None
    if downlink_ctx is not None:
        from repro.comm.downlink import DownlinkResult, apply_downlink
        updates, dl_state, down_wire, down_eff = apply_downlink(
            updates, flat_s, comp, downlink_ctx.state)
        dl_result = DownlinkResult(dl_state, down_wire, down_eff)
    out = (treedef.unflatten(updates), treedef.unflatten(new_mem), wire,
           eff_wire, sums.finalize())
    if tp.stateful:
        out = out + (new_state,)
    return out + (dl_result,) if dl_result is not None else out


def _consume_decoded_leaf(g, m, g2f, g_vals, g_idx, spec, L, d, count, W,
                          dp_axes, use_fused, sent, resid, acc2,
                          verdict=None):
    """Post-gather per-leaf consumer — THE definition of the transport
    parity contract, shared by both schedules: the mean update, this
    worker's EF residual (own rows sliced from the gathered decode — no
    second decode of the own payload), the byte costs, and the
    decoded-side telemetry sums.

    Returns ``(upd, mem_leaf, wire_add, eff_add, resid_sq, own_sq,
    own_dot_g, quar_rows)``; masked-beyond-k_t entries are absent from
    the decoded own rows, so — like quantization error and tie drops —
    they land in the residual.

    ``verdict`` ((W, L) bool, DESIGN.md §16): per-row decode validity.
    Invalid rows arrive already quarantined (zero mass), so the mean's
    denominator switches from W to the per-layer valid-row count — the
    fed support-weighted division, bit-exact to ``/ W`` when every row
    is valid — and an invalid OWN row freezes this leaf's EF residual
    for the round (the payload never reached anyone intact; re-sending
    the whole accumulator next round is the EF-correct response).
    """
    # decoding the gathered rows into the dense mean is the codec's; the
    # own rows' residual and its telemetry are the EF memory update
    with jax.named_scope("csgd_codec"):
        total = _scatter_layers(g_vals, g_idx, L, d, jnp.float32)
        if verdict is None:
            mean_dense = total / W
        else:
            # the §13 support-weighted division without its 0/0 `where`:
            # quarantined rows scatter zero mass, so an all-invalid layer
            # has an all-zero total and /max(s,1) already answers 0 — one
            # fewer (L, d) pass on the always-on clean path (1.05x gate)
            n_valid = jnp.sum(verdict.astype(jnp.float32), axis=0)  # (L,)
            mean_dense = total / jnp.maximum(n_valid[:, None], 1.0)
        upd = mean_dense.reshape(g.shape)
    wire_add = jnp.float32(L * spec.row_bytes)
    eff_add = (jnp.float32(L) * spec.effective_row_bytes(count)
               if spec.ragged else jnp.float32(L * spec.row_bytes))
    with jax.named_scope("csgd_ef"):
        w_idx = _dp_index(dp_axes)
        own_vals = jax.lax.dynamic_index_in_dim(g_vals, w_idx, 0,
                                                keepdims=False)
        own_idx = jax.lax.dynamic_index_in_dim(g_idx, w_idx, 0,
                                               keepdims=False)
        own_dense = _scatter_layers(own_vals, own_idx, L, d, jnp.float32)
        if use_fused:
            r = resid + (sent - own_dense)
        else:
            r = acc2 - own_dense
        quar = jnp.float32(0.0)
        if verdict is not None:
            own_ok = jax.lax.dynamic_index_in_dim(verdict, w_idx, 0,
                                                  keepdims=False)   # (L,)
            m2f = m.astype(jnp.float32).reshape(L, d)
            r = jnp.where(own_ok[:, None], r, m2f)
            quar = jnp.float32(verdict.size) - jnp.sum(n_valid)
        # telemetry: the decoded-side sums touch only the k wire entries;
        # sum m'^2 fuses into the residual's own materialization above
        leaf_own_sq, leaf_dot = sparse_own_sums(own_vals, own_idx, g2f)
        mem_leaf = r.reshape(m.shape).astype(m.dtype)
        resid_sq = jnp.sum(r * r)
    return (upd, mem_leaf, wire_add, eff_add, resid_sq, leaf_own_sq,
            leaf_dot, quar)


@register_transport("perleaf", description=(
    "reference schedule: one packed all_gather + one launch set per leaf"))
def _perleaf_exchange(flat_g, flat_m, flat_s, eta, comp, dp_axes, gamma_t,
                      W):
    """Reference transport: one packed all_gather + one launch set PER
    LEAF (plus one pmean per dense leaf).  The bucketed transport is
    regression-pinned bit-exact against this path."""
    use_fused = comp.method == "block_topk" and comp.use_kernel
    updates, new_mem = [], []
    wire = jnp.float32(0.0)
    eff_wire = jnp.float32(0.0)
    sums = TelemetrySums.zero()
    for leaf_i, (g, m, stacked) in enumerate(zip(flat_g, flat_m, flat_s)):
        g2 = _leaf_2d(g, stacked)
        L, d = g2.shape
        if comp.ships_dense(d):
            with jax.named_scope("csgd_codec"):
                acc = m.astype(jnp.float32) + eta * g.astype(jnp.float32)
                upd = jax.lax.pmean(acc, dp_axes)
            updates.append(upd)
            new_mem.append(jnp.zeros_like(m))
            wire = wire + jnp.float32(acc.size * acc.dtype.itemsize)
            eff_wire = eff_wire + jnp.float32(acc.size * acc.dtype.itemsize)
            sums = sums.add_dense(acc, g)
            continue
        with jax.named_scope("csgd_ef"):
            g2f = g2.astype(jnp.float32)
            if use_fused:
                # fused two-pass Pallas path (DESIGN.md §3): pass 1 streams
                # (m, g) once for the per-block k_b-th |m + eta*g| statistic
                # AND the dense telemetry moments (sum g^2, sum acc^2) on the
                # same resident tile; pass 2 streams them again and writes
                # (sent, m') — the accumulator never round-trips through HBM.
                m2 = _leaf_2d(m, stacked).astype(jnp.float32)
                # threshold at the BUDGET level (geometry_gamma == max_gamma
                # for adaptive compressors): pass 1 hands out exactly
                # block_k() budget pairs per block, in block top-k order
                # (ties at tau past k_b stay in sent, off the wire, and are
                # recycled into m' below); any per-round k_t mask is
                # applied at encode time
                out = ops.fused_ef_compress(
                    m2, g2f, eta, comp.geometry_gamma, comp.block,
                    telemetry=True)
                sent, resid, vals, idx = out.sent, out.resid, out.vals, \
                    out.idx
                leaf_g_sq = jnp.sum(out.moments[:, 0])
                leaf_acc_sq = jnp.sum(out.moments[:, 1])
            else:
                acc2 = _leaf_2d(m, stacked).astype(jnp.float32) + eta * g2f
                leaf_g_sq = jnp.sum(g2f * g2f)
                leaf_acc_sq = jnp.sum(acc2 * acc2)
                vals, idx, (L, d) = compress_leaf(acc2, comp, stacked)

        # ---- bit-packed wire (DESIGN.md §8): encode once, gather ONE
        # uint32 payload per leaf — the payload's byte length is exactly
        # Compressor.wire_bytes (checked at trace time below), and the EF
        # residual is taken against what receivers actually decode, so
        # quantization error AND tie-dropped entries are recycled.
        with jax.named_scope("csgd_codec"):
            spec = wire_fmt.WireSpec.for_row(comp, d)
            # per-round valid count (DESIGN.md §9): entries past it are
            # masked out of the payload behind the count header word
            count = _leaf_count(comp, spec, gamma_t, d)
            counts = None if count is None else jnp.broadcast_to(count, (L,))
            payload = wire_fmt.encode_rows(vals, idx, spec, counts=counts)
            check_payload(payload, spec, comp, d)

            all_pay = gather_packed(payload, dp_axes)        # (W, L, words)
            all_rows = faults.maybe_corrupt(
                all_pay.reshape(-1, spec.row_words), spec, leaf_i, L)
            g_vals, g_idx = wire_fmt.decode_rows(all_rows, spec)
            verdict = None
            if faults.guards_active():
                verdict = wire_fmt.row_verdict(all_rows, spec, g_vals, g_idx)
                g_vals, g_idx = wire_fmt.quarantine_rows(g_vals, g_idx,
                                                         verdict)
                verdict = verdict.reshape(W, L)
            g_vals = g_vals.reshape(W, L, spec.k)
            g_idx = g_idx.reshape(W, L, spec.k)
        (upd, mem_leaf, wire_add, eff_add, resid_sq, own_sq, own_dot,
         quar) = _consume_decoded_leaf(
                g, m, g2f, g_vals, g_idx, spec, L, d, count, W, dp_axes,
                use_fused, sent if use_fused else None,
                resid if use_fused else None,
                None if use_fused else acc2, verdict=verdict)
        updates.append(upd)
        new_mem.append(mem_leaf)
        wire = wire + wire_add
        eff_wire = eff_wire + eff_add
        sums = sums.add(g_sq=leaf_g_sq, acc_sq=leaf_acc_sq,
                        resid_sq=resid_sq, own_sq=own_sq,
                        own_dot_g=own_dot, quar_rows=quar)

    return updates, new_mem, wire, eff_wire, sums


@register_transport("bucketed", description=(
    "O(1) collectives: ONE flat packed all_gather + ONE pmean per step"))
def _bucketed_exchange(flat_g, flat_m, flat_s, eta, comp, dp_axes, gamma_t,
                       W):
    """Bucketed transport (DESIGN.md §11): the same per-leaf selection,
    EF, accounting, and telemetry as :func:`_perleaf_exchange` — but the
    step's collective/launch schedule is O(1), not O(leaves):

    * ONE batched fused-EF two-pass launch pair over every kernel-path
      leaf's concatenated block rows (``ops.fused_ef_compress_batched``);
    * ONE flat packed ``all_gather`` carrying every compressed leaf's
      exact payload rows back to back (``comm.bucket``), with one
      batched ``wire_pack``/``wire_unpack`` launch per bucket section;
    * ONE ``pmean`` of the concatenated dense small leaves.

    Per-leaf float accumulation order (wire/eff bytes, telemetry sums) is
    preserved, so updates/memory/byte outputs are bit-identical to the
    per-leaf path (telemetry to <= 8 ulp — see the reduce note below).
    """
    plan = build_bucket_plan([g.shape for g in flat_g], flat_s, comp)
    lanes = plan.leaves
    n = len(lanes)

    # ---- selection at the static budget, shared with the gossip
    # transport (repro.core.leafmath.select_and_encode): per-leaf BY
    # DESIGN — the contraction constant is per layer row; only the
    # collective schedule below is transport-specific
    sel = select_and_encode(flat_g, flat_m, flat_s, eta, comp, gamma_t,
                            plan)
    use_fused = sel.use_fused
    g2f, acc2, sent, resid = sel.g2f, sel.acc2, sel.sent, sel.resid
    leaf_g_sq, leaf_acc_sq = sel.leaf_g_sq, sel.leaf_acc_sq
    counts = sel.counts

    # ---- ONE flat all_gather for every compressed leaf ------------------
    decoded = [None] * n
    verdicts = [None] * n
    if plan.total_words:
        payload = encode_buckets(plan, sel.enc_rows)
        check_bucket_payload(payload, plan, comp)
        all_pay = gather_packed(payload, dp_axes)     # (W, total_words)
        if faults.guards_active():
            decoded, verdicts = decode_buckets(plan, all_pay,
                                               with_verdicts=True)
        else:
            decoded = decode_buckets(plan, all_pay)

    # ---- ONE pmean folds every dense small leaf -------------------------
    dense_acc = [None] * n
    dense_mean = [None] * n
    dense_ids = list(plan.dense_ids)
    with jax.named_scope("csgd_codec"):
        for i in dense_ids:
            dense_acc[i] = flat_m[i].astype(jnp.float32) \
                + eta * flat_g[i].astype(jnp.float32)
        if dense_ids:
            mean_cat = jax.lax.pmean(
                jnp.concatenate([dense_acc[i].reshape(-1)
                                 for i in dense_ids]), dp_axes)
            off = 0
            for i in dense_ids:
                size = dense_acc[i].size
                dense_mean[i] = mean_cat[off:off + size].reshape(
                    dense_acc[i].shape)
                off += size

    # ---- per-leaf consumers, ORIGINAL tree order (the f32 accumulation
    # order of the byte counters and telemetry sums is part of the
    # bit-exact parity contract with the per-leaf path)
    updates, new_mem = [], []
    wire = jnp.float32(0.0)
    eff_wire = jnp.float32(0.0)
    sums = TelemetrySums.zero()
    for lane, g, m in zip(lanes, flat_g, flat_m):
        i = lane.index
        if lane.dense:
            acc = dense_acc[i]
            updates.append(dense_mean[i])
            new_mem.append(jnp.zeros_like(m))
            wire = wire + jnp.float32(acc.size * acc.dtype.itemsize)
            eff_wire = eff_wire + jnp.float32(acc.size * acc.dtype.itemsize)
            sums = sums.add_dense(acc, g)
            continue
        spec, L, d = lane.spec, lane.L, lane.d
        g_vals, g_idx = decoded[i]
        (upd, mem_leaf, wire_add, eff_add, resid_sq, own_sq, own_dot,
         quar) = _consume_decoded_leaf(
                g, m, g2f[i], g_vals, g_idx, spec, L, d, counts[i], W,
                dp_axes, use_fused, sent[i], resid[i], acc2[i],
                verdict=verdicts[i])
        updates.append(upd)
        new_mem.append(mem_leaf)
        wire = wire + wire_add
        eff_wire = eff_wire + eff_add
        sums = sums.add(g_sq=leaf_g_sq[i], acc_sq=leaf_acc_sq[i],
                        resid_sq=resid_sq, own_sq=own_sq,
                        own_dot_g=own_dot, quar_rows=quar)

    return updates, new_mem, wire, eff_wire, sums


def dense_aggregate(grads: PyTree, eta: jax.Array,
                    dp_axes: AxisNames) -> tuple[PyTree, jax.Array]:
    """Baseline: dense pmean of eta*grad over dp axes (uncompressed wire).

    The bytes charged are the itemsize of the f32 buffer the pmean
    actually moves — the same ``size * dtype.itemsize`` basis the
    transports charge their dense leaves, so the two accountings cannot
    drift (they used to: this path hard-coded 4 bytes/element)."""
    with jax.named_scope("csgd_codec"):
        upd = jax.tree.map(
            lambda g: jax.lax.pmean(eta * g.astype(jnp.float32), dp_axes),
            grads)
        wire = jnp.float32(sum(u.size * u.dtype.itemsize
                               for u in jax.tree.leaves(upd)))
        return upd, wire
