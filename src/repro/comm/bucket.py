"""Bucketed payload transport for the compressed exchange (DESIGN.md §11).

``dcsgd.worker_compress_aggregate`` historically looped over pytree leaves
in Python, issuing one packed ``all_gather`` and one pack/unpack kernel
pair PER LEAF — dozens of latency-bound collectives and tiny launches per
step on the registry's transformer configs.  This module coalesces the
transport while leaving selection, EF, and all per-leaf numerics
untouched:

* :func:`build_bucket_plan` — a **trace-time** plan (pure Python over
  static leaf shapes): every compressible leaf gets a :class:`LeafLane`
  (its (L, d) row geometry, :class:`~repro.comm.wire.WireSpec`, and word
  offset into one flat wire buffer), and lanes sharing a field layout
  (``index_bits``; ``value_bits``/``block``/``k_b``/``ragged`` are
  compressor-wide) group into at most two :class:`Bucket`\\ s.
* :func:`encode_buckets` — per-leaf field construction (the exact
  :func:`repro.comm.wire.row_fields` math: scales, quantization, ragged
  value masking), then ONE ``wire_pack`` launch per bucket field section
  via the word-aligned stream reflow
  (:func:`repro.kernels.ops.pack_fields_stream`), then per-leaf assembly
  of the **exact** per-leaf payload rows into one flat ``(total_words,)``
  uint32 buffer.  No padding word ever crosses the wire: the buffer's
  byte length is the same per-leaf ``Compressor.wire_bytes`` sum the old
  loop shipped (enforced by ``exchange.check_bucket_payload``).
* :func:`decode_buckets` — the inverse: slice each gathered leaf segment
  by the plan's offset table, ONE ``wire_unpack`` launch per bucket
  section, then per-leaf interpretation
  (:func:`repro.comm.wire.fields_to_rows`) honoring each row's own ragged
  valid count.  The per-leaf ``(W, L, k)`` results are bit-identical to
  per-leaf :func:`~repro.comm.wire.decode_rows` on per-leaf gathers.

The step's collective schedule then is O(1): ONE ``all_gather`` of the
flat buffer (every bucket rides the same collective) plus ONE ``pmean``
of the concatenated dense small leaves — down from one per leaf.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.kernels import ops
from . import faults
from . import wire as wire_fmt
from .wire import WireSpec


def plan_geometry(shape: Sequence[int], stacked: bool) -> tuple[int, int]:
    """(L, d) per-layer row view of a leaf shape — mirrors
    ``dcsgd._leaf_2d`` exactly (stacked leaves: leading axis = layers)."""
    shape = tuple(shape)
    size = 1
    for s in shape:
        size *= s
    if stacked and len(shape) >= 2:
        return shape[0], size // shape[0]
    return 1, size


@dataclasses.dataclass(frozen=True)
class LeafLane:
    """Trace-time transport geometry of one gradient-pytree leaf."""

    index: int                 # position in the flattened pytree
    shape: tuple[int, ...]
    L: int                     # payload rows (layers; 1 when unstacked)
    d: int                     # dense row length the indices address
    stacked: bool
    dense: bool                # ships uncompressed (pmean), no payload
    spec: WireSpec | None = None
    word_off: int = 0          # first word of this leaf's payload segment

    @property
    def words(self) -> int:
        """Flat words this leaf contributes to the wire buffer."""
        return 0 if self.dense else self.L * self.spec.row_words


@dataclasses.dataclass(frozen=True)
class Bucket:
    """Leaves whose packed field sections share one launch geometry.

    ``value_bits``/``block``/``k_b``/``ragged`` are properties of the one
    Compressor governing the tree, so the only layout split left is the
    index width — at most two buckets ever exist (16- and 32-bit
    indices)."""

    index_bits: int
    leaf_ids: tuple[int, ...]  # tree-order indices of member leaves


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """Static transport plan for one gradient pytree under one Compressor."""

    leaves: tuple[LeafLane, ...]
    buckets: tuple[Bucket, ...]
    total_words: int           # flat wire-buffer length (== sum lane.words)

    @property
    def compressed_ids(self) -> tuple[int, ...]:
        return tuple(ln.index for ln in self.leaves if not ln.dense)

    @property
    def dense_ids(self) -> tuple[int, ...]:
        return tuple(ln.index for ln in self.leaves if ln.dense)

    @property
    def n_gathers(self) -> int:
        """Collectives the compressed transport issues per step: every
        bucket rides ONE flat all_gather (0 when nothing compresses)."""
        return 1 if self.total_words else 0


def build_bucket_plan(shapes: Sequence[Sequence[int]],
                      stacked: Sequence[bool], comp) -> BucketPlan:
    """Build the trace-time plan for leaves of the given ``shapes`` under
    Compressor ``comp``.  The dense/compressed split mirrors
    ``worker_compress_aggregate`` exactly; segment offsets follow tree
    order, so the flat buffer is the in-order concatenation of the same
    per-leaf payloads the per-leaf transport ships."""
    lanes: list[LeafLane] = []
    by_bits: dict[int, list[int]] = {}
    word_off = 0
    for i, (shape, st) in enumerate(zip(shapes, stacked)):
        L, d = plan_geometry(shape, st)
        if comp.ships_dense(d):
            lanes.append(LeafLane(i, tuple(shape), L, d, st, True))
            continue
        spec = WireSpec.for_row(comp, d)
        lanes.append(LeafLane(i, tuple(shape), L, d, st, False, spec,
                              word_off))
        word_off += L * spec.row_words
        by_bits.setdefault(spec.index_bits, []).append(i)
    buckets = tuple(Bucket(bits, tuple(ids))
                    for bits, ids in by_bits.items())
    return BucketPlan(tuple(lanes), buckets, word_off)


# ---------------------------------------------------------------------------
# batched field-section codec (one launch per bucket section)
# ---------------------------------------------------------------------------

def _pack_sections(group, bits: int, impl):
    """One stream-pack launch for a group of (leaf_id, (L, k) fields,
    words_per_row) sections -> {leaf_id: (L, words_per_row) words}.

    Each section is zero-padded to whole words per row first, so the
    concatenated field stream is word-aligned and the packed stream
    splits back into each leaf's exact section words."""
    streams, sizes = [], []
    F = max(1, 32 // bits)
    for _, fields, w in group:
        L, k = fields.shape
        pad = w * F - k
        if pad:
            fields = jnp.pad(fields, ((0, 0), (0, pad)))
        streams.append(fields.reshape(-1))
        sizes.append(L * w)
    words = ops.pack_fields_stream(jnp.concatenate(streams), bits,
                                   impl=impl)
    out, off = {}, 0
    for (leaf_id, fields, w), n in zip(group, sizes):
        out[leaf_id] = words[off:off + n].reshape(fields.shape[0], w)
        off += n
    return out


def _unpack_sections(group, bits: int, impl):
    """Inverse of :func:`_pack_sections`: (leaf_id, (R, w) section words,
    k) groups -> {leaf_id: (R, k) fields} via one stream-unpack launch."""
    streams = [words.reshape(-1) for _, words, _ in group]
    fields = ops.unpack_fields_stream(jnp.concatenate(streams), bits,
                                      impl=impl)
    F = max(1, 32 // bits)
    out, off = {}, 0
    for leaf_id, words, k in group:
        R, w = words.shape
        out[leaf_id] = fields[off:off + R * w * F].reshape(R, w * F)[:, :k]
        off += R * w * F
    return out


def encode_buckets(plan: BucketPlan, rows, *,
                   impl: str | None = None) -> jax.Array:
    """Encode every compressed leaf's (vals, idx, counts) into the flat
    (total_words,) uint32 wire buffer.

    ``rows``: sequence aligned with ``plan.leaves`` — ``(vals (L, k) f32,
    idx (L, k) i32, counts (L,) i32 | None)`` per compressed leaf, None
    for dense lanes.  The per-row math (ragged value masking before the
    quantization scale, scales, field construction) is
    :func:`repro.comm.wire.row_fields` — shared bit-for-bit with
    ``encode_rows``; the ragged count mask the per-leaf kernels apply
    in-launch is applied here to the field sections before the batched
    stream pack (identical fields either way).
    """
    with jax.named_scope("csgd_codec"):
        secs: dict[int, tuple] = {}
        for ln in plan.leaves:
            if ln.dense:
                continue
            vals, idx, counts = rows[ln.index]
            header, ifields, vfields, counts = wire_fmt.row_fields(
                vals, idx, ln.spec, counts=counts)
            if ln.spec.ragged:
                valid = wire_fmt.field_mask(ln.spec.k, counts,
                                            ln.spec.count_period)
                ifields = jnp.where(valid, ifields, jnp.uint32(0))
                vfields = jnp.where(valid, vfields, jnp.uint32(0))
            secs[ln.index] = (header, ifields, vfields)

        lanes = {ln.index: ln for ln in plan.leaves}
        iwords: dict[int, jax.Array] = {}
        vwords: dict[int, jax.Array] = {}
        for b in plan.buckets:
            iwords.update(_pack_sections(
                [(i, secs[i][1], lanes[i].spec.index_words)
                 for i in b.leaf_ids],
                b.index_bits, impl))
            # value_bits is compressor-wide, so the value sections of every
            # bucket share one width; keep the launch per bucket so the two
            # stream shapes stay tied to the bucket geometry
            vwords.update(_pack_sections(
                [(i, secs[i][2], lanes[i].spec.value_words)
                 for i in b.leaf_ids],
                lanes[b.leaf_ids[0]].spec.value_bits, impl))

        segments = []
        for ln in plan.leaves:
            if ln.dense:
                continue
            header = secs[ln.index][0]
            parts = ([header] if header is not None else [])
            parts += [iwords[ln.index], vwords[ln.index]]
            seg = jnp.concatenate(parts, axis=-1)
            assert seg.shape == (ln.L, ln.spec.row_words), \
                (seg.shape, ln.L, ln.spec.row_words)
            segments.append(seg.reshape(-1))
        payload = jnp.concatenate(segments)
        assert payload.shape == (plan.total_words,)
        return payload


def decode_buckets(plan: BucketPlan, gathered: jax.Array, *,
                   impl: str | None = None, with_verdicts: bool = False):
    """Decode an all-gathered (W, total_words) flat buffer back to
    per-leaf ((W, L, k) f32 values, (W, L, k) i32 flat indices) pairs —
    a list aligned with ``plan.leaves`` (None for dense lanes), each
    bit-identical to per-leaf ``decode_rows`` of a per-leaf gather.

    Ragged rows are decoded by their OWN header count (workers carry
    heterogeneous k_t); the count mask the per-leaf kernels apply
    in-launch is applied per leaf after the batched stream unpack.

    This is the wire boundary: an active fault-injection campaign
    (comm/faults.py) corrupts each lane's gathered rows here, before
    unpack.  With ``with_verdicts`` a second aligned list of per-lane
    ``(W, L)`` bool validity verdicts (DESIGN.md §16) is returned and
    invalid rows come back already quarantined (zero value at index 0);
    on a clean wire every verdict is True and the decode is bit-exact
    vs ``with_verdicts=False``.
    """
    with jax.named_scope("csgd_codec"):
        W = gathered.shape[0]
        lanes = {ln.index: ln for ln in plan.leaves}
        pay: dict[int, jax.Array] = {}
        for ln in plan.leaves:
            if ln.dense:
                continue
            seg = gathered[:, ln.word_off:ln.word_off + ln.words]
            rows = seg.reshape(W * ln.L, ln.spec.row_words)
            pay[ln.index] = faults.maybe_corrupt(rows, ln.spec, ln.index, ln.L)

        ifields: dict[int, jax.Array] = {}
        vfields: dict[int, jax.Array] = {}
        for b in plan.buckets:
            igroup, vgroup = [], []
            for i in b.leaf_ids:
                spec = lanes[i].spec
                off = spec.header_words
                igroup.append((i, pay[i][:, off:off + spec.index_words],
                               spec.k))
                vgroup.append((i, pay[i][:, off + spec.index_words:
                                         off + spec.index_words
                                         + spec.value_words], spec.k))
            ifields.update(_unpack_sections(igroup, b.index_bits, impl))
            vfields.update(_unpack_sections(
                vgroup, lanes[b.leaf_ids[0]].spec.value_bits, impl))

        out = [None] * len(plan.leaves)
        verdicts = [None] * len(plan.leaves)
        by_spec: dict = {}
        for ln in plan.leaves:
            if ln.dense:
                continue
            spec, i = ln.spec, ln.index
            counts = pay[i][:, 0].astype(jnp.int32) if spec.ragged else None
            ifld, vfld = ifields[i], vfields[i]
            if spec.ragged:
                valid = wire_fmt.field_mask(spec.k, counts, spec.count_period)
                ifld = jnp.where(valid, ifld, jnp.uint32(0))
                vfld = jnp.where(valid, vfld, jnp.uint32(0))
            off = spec.header_words
            scale_words = pay[i][:, off - 1:off] if spec.value_bits <= 8 \
                else None
            vals, idx = wire_fmt.fields_to_rows(ifld, vfld, scale_words,
                                                counts, spec)
            if with_verdicts:
                by_spec.setdefault(spec, []).append((ln, vals, idx))
            else:
                out[i] = (vals.reshape(W, ln.L, spec.k),
                          idx.reshape(W, ln.L, spec.k))
        if not with_verdicts:
            return out
        # verdict + quarantine batch per WireSpec group, not per lane: every
        # lane with the same row layout rides ONE fused launch (same
        # coalescing argument as the bucket gather itself), keeping the §16
        # guards inside the 1.05x guarded-vs-unguarded bench gate.  Row order
        # is tree order within the concatenation, so slicing back per lane is
        # bit-exact vs the per-lane calls.
        for spec, members in by_spec.items():
            if len(members) > 1:
                cat_pay = jnp.concatenate(
                    [pay[ln.index] for ln, _, _ in members])
                cat_vals = jnp.concatenate([v for _, v, _ in members])
                cat_idx = jnp.concatenate([x for _, _, x in members])
            else:
                ln0 = members[0][0]
                cat_pay, cat_vals, cat_idx = (pay[ln0.index], members[0][1],
                                              members[0][2])
            v = wire_fmt.row_verdict(cat_pay, spec, cat_vals, cat_idx)
            cat_vals, cat_idx = wire_fmt.quarantine_rows(cat_vals, cat_idx, v)
            off = 0
            for ln, _, _ in members:
                rows = W * ln.L
                verdicts[ln.index] = v[off:off + rows].reshape(W, ln.L)
                out[ln.index] = (
                    cat_vals[off:off + rows].reshape(W, ln.L, spec.k),
                    cat_idx[off:off + rows].reshape(W, ln.L, spec.k))
                off += rows
        return out, verdicts
