"""Packed payload exchange over the data-parallel mesh axes.

The only collective the compressed path issues is an ``all_gather`` of
packed payload words built by :mod:`repro.comm.wire` — per leaf on the
reference transport (W * L * ``WireSpec.row_bytes`` bytes per leaf), ONE
flat buffer for the whole pytree on the default bucketed transport
(:mod:`repro.comm.bucket`, DESIGN.md §11).  The byte-accounting contract
(``Compressor.wire_bytes`` == payload bytes, with no padding word ever
riding the collective) is enforced at trace time by :func:`check_payload`
/ :func:`check_bucket_payload`.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from .wire import WireSpec

AxisNames = Sequence[str] | str


def check_payload(payload: jax.Array, spec: WireSpec, comp, d: int) -> None:
    """Trace-time guarantee that the buffer about to cross the mesh axis is
    exactly the bytes ``Compressor.wire_bytes`` accounts for.  Shapes and
    dtypes are static, so a violation fails at trace/compile time, not at
    runtime on some worker.  Raises (not assert): the contract must hold
    under ``python -O`` too."""
    if payload.dtype != jnp.uint32:
        raise ValueError(f"payload must be uint32, got {payload.dtype}")
    if payload.shape[-1] != spec.row_words:
        raise ValueError(f"payload row is {payload.shape[-1]} words, "
                         f"spec says {spec.row_words}")
    accounted = comp.wire_bytes(d)
    physical = spec.row_bytes
    if physical != accounted:
        raise ValueError(
            f"wire accounting drift: payload row is {physical} B but "
            f"Compressor.wire_bytes({d}) = {accounted} B")


def check_bucket_payload(payload: jax.Array, plan, comp) -> None:
    """Bucket-geometry counterpart of :func:`check_payload` (DESIGN.md
    §11): the ONE flat uint32 buffer about to cross the mesh axis is
    exactly the bytes the per-leaf accounting sums to — the bucketed
    transport ships the same per-leaf payload rows back to back, never a
    padding word.  ``plan`` is a :class:`repro.comm.bucket.BucketPlan`.
    All quantities are static, so violations fail at trace time."""
    if payload.dtype != jnp.uint32:
        raise ValueError(f"payload must be uint32, got {payload.dtype}")
    if payload.shape != (plan.total_words,):
        raise ValueError(f"bucket payload is {payload.shape}, plan says "
                         f"({plan.total_words},)")
    words = 0
    for lane in plan.leaves:
        if lane.dense:
            continue
        accounted = comp.wire_bytes(lane.d)
        if lane.spec.row_bytes != accounted:
            raise ValueError(
                f"wire accounting drift: leaf {lane.index} payload row is "
                f"{lane.spec.row_bytes} B but Compressor.wire_bytes"
                f"({lane.d}) = {accounted} B")
        if lane.word_off != words:
            raise ValueError(
                f"bucket offset drift: leaf {lane.index} at word "
                f"{lane.word_off}, expected {words}")
        words += lane.words
    if words != plan.total_words:
        raise ValueError(f"bucket plan sums to {words} words, "
                         f"total_words says {plan.total_words}")


def effective_payload_bytes(payload: jax.Array, spec: WireSpec) -> jax.Array:
    """Traced count of *useful* bytes in a gathered/encoded (… , row_words)
    payload: for ragged specs, each row's valid-count header word prices
    the row at what a truly ragged collective would ship
    (``WireSpec.effective_row_bytes``); non-ragged payloads are fully
    useful.  This is the runtime counterpart of the static
    ``check_payload`` contract — the budget stays the trace-time bound,
    this is the per-step metric under it."""
    rows = payload.reshape(-1, payload.shape[-1])
    if not spec.ragged:
        return jnp.float32(rows.shape[0] * spec.row_bytes)
    # the gathered header word is worker-controlled garbage until proven
    # otherwise — decode_rows tolerates any bit pattern (the count mask
    # clamps), so the byte metric must too: an unclamped hostile count
    # would inflate effective_wire_bytes beyond the static budget
    counts = jnp.clip(rows[:, 0].astype(jnp.int32), 0, spec.full_count)
    return jnp.sum(spec.effective_row_bytes(counts))


def gather_packed(payload: jax.Array, dp_axes: AxisNames, *,
                  ring_chunks: int | None = None) -> jax.Array:
    """All-gather one worker's (L, row_words) payload over the dp axes ->
    (W, L, row_words) with the worker axis flattened across multi-axis
    meshes (('pod','data') gathers as (pod, data, ...)).

    ``ring_chunks``: when set, the gather is carried by the chunked
    ppermute ring schedule of :func:`repro.comm.ring.ring_all_gather`
    (DESIGN.md §14) instead of one flat ``lax.all_gather`` — bit-identical
    result, same total bytes per link, but split into ``n_chunks * (W-1)``
    small dependency-free collectives an overlap-capable runtime can hide
    behind compute."""
    with jax.named_scope("csgd_codec"):
        if ring_chunks is not None:
            from repro.comm.ring import ring_all_gather
            flat = ring_all_gather(payload.reshape(-1), dp_axes, ring_chunks)
            return flat.reshape(-1, *payload.shape)
        gathered = jax.lax.all_gather(payload, dp_axes)
        if isinstance(dp_axes, (tuple, list)) and len(dp_axes) > 1:
            gathered = gathered.reshape(-1, *payload.shape)
        return gathered
