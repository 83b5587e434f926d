"""Chunked ring all-gather over the packed bucket buffer (DESIGN.md §14).

``gather_packed`` (comm/exchange.py) moves the whole §11 bucket buffer in
ONE ``lax.all_gather``.  That is optimal for collective *count* but the
gather sits serially between backward and the update: nothing downstream
can start until every byte has landed.  This module re-expresses the same
gather as a **ring schedule** — ``W-1`` send-right ``ppermute`` steps per
chunk over ``n_chunks`` word-aligned sections of the buffer — which

* moves the SAME total bytes per link as the flat gather
  ((W-1)/W of the gathered buffer), and
* breaks the transfer into many small dependency-free collectives, so an
  overlap-capable runtime can interleave them with compute (and with the
  decode of already-arrived chunks).

Bit-exactness vs ``lax.all_gather`` is pinned by parity tests: ppermute
only relabels device placement, so the assembled ``(W, total_words)``
buffer is an exact copy of every worker's payload in axis-index order.

Multi-axis dp meshes gather as a **ring of rings**: the innermost axis
first (matching the row-major stacking of ``lax.all_gather`` over an
axis tuple), then each outer axis over the enlarged block, so the final
``reshape(-1, total_words)`` reproduces ``gather_packed``'s row order.

The pure-Python scheduling pieces (``chunk_table``, ``step_source``) are
shared with ``ring_gather_reference``, a NumPy simulator used by the
single-device hypothesis property in tests/test_property.py — the SPMD
path and the reference cannot drift apart on chunk/source arithmetic.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np


__all__ = [
    "chunk_table",
    "step_source",
    "n_permutes",
    "ring_all_gather",
    "ring_gather_reference",
]


def chunk_table(total_words: int, n_chunks: int) -> tuple[tuple[int, int], ...]:
    """Word-aligned ``(offset, length)`` sections covering ``[0, total_words)``.

    ``n_chunks`` is clamped to ``[1, total_words]`` (a chunk must hold at
    least one word); the first ``total_words % n`` chunks get one extra
    word, so non-divisible splits stay contiguous and exhaustive.
    """
    if total_words < 0:
        raise ValueError(f"total_words must be >= 0, got {total_words}")
    if n_chunks < 1:
        raise ValueError(f"n_chunks must be >= 1, got {n_chunks}")
    if total_words == 0:
        return ()
    n = min(n_chunks, total_words)
    base, rem = divmod(total_words, n)
    table = []
    off = 0
    for c in range(n):
        ln = base + (1 if c < rem else 0)
        table.append((off, ln))
        off += ln
    return tuple(table)


def step_source(i, s: int, size: int):
    """Origin worker of the chunk held by worker ``i`` after ring step ``s``.

    Send-right ring (``j -> (j+1) % size``): after ``s`` hops, worker
    ``i`` holds the chunk that started at ``(i - s) % size``.  ``i`` may
    be a traced ``axis_index``; ``s``/``size`` are static Python ints.
    """
    return (i - s) % size


def n_permutes(axis_sizes: Sequence[int], total_words: int,
               n_chunks: int) -> int:
    """Exact number of ``collective_permute`` ops ``ring_all_gather`` emits.

    Innermost axis first; each axis of size ``A > 1`` contributes
    ``chunks_eff * (A - 1)`` permutes where ``chunks_eff`` is ``n_chunks``
    clamped to the block length at that stage (the block grows by the
    inner axes' sizes as the ring-of-rings proceeds outward).
    """
    total = 0
    words = total_words
    for size in reversed(tuple(axis_sizes)):
        if words > 0 and size > 1:
            total += len(chunk_table(words, n_chunks)) * (size - 1)
        words *= size
    return total


def _ring_axis_gather(vec: jax.Array, axis: str, n_chunks: int) -> jax.Array:
    """All-gather flat ``vec`` along one mesh axis via a chunked ring.

    Returns ``(A, len(vec))`` with row ``a`` holding axis-index ``a``'s
    vector — identical to ``lax.all_gather(vec, axis)``.
    """
    size = jax.lax.axis_size(axis)
    if size == 1:
        return vec[None]
    i = jax.lax.axis_index(axis)
    out = jnp.zeros((size,) + vec.shape, vec.dtype)
    # own block lands at the (traced) own row; every remote block arrives
    # over the ring below.
    out = jax.lax.dynamic_update_slice(out, vec[None], (i, jnp.int32(0)))
    perm = [(j, (j + 1) % size) for j in range(size)]
    for off, ln in chunk_table(vec.shape[0], n_chunks):
        buf = vec[off:off + ln]
        for s in range(1, size):
            buf = jax.lax.ppermute(buf, axis, perm)
            src = step_source(i, s, size)
            out = jax.lax.dynamic_update_slice(
                out, buf[None], (src, jnp.int32(off)))
    return out


def ring_all_gather(payload: jax.Array, dp_axes, n_chunks: int = 1
                    ) -> jax.Array:
    """Drop-in for ``gather_packed``: ``(total_words,)`` -> ``(W, total_words)``.

    Streams the buffer in ``n_chunks`` sections over ``W-1`` ppermute
    ring steps per axis instead of one flat all_gather; the result is
    bit-identical (row ``w`` = worker ``w``'s payload, rows ordered by
    ``lax.axis_index(dp_axes)`` exactly like the all_gather stacking).
    """
    axes = (dp_axes,) if isinstance(dp_axes, str) else tuple(dp_axes)
    words = payload.shape[0]
    block = payload
    # ring of rings: innermost axis first so the final row order matches
    # the row-major (outer, ..., inner) stacking of the flat all_gather.
    for axis in reversed(axes):
        block = _ring_axis_gather(block.reshape(-1), axis, n_chunks)
    return block.reshape(-1, words)


def ring_gather_reference(bufs: np.ndarray, n_chunks: int) -> np.ndarray:
    """NumPy simulator of the single-axis ring schedule (no collectives).

    ``bufs``: ``(W, total_words)`` — worker ``w``'s payload in row ``w``.
    Simulates the exact send-right schedule (same ``chunk_table`` /
    ``step_source`` arithmetic as the SPMD path) and returns the
    per-worker assembled buffers, shape ``(W, W, total_words)``.  Raises
    if any (worker, row, word) slot is written twice or left unwritten —
    the property test's guarantee that the schedule covers the buffer
    exactly once.
    """
    bufs = np.asarray(bufs)
    W, total_words = bufs.shape
    out = np.zeros((W, W, total_words), dtype=bufs.dtype)
    written = np.zeros((W, W, total_words), dtype=np.int32)
    for w in range(W):  # own block, written up front like the SPMD path
        out[w, w] = bufs[w]
        written[w, w] += 1
    for off, ln in chunk_table(total_words, n_chunks):
        hold = bufs[:, off:off + ln].copy()  # hold[w] = chunk at worker w
        for s in range(1, W):
            # send right: worker w's new buffer came from worker w-1
            hold = np.roll(hold, 1, axis=0)
            for w in range(W):
                src = step_source(w, s, W)
                out[w, src, off:off + ln] = hold[w]
                written[w, src, off:off + ln] += 1
    if total_words and W > 1 and not (written == 1).all():
        bad = int((written != 1).sum())
        raise AssertionError(
            f"ring schedule wrote {bad} slots != exactly once "
            f"(W={W}, n_chunks={n_chunks}, total_words={total_words})")
    return out
