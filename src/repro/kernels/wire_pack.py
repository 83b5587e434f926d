"""Pallas kernels for the bit-packed compressed-gradient wire format.

The packed payload (DESIGN.md §8) stores each wire entry's index and
quantized value as fixed-width bit-fields inside contiguous ``uint32``
words.  The field<->word conversion is the only data-parallel part of the
codec and the part worth a kernel: on TPU it is a pure VPU shift/or (pack)
or shift/mask (unpack) pass over lane-dense (rows, chunk) tiles.

Layout contract (shared with the ``kernels/ref.py`` oracles bit-for-bit):
``F = 32 // bits`` fields per word, field ``f`` occupying bits
``[f*bits, (f+1)*bits)`` — little-endian fields within each word.

The TPU compiler has no lane-strided load or store, and splitting the lane
dimension into (words, F) inside a kernel is either refused (pack) or
unrolled into a compile that runs for minutes (unpack).  So the kernels
work on *field planes* — ``planes[s, r, w] = fields[r, w*F + s]`` — and
the interleave between fields and planes is one XLA transpose outside the
kernel: pack ORs the F shifted planes of a tile into its words, unpack
writes the F masked shifts of its words to the F planes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Row/word tile geometry: the word side of a tile is (256, 512) uint32 =
# 512 KiB; the field side is at most 8x wider (bits=4) = 4 MiB — both
# VMEM-resident with double-buffering headroom, and payload row counts
# (model layers) rarely exceed a few tiles.
ROWS = 256
WORD_CHUNK = 512


def stream_shape(n_words: int) -> tuple[int, int]:
    """(rows, cols) reflow geometry for a FLAT word stream of ``n_words``
    uint32 words — the bucket-shaped launch (DESIGN.md §11).

    Packing is word-local (word w holds fields [w*F, (w+1)*F) whatever the
    row structure), so a whole bucket's concatenated field stream can be
    reshaped row-major into (rows, cols) word tiles, packed/unpacked in
    ONE kernel launch, and flattened back — each leaf's exact word segment
    slices out unchanged.  Cols saturate at :data:`WORD_CHUNK` so big
    buckets fill full (ROWS, WORD_CHUNK) VPU tiles.
    """
    cols = min(WORD_CHUNK, max(n_words, 1))
    return -(-max(n_words, 1) // cols), cols


def _field_mask(c_ref, s: int, rows: int, wc: int, F: int, period: int):
    """(rows, wc) validity mask of field plane ``s`` in the current grid
    tile: the field of word column ``w`` (global) and plane ``s`` has row
    index ``j = w*F + s``, valid iff ``j % period < count[row]`` — the
    ragged-payload predicate.  The modulo makes it a per-block prefix for
    block-local wire rows and a plain prefix for flat rows, with zero extra
    HBM traffic: counts ride in as one (rows, 1) int32 column per tile."""
    w = pl.program_id(1) * wc + jax.lax.broadcasted_iota(
        jnp.int32, (rows, wc), 1)
    return ((w * F + s) % period) < c_ref[...]


def _pack_kernel(f_ref, *refs, bits: int, period: int):
    """(F, rows, wc) field planes -> (rows, wc) words: an OR-fold of the
    F shifted planes (disjoint bit ranges).  With ``period`` > 0 the first
    of ``refs`` is the (rows, 1) count column and fields beyond the
    per-row valid count are zeroed on the same pass."""
    out_ref = refs[-1]
    F = 32 // bits
    _, rows, wc = f_ref.shape
    acc = None
    for s in range(F):
        f = f_ref[s] & jnp.uint32((1 << bits) - 1)
        if period:
            f = jnp.where(_field_mask(refs[0], s, rows, wc, F, period), f,
                          jnp.uint32(0))
        f = f << jnp.uint32(s * bits)
        acc = f if acc is None else acc | f
    out_ref[...] = acc


def _unpack_kernel(w_ref, *refs, bits: int, period: int):
    """(rows, wc) words -> (F, rows, wc) field planes; with ``period`` > 0
    fields beyond the valid count come out 0 regardless of the packed
    tail's bytes."""
    out_ref = refs[-1]
    F = 32 // bits
    w = w_ref[...]
    rows, wc = w.shape
    for s in range(F):
        f = (w >> jnp.uint32(s * bits)) & jnp.uint32((1 << bits) - 1)
        if period:
            f = jnp.where(_field_mask(refs[0], s, rows, wc, F, period), f,
                          jnp.uint32(0))
        out_ref[s] = f


def _geometry(R: int, W: int):
    rows = min(ROWS, R)
    wc = min(WORD_CHUNK, W)
    return rows, wc, (pl.cdiv(R, rows), pl.cdiv(W, wc))


def _counts_operand(counts, rows: int):
    """Extra (operand, BlockSpec) for a ragged launch, else nothing."""
    if counts is None:
        return [], []
    c = jnp.asarray(counts, jnp.int32).reshape(-1, 1)
    return [c], [pl.BlockSpec((rows, 1), lambda i, j: (i, 0))]


@functools.partial(jax.jit, static_argnames=("bits", "period", "interpret"))
def pack_words(fields: jax.Array, bits: int,
               counts: jax.Array | None = None, period: int = 0, *,
               interpret: bool = True):
    """Pack (R, n) uint32 bit-fields into (R, n*bits/32) uint32 words.

    n must be a multiple of 32//bits (``ops.pack_fields`` zero-pads).
    ``counts``/``period``: ragged payloads — fields with
    ``j % period >= counts[row]`` are zeroed inside the kernel before
    packing (valid-count semantics, DESIGN.md §9).
    """
    if bits >= 32:
        out = fields.astype(jnp.uint32)
        if counts is not None:
            from . import ref
            out = jnp.where(ref._count_mask(*out.shape, counts, period),
                            out, 0)
        return out
    F = 32 // bits
    R, n = fields.shape
    W = n // F
    rows, wc, grid = _geometry(R, W)
    # field planes: planes[s, r, w] = fields[r, w*F + s]
    planes = fields.astype(jnp.uint32).reshape(R, W, F).transpose(2, 0, 1)
    extra, extra_specs = _counts_operand(counts, rows)
    return pl.pallas_call(
        functools.partial(_pack_kernel, bits=bits,
                          period=period if counts is not None else 0),
        grid=grid,
        in_specs=[pl.BlockSpec((F, rows, wc), lambda i, j: (0, i, j))]
        + extra_specs,
        out_specs=pl.BlockSpec((rows, wc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, W), jnp.uint32),
        interpret=interpret,
    )(planes, *extra)


@functools.partial(jax.jit, static_argnames=("bits", "period", "interpret"))
def unpack_words(words: jax.Array, bits: int,
                 counts: jax.Array | None = None, period: int = 0, *,
                 interpret: bool = True):
    """Inverse of :func:`pack_words`: (R, W) words -> (R, W*32/bits)
    fields, masked beyond the per-row valid count when ``counts`` is
    given."""
    if bits >= 32:
        out = words.astype(jnp.uint32)
        if counts is not None:
            from . import ref
            out = jnp.where(ref._count_mask(*out.shape, counts, period),
                            out, 0)
        return out
    F = 32 // bits
    R, W = words.shape
    rows, wc, grid = _geometry(R, W)
    extra, extra_specs = _counts_operand(counts, rows)
    planes = pl.pallas_call(
        functools.partial(_unpack_kernel, bits=bits,
                          period=period if counts is not None else 0),
        grid=grid,
        in_specs=[pl.BlockSpec((rows, wc), lambda i, j: (i, j))]
        + extra_specs,
        out_specs=pl.BlockSpec((F, rows, wc), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((F, R, W), jnp.uint32),
        interpret=interpret,
    )(words.astype(jnp.uint32), *extra)
    return planes.transpose(1, 2, 0).reshape(R, W * F)
