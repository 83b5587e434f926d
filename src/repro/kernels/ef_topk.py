"""Pallas TPU kernels for fused error-feedback sparsification.

The compression hot spot of CSGD-ASSS is, per step and per layer shard:

    acc  = m + eta*g          (read m, g : 2 streams)
    tau  = k-th |.| statistic (selection)
    sent = acc * (|acc|>=tau) (write)
    m'   = acc - sent         (write)

A naive jnp composition reads ``acc`` three times from HBM and materializes
intermediates; the fused kernel streams each element exactly once:
2 reads + 2 writes, perfectly memory-bound at 4 bytes/elem/stream.

The kernels implement the two-pass block-local scheme (DESIGN.md §3):

* pass 1 ``_ef_block_stats_kernel`` / ``_ef_stats_telemetry_kernel`` —
  ranks each block of ``acc = m + eta*g`` (formed on the fly: 2 reads)
  and writes its k_b-th largest |acc| AND the block's k_b wire pairs, the
  block-local lanes and signed values of its top-k_b entries in
  ``lax.top_k`` order, so no later sort or gather is needed;
* pass 2 ``_ef_apply_kernel``     — the fused elementwise update above,
  thresholding each 1024-wide block against ITS OWN tau from pass 1;
* ``_block_stats_kernel``         — the single-input k_b-th |x| statistic
  and ``_threshold_split_kernel`` (x -> sent, residual) for the dense
  ``Compressor.compress_dense`` path.

Blocks are (8, 128)-lane aligned for the VPU; tensors are processed as
(rows, 1024) tiles resident in VMEM.  Pass 1 writes its pairs transposed,
(k_b, rows) with the block rows on lanes, so the pair outputs are dense
in HBM instead of padding k_b lanes out to 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Tile geometry: 8 sublanes x 128 lanes = the float32 VREG footprint.  A
# (256, 1024) f32 tile is 1 MiB per stream; the pass-2 kernel touches 4
# streams (m, g, sent, m') = 4 MiB of VMEM, a quarter of a core's ~16 MiB —
# leaving headroom for double buffering of the HBM->VMEM pipeline.
ROWS = 256
COLS = 1024


# Interpret-mode row cap: the `_rank_blocks` fori_loop re-touches its
# whole tile every iteration, so off-TPU the tile should sit in L2 —
# (64, 512) f32 = 128 KiB was the measured optimum on the bucketed
# transport's concatenated row counts (~30% faster than 256-row tiles).
# Compiled TPU launches keep ROWS (a VMEM budget, not a cache guess).
INTERPRET_ROWS = 64


def _tile_rows(R: int, interpret: bool) -> int:
    """Row-tile height for an R-row launch: split the grid EVENLY instead
    of ``min(cap, R)`` so the last tile carries < n_tiles padding rows.
    A naive cap wastes up to cap-1 padded rows — on the bucketed
    transport's concatenated block rows (DESIGN.md §11) that was measured
    as ~60% dead work for row counts just past a tile boundary.  Every op
    here is row-local, so the tiling is numerically invisible.

    A tile shorter than R is rounded up to a multiple of 8 sublanes — the
    TPU compiler refuses any other block height below the full dimension
    (R=300 would otherwise split into two 150-row tiles)."""
    cap = INTERPRET_ROWS if interpret else ROWS
    n_tiles = -(-R // cap)
    rows = -(-R // n_tiles)
    return rows if rows == R else -(-rows // 8) * 8


def _lane_tile_rows(R: int, interpret: bool) -> int:
    """Row-tile height for pass 1, whose pair outputs carry the block rows
    on lanes: on TPU a tile shorter than R must span whole 128-lane
    vregs, so :func:`_tile_rows`' height is rounded up to a multiple of
    128 (the last tile runs past R; every op is row-local)."""
    rows = _tile_rows(R, interpret)
    return rows if interpret or rows == R else -(-rows // 128) * 128


def _rank_blocks(x: jax.Array, k_b: int, *, pairs: bool):
    """Rank each row of ``x`` (rows, C) by |x| via iterative
    max-extraction — k_b is small (= gamma*block <= ~32), so this maps to
    VPU max-reductions rather than a full sort; the MXU stays free.

    Exactly ONE element is knocked out per iteration (ties broken by
    lowest lane index), so duplicated magnitudes count like lax.top_k's
    and the result matches the ref.py oracle bit-for-bit.

    Returns the k_b-th largest |x| per row, (rows, 1).  With ``pairs``
    also the k_b entries knocked out, in knock-out order — exactly
    ``lax.top_k(|x|, k_b)``'s indices and order — as block-local lanes
    (k_b, rows) int32 and signed values (k_b, rows) f32.  The search key
    is ``2*lane + signbit(x)``: keys are distinct per lane, so the min
    over tied keys is the lowest tied lane and carries its sign, and a
    value is its magnitude with that sign (-0.0 included) at no extra
    reduction.
    """
    rows, C = x.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, C), 1)
    if pairs:
        sign = jax.lax.shift_right_logical(
            jax.lax.bitcast_convert_type(x, jnp.int32), 31)
        key, miss = 2 * lane + sign, 2 * C
        width = -(-k_b // 128) * 128                # whole vregs of slots
        slot = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    else:
        key, miss = lane, C

    def body(i, carry):
        mag_c, cur = carry[:2]
        cur = jnp.max(mag_c, axis=-1, keepdims=True)      # (rows, 1)
        hit = jnp.min(jnp.where(mag_c >= cur, key, miss),
                      axis=-1, keepdims=True)             # first argmax
        mag_c = jnp.where(key == hit, -jnp.inf, mag_c)
        if not pairs:
            return mag_c, cur
        keys, mags = carry[2:]
        return (mag_c, cur, jnp.where(slot == i, hit, keys),
                jnp.where(slot == i, cur, mags))

    init = (jnp.abs(x), jnp.zeros((rows, 1), jnp.float32))
    if pairs:
        init += (jnp.zeros((rows, width), jnp.int32),
                 jnp.zeros((rows, width), jnp.float32))
    out = jax.lax.fori_loop(0, k_b, body, init)
    if not pairs:
        return out[1]
    _, kth, keys, mags = out
    vals = jnp.where((keys & 1) == 1, -mags, mags)
    return kth, (keys >> 1).T[:k_b], vals.T[:k_b]


# ---------------------------------------------------------------------------
# pass 2: fused EF accumulate + block-threshold sparsify
# ---------------------------------------------------------------------------

def _ef_apply_kernel(m_ref, g_ref, eta_ref, tau_ref, sent_ref, mnew_ref):
    """Fused: acc = m + eta*g; sent = acc*(|acc|>=tau_row); m' = acc - sent.

    tau_ref: (rows, 1) — one threshold per 1024-wide block row, broadcast
    across the lanes of its row.
    """
    eta = eta_ref[0]
    tau = tau_ref[...]                                   # (rows, 1)
    acc = m_ref[...].astype(jnp.float32) + eta * g_ref[...].astype(jnp.float32)
    keep = jnp.abs(acc) >= tau
    sent = jnp.where(keep, acc, 0.0)
    sent_ref[...] = sent.astype(sent_ref.dtype)
    mnew_ref[...] = (acc - sent).astype(mnew_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ef_apply(m: jax.Array, g: jax.Array, eta: jax.Array, tau: jax.Array,
             *, interpret: bool = True):
    """Apply the fused EF update to a 2D (R, C) block-padded tensor pair.

    m, g: (R, C) with C % 128 == 0. eta: scalar (shape (1,)); tau: (R, 1)
    per-block-row thresholds.  Returns (sent, m_new) with m.dtype.
    """
    R, C = m.shape
    rows = _tile_rows(R, interpret)
    grid = (pl.cdiv(R, rows), pl.cdiv(C, COLS))
    spec = pl.BlockSpec((rows, min(COLS, C)), lambda i, j: (i, j))
    scal = pl.BlockSpec((1,), lambda i, j: (0,))  # eta broadcast to all tiles
    tspec = pl.BlockSpec((rows, 1), lambda i, j: (i, 0))
    out_shape = (jax.ShapeDtypeStruct(m.shape, m.dtype),
                 jax.ShapeDtypeStruct(m.shape, m.dtype))
    return pl.pallas_call(
        _ef_apply_kernel,
        grid=grid,
        in_specs=[spec, spec, scal, tspec],
        out_specs=(spec, spec),
        out_shape=out_shape,
        interpret=interpret,
    )(m, g, eta.reshape(1), tau.reshape(R, 1).astype(jnp.float32))


# ---------------------------------------------------------------------------
# pass 1: per-block selection statistics
# ---------------------------------------------------------------------------

def _block_stats_kernel(x_ref, out_ref, *, k_b: int):
    """Per (C-wide) block: k_b-th largest |x| within each row-block.

    x_ref: (rows, C) tile; out_ref: (rows, 1) thresholds per row-block.
    """
    out_ref[...] = _rank_blocks(x_ref[...].astype(jnp.float32), k_b,
                                pairs=False)


@functools.partial(jax.jit, static_argnames=("k_b", "interpret"))
def block_stats(x: jax.Array, k_b: int, *, interpret: bool = True):
    """Per-block k_b-th largest |x|. x: (nb, C) -> (nb, 1) f32."""
    nb, C = x.shape
    rows = _tile_rows(nb, interpret)
    grid = (pl.cdiv(nb, rows),)
    return pl.pallas_call(
        functools.partial(_block_stats_kernel, k_b=k_b),
        grid=grid,
        in_specs=[pl.BlockSpec((rows, C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        interpret=interpret,
    )(x)


def _ef_block_stats_kernel(m_ref, g_ref, eta_ref, tau_ref, val_ref, idx_ref,
                           *, k_b: int):
    """Fused pass 1: per-block k_b-th largest |m + eta*g| and the block's
    k_b wire pairs — the accumulator is formed on the fly so it is never
    written back to HBM.

    tau_ref: (rows, 1); val_ref, idx_ref: (k_b, rows) signed values and
    block-local lanes of each block row's top-k_b, in lax.top_k order.
    """
    eta = eta_ref[0]
    acc = m_ref[...].astype(jnp.float32) + eta * g_ref[...].astype(jnp.float32)
    tau_ref[...], idx_ref[...], val_ref[...] = _rank_blocks(acc, k_b,
                                                            pairs=True)


def _ef_stats_telemetry_kernel(m_ref, g_ref, eta_ref, tau_ref, val_ref,
                               idx_ref, mom_ref, *, k_b: int):
    """Fused pass 1 + telemetry moments (DESIGN.md §10): the same streaming
    pass that ranks |acc| also reduces the two dense telemetry moments —
    ``sum g^2`` and ``sum acc^2`` per block row — while the operands sit in
    VMEM, so the compression-telemetry signal costs no extra HBM sweep.

    mom_ref: (rows, 2) per block row: [sum g^2, sum acc^2]; the other
    outputs as in :func:`_ef_block_stats_kernel`.
    """
    eta = eta_ref[0]
    gf = g_ref[...].astype(jnp.float32)
    acc = m_ref[...].astype(jnp.float32) + eta * gf
    tau_ref[...], idx_ref[...], val_ref[...] = _rank_blocks(acc, k_b,
                                                            pairs=True)
    mom_ref[...] = jnp.concatenate(
        [jnp.sum(gf * gf, axis=-1, keepdims=True),
         jnp.sum(acc * acc, axis=-1, keepdims=True)], axis=-1)


def _pass1(kernel, m, g, eta, k_b, n_moments, interpret):
    nb, C = m.shape
    rows = _lane_tile_rows(nb, interpret)
    row_spec = lambda w: pl.BlockSpec((rows, w), lambda i: (i, 0))
    pair_spec = pl.BlockSpec((k_b, rows), lambda i: (0, i))
    out_specs = [row_spec(1), pair_spec, pair_spec]
    out_shape = [jax.ShapeDtypeStruct((nb, 1), jnp.float32),
                 jax.ShapeDtypeStruct((k_b, nb), jnp.float32),
                 jax.ShapeDtypeStruct((k_b, nb), jnp.int32)]
    if n_moments:
        out_specs.append(row_spec(n_moments))
        out_shape.append(jax.ShapeDtypeStruct((nb, n_moments), jnp.float32))
    return pl.pallas_call(
        functools.partial(kernel, k_b=k_b),
        grid=(pl.cdiv(nb, rows),),
        in_specs=[row_spec(C), row_spec(C),
                  pl.BlockSpec((1,), lambda i: (0,))],
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        interpret=interpret,
    )(m, g, eta.reshape(1))


@functools.partial(jax.jit, static_argnames=("k_b", "interpret"))
def ef_block_stats(m: jax.Array, g: jax.Array, eta: jax.Array, k_b: int,
                   *, interpret: bool = True):
    """Fused pass 1 over (nb, C) block rows of m, g.

    Returns (tau: (nb, 1) f32, vals: (k_b, nb) f32, lanes: (k_b, nb)
    int32): per block row the k_b-th largest |m + eta*g| and its top-k_b
    entries' signed values and block-local lanes, in lax.top_k order.
    """
    return _pass1(_ef_block_stats_kernel, m, g, eta, k_b, 0, interpret)


@functools.partial(jax.jit, static_argnames=("k_b", "interpret"))
def ef_stats_telemetry(m: jax.Array, g: jax.Array, eta: jax.Array, k_b: int,
                       *, interpret: bool = True):
    """Fused pass 1 with telemetry moments.  m, g: (nb, C).

    Returns :func:`ef_block_stats`' (tau, vals, lanes) and moments:
    (nb, 2) f32 = [sum g^2, sum acc^2] per block row.
    """
    return _pass1(_ef_stats_telemetry_kernel, m, g, eta, k_b, 2, interpret)


# ---------------------------------------------------------------------------
# dense split (compress_dense path): x -> (sent, residual)
# ---------------------------------------------------------------------------

def _threshold_split_kernel(x_ref, tau_ref, sent_ref, res_ref):
    x = x_ref[...].astype(jnp.float32)
    tau = tau_ref[...]                                   # (rows, 1)
    sent = jnp.where(jnp.abs(x) >= tau, x, 0.0)
    sent_ref[...] = sent.astype(sent_ref.dtype)
    res_ref[...] = (x - sent).astype(res_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def threshold_split(x: jax.Array, tau: jax.Array, *, interpret: bool = True):
    """Split (R, C) blocks into kept values and residual: 1 read, 2 writes.

    tau: (R, 1) per-block-row thresholds. Returns (sent, residual), x.dtype.
    """
    R, C = x.shape
    rows = _tile_rows(R, interpret)
    grid = (pl.cdiv(R, rows), pl.cdiv(C, COLS))
    spec = pl.BlockSpec((rows, min(COLS, C)), lambda i, j: (i, j))
    tspec = pl.BlockSpec((rows, 1), lambda i, j: (i, 0))
    out_shape = (jax.ShapeDtypeStruct(x.shape, x.dtype),
                 jax.ShapeDtypeStruct(x.shape, x.dtype))
    return pl.pallas_call(
        _threshold_split_kernel,
        grid=grid,
        in_specs=[spec, tspec],
        out_specs=(spec, spec),
        out_shape=out_shape,
        interpret=interpret,
    )(x, tau.reshape(R, 1).astype(jnp.float32))
