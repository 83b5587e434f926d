"""Pallas TPU kernels for fused error-feedback sparsification.

The compression hot spot of CSGD-ASSS is, per step and per layer shard:

    acc  = m + eta*g          (read m, g : 2 streams)
    tau  = k-th |.| statistic (selection)
    sent = acc * (|acc|>=tau) (write)
    m'   = acc - sent         (write)

A naive jnp composition reads ``acc`` three times from HBM and materializes
intermediates; the fused kernel streams each element exactly once:
2 reads + 2 writes, perfectly memory-bound at 4 bytes/elem/stream.

Three kernels implement the two-pass block-local scheme (DESIGN.md §3):

* pass 1 ``_block_stats_kernel``  — per-block k_b-th largest |acc|,
  computing ``acc = m + eta*g`` on the fly (2 reads, tiny write);
* pass 2 ``_ef_apply_kernel``     — the fused elementwise update above,
  thresholding each 1024-wide block against ITS OWN tau from pass 1;
* ``_threshold_split_kernel``     — single-input variant (x -> sent,
  residual) for the dense ``Compressor.compress_dense`` path.

Blocks are (8, 128)-lane aligned for the VPU; tensors are processed as
(rows, 1024) tiles resident in VMEM.
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


# Interpret-mode row cap: the `_kth_largest` fori_loop re-touches its
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


def _kth_largest(mag: jax.Array, k_b: int) -> jax.Array:
    """k_b-th largest value per row of ``mag`` (rows, C) via iterative
    max-extraction — k_b is small (= gamma*block <= ~32), so this maps to
    VPU max-reductions rather than a full sort; the MXU stays free.

    Exactly ONE element is knocked out per iteration (ties broken by
    lowest lane index), so duplicated magnitudes count like lax.top_k's
    and the result matches the ref.py oracle bit-for-bit.
    """
    rows, C = mag.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, C), 1)

    def body(i, carry):
        mag_c, cur = carry
        cur = jnp.max(mag_c, axis=-1, keepdims=True)      # (rows, 1)
        hit = jnp.min(jnp.where(mag_c >= cur, lane, C),
                      axis=-1, keepdims=True)             # first argmax
        mag_c = jnp.where(lane == hit, -jnp.inf, mag_c)
        return (mag_c, cur)

    _, kth = jax.lax.fori_loop(0, k_b, body,
                               (mag, jnp.zeros((mag.shape[0], 1),
                                               jnp.float32)))
    return kth


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
    mag = jnp.abs(x_ref[...].astype(jnp.float32))
    out_ref[...] = _kth_largest(mag, k_b)


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


def _ef_block_stats_kernel(m_ref, g_ref, eta_ref, out_ref, *, k_b: int):
    """Fused pass 1: per-block k_b-th largest |m + eta*g| — the accumulator
    is formed on the fly so it is never written back to HBM."""
    eta = eta_ref[0]
    acc = m_ref[...].astype(jnp.float32) + eta * g_ref[...].astype(jnp.float32)
    out_ref[...] = _kth_largest(jnp.abs(acc), k_b)


def _ef_stats_telemetry_kernel(m_ref, g_ref, eta_ref, tau_ref, mom_ref, *,
                               k_b: int):
    """Fused pass 1 + telemetry moments (DESIGN.md §10): the same streaming
    pass that ranks |acc| also reduces the two dense telemetry moments —
    ``sum g^2`` and ``sum acc^2`` per block row — while the operands sit in
    VMEM, so the compression-telemetry signal costs no extra HBM sweep.

    mom_ref: (rows, 2) per block row: [sum g^2, sum acc^2].
    """
    eta = eta_ref[0]
    gf = g_ref[...].astype(jnp.float32)
    acc = m_ref[...].astype(jnp.float32) + eta * gf
    tau_ref[...] = _kth_largest(jnp.abs(acc), k_b)
    mom_ref[...] = jnp.concatenate(
        [jnp.sum(gf * gf, axis=-1, keepdims=True),
         jnp.sum(acc * acc, axis=-1, keepdims=True)], axis=-1)


@functools.partial(jax.jit, static_argnames=("k_b", "interpret"))
def ef_block_stats(m: jax.Array, g: jax.Array, eta: jax.Array, k_b: int,
                   *, interpret: bool = True):
    """Per-block k_b-th largest |m + eta*g|. m, g: (nb, C) -> (nb, 1) f32."""
    nb, C = m.shape
    rows = _tile_rows(nb, interpret)
    grid = (pl.cdiv(nb, rows),)
    spec = pl.BlockSpec((rows, C), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_ef_block_stats_kernel, k_b=k_b),
        grid=grid,
        in_specs=[spec, spec, pl.BlockSpec((1,), lambda i: (0,))],
        out_specs=pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        interpret=interpret,
    )(m, g, eta.reshape(1))


@functools.partial(jax.jit, static_argnames=("k_b", "interpret"))
def ef_stats_telemetry(m: jax.Array, g: jax.Array, eta: jax.Array, k_b: int,
                       *, interpret: bool = True):
    """Fused pass 1 with telemetry moments.  m, g: (nb, C).

    Returns (tau: (nb, 1) f32, moments: (nb, 2) f32 = [sum g^2, sum acc^2]
    per block row).
    """
    nb, C = m.shape
    rows = _tile_rows(nb, interpret)
    grid = (pl.cdiv(nb, rows),)
    spec = pl.BlockSpec((rows, C), lambda i: (i, 0))
    out_shape = (jax.ShapeDtypeStruct((nb, 1), jnp.float32),
                 jax.ShapeDtypeStruct((nb, 2), jnp.float32))
    return pl.pallas_call(
        functools.partial(_ef_stats_telemetry_kernel, k_b=k_b),
        grid=grid,
        in_specs=[spec, spec, pl.BlockSpec((1,), lambda i: (0,))],
        out_specs=(pl.BlockSpec((rows, 1), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 2), lambda i: (i, 0))),
        out_shape=out_shape,
        interpret=interpret,
    )(m, g, eta.reshape(1))


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
