"""Pure-jnp oracles for every Pallas kernel. Ground truth for tests.

Each function mirrors the corresponding kernel's contract exactly; kernels are
validated with ``assert_allclose`` against these across shape/dtype sweeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# --------------------------- ef_topk ---------------------------------------

def ef_threshold_update(m: jax.Array, g: jax.Array, eta: jax.Array,
                        tau: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Fused error-feedback threshold sparsification (DESIGN.md §3).

        acc  = m + eta * g
        sent = acc * (|acc| >= tau)
        m'   = acc - sent

    All arrays same shape; eta, tau scalars. Returns (sent, m_new) in the
    dtype of ``m``.
    """
    acc = m.astype(jnp.float32) + eta.astype(jnp.float32) * g.astype(jnp.float32)
    mask = jnp.abs(acc) >= tau.astype(jnp.float32)
    sent = jnp.where(mask, acc, 0.0)
    m_new = acc - sent
    return sent.astype(m.dtype), m_new.astype(m.dtype)


def ef_block_update(m: jax.Array, g: jax.Array, eta: jax.Array,
                    tau: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-block-row EF threshold sparsification (DESIGN.md §3).

    m, g: (R, C) block rows; eta scalar; tau: (R, 1) per-row thresholds.

        acc  = m + eta * g
        sent = acc * (|acc| >= tau_row)
        m'   = acc - sent

    Returns (sent, m_new) in the dtype of ``m``.  The EF identity
    ``sent + m' == m + eta*g`` holds bit-exactly in f32.
    """
    acc = m.astype(jnp.float32) + eta.astype(jnp.float32) * g.astype(jnp.float32)
    mask = jnp.abs(acc) >= tau.reshape(-1, 1).astype(jnp.float32)
    sent = jnp.where(mask, acc, 0.0)
    return sent.astype(m.dtype), (acc - sent).astype(m.dtype)


def _block_topk_pairs(acc: jax.Array, k_b: int):
    """Per-block-row top-k_b of |acc|: the k_b-th magnitude (R, 1) and the
    wire pairs in the kernel's transposed layout — signed values (k_b, R)
    f32 and block-local lanes (k_b, R) int32, in lax.top_k order."""
    mags, lanes = jax.lax.top_k(jnp.abs(acc), k_b)
    vals = jnp.take_along_axis(acc, lanes, axis=1)
    return mags[:, -1:], vals.T, lanes.astype(jnp.int32).T


def ef_block_stats(m: jax.Array, g: jax.Array, eta: jax.Array,
                   k_b: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused pass 1 (DESIGN.md §3) over (R, C) block rows: per row the
    k_b-th largest |m + eta*g| (R, 1) and its top-k_b wire pairs, signed
    values (k_b, R) f32 and block-local lanes (k_b, R) int32."""
    acc = m.astype(jnp.float32) + eta.astype(jnp.float32) * g.astype(jnp.float32)
    return _block_topk_pairs(acc, k_b)


def ef_block_stats_telemetry(m: jax.Array, g: jax.Array, eta: jax.Array,
                             k_b: int):
    """Fused pass 1 + telemetry moments (DESIGN.md §10):
    :func:`ef_block_stats`' (tau, vals, lanes) AND the dense telemetry
    moments of the same streamed operands, (R, 2) f32 with columns
    [sum g^2, sum acc^2]."""
    gf = g.astype(jnp.float32)
    acc = m.astype(jnp.float32) + eta.astype(jnp.float32) * gf
    moments = jnp.concatenate(
        [jnp.sum(gf * gf, axis=-1, keepdims=True),
         jnp.sum(acc * acc, axis=-1, keepdims=True)], axis=-1)
    return _block_topk_pairs(acc, k_b) + (moments,)


def threshold_split(x: jax.Array, tau: jax.Array) -> tuple[jax.Array,
                                                           jax.Array]:
    """Per-block-row dense split: (sent, residual). x: (R, C); tau: (R, 1)."""
    xf = x.astype(jnp.float32)
    sent = jnp.where(jnp.abs(xf) >= tau.reshape(-1, 1).astype(jnp.float32),
                     xf, 0.0)
    return sent.astype(x.dtype), (xf - sent).astype(x.dtype)


def block_abs_topk_threshold(x: jax.Array, k_b: int, block: int) -> jax.Array:
    """Per-block k_b-th largest |x|. x flat, padded to a multiple of block.

    Returns (n_blocks,) thresholds — pass-1 statistics for the two-pass
    block-local selection.
    """
    blocks = x.reshape(-1, block)
    mag = jnp.abs(blocks)
    vals, _ = jax.lax.top_k(mag, k_b)
    return vals[:, -1]


# --------------------------- wire pack/unpack ------------------------------

def _count_mask(R: int, n: int, counts: jax.Array, period: int) -> jax.Array:
    """(R, n) validity mask: field j of a row is valid iff
    ``j % period < count`` — the ragged-payload predicate (DESIGN.md §9):
    a per-block prefix for block-local wire rows (period = k_b), a plain
    row prefix for flat rows (period = k)."""
    pos = jnp.arange(n, dtype=jnp.int32) % jnp.int32(period)
    return pos[None, :] < jnp.asarray(counts, jnp.int32).reshape(-1, 1)


def pack_fields(fields: jax.Array, bits: int,
                counts: jax.Array | None = None,
                period: int = 0) -> jax.Array:
    """Pack (R, n) uint32 bit-fields into (R, n*bits/32) uint32 words.

    ``bits`` in {4, 8, 16, 32}; n must be a multiple of 32//bits (callers
    zero-pad).  Field f of word w occupies bits [f*bits, (f+1)*bits) —
    little-endian fields within the word, so packed payloads are
    byte-order independent at the word level.  Fields are masked to
    ``bits`` before packing; disjoint bit ranges make the or a sum.

    ``counts`` (+ static ``period``): per-row valid counts; fields with
    ``j % period >= counts[row]`` are zeroed on the way into the words, so
    ragged payloads never leak stale entries past their count header.
    """
    fields = fields.astype(jnp.uint32)
    R, n = fields.shape
    if counts is not None:
        fields = jnp.where(_count_mask(R, n, counts, period), fields, 0)
    if bits >= 32:
        return fields
    F = 32 // bits
    mask = jnp.uint32((1 << bits) - 1)
    w = (fields & mask).reshape(R, n // F, F)
    shifts = (jnp.arange(F, dtype=jnp.uint32) * jnp.uint32(bits))
    return jnp.sum(w << shifts[None, None, :], axis=-1, dtype=jnp.uint32)


def unpack_fields(words: jax.Array, bits: int,
                  counts: jax.Array | None = None,
                  period: int = 0) -> jax.Array:
    """Inverse of :func:`pack_fields`: (R, W) words -> (R, W*32/bits)
    fields.  ``counts`` masks decoded fields beyond the per-row valid
    count to 0 — decode-side enforcement of the ragged contract, robust
    to arbitrary bytes in the invalid tail."""
    words = words.astype(jnp.uint32)
    if bits >= 32:
        if counts is not None:
            words = jnp.where(
                _count_mask(*words.shape, counts, period), words, 0)
        return words
    F = 32 // bits
    R, W = words.shape
    mask = jnp.uint32((1 << bits) - 1)
    shifts = (jnp.arange(F, dtype=jnp.uint32) * jnp.uint32(bits))
    fields = (words[:, :, None] >> shifts[None, None, :]) & mask
    fields = fields.reshape(R, W * F)
    if counts is not None:
        fields = jnp.where(_count_mask(R, W * F, counts, period), fields, 0)
    return fields


# --------------------------- flash attention -------------------------------

def mha_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = True, window: int | None = None,
                  scale: float | None = None,
                  q_offset: int | None = None) -> jax.Array:
    """Multi-head attention oracle.

    q: (B, H, Sq, D); k, v: (B, H, Sk, D). ``window`` = sliding-window size
    (None = full). ``q_offset`` = absolute position of the first query
    (default Sk - Sq: queries are the trailing positions). Returns
    (B, H, Sq, D) in q.dtype, computed in f32.
    """
    *_, Sq, D = q.shape
    Sk = k.shape[-2]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if q_offset is None:
        q_offset = Sk - Sq
    qpos = jnp.arange(Sq)[:, None] + q_offset
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


# --------------------------- rmsnorm ----------------------------------------

def rmsnorm_reference(x: jax.Array, w: jax.Array,
                      eps: float = 1e-6) -> jax.Array:
    """RMSNorm oracle: x * rsqrt(mean(x^2) + eps) * w, f32 accumulation."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


# --------------------------- rwkv wkv ---------------------------------------

def wkv_reference(r, k, v, w, u, s0):
    """Sequential oracle for the RWKV-6 WKV recurrence.

    r/k/v/w: (B, S, H, K|V); u: (H, K); s0: (B, H, K, V).
    Returns (y: (B, S, H, V), sT)."""
    def step(S_state, inp):
        rt, kt, vt, wt = inp                        # (B, H, K|V) each
        kv = jnp.einsum("bhk,bhv->bhkv", kt, vt)
        y = jnp.einsum("bhk,bhkv->bhv", rt,
                       S_state + u[None, :, :, None] * kv)
        return wt[..., None] * S_state + kv, y

    seq = [a.transpose(1, 0, 2, 3).astype(jnp.float32) for a in (r, k, v, w)]
    sT, ys = jax.lax.scan(step, s0.astype(jnp.float32), tuple(seq))
    return ys.transpose(1, 0, 2, 3), sT
