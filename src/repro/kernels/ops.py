"""Public kernel ops — thin, shape-normalizing wrappers over the dispatch
registry (see :mod:`repro.kernels.dispatch`).

Every op takes ``impl``: None (the op's registered default policy), "ref"
(pure jnp), "pallas" (backend-appropriate kernel variant), or an explicit
"pallas-interpret" / "pallas-tpu".  The EF-compression ops default to the
fused Pallas path everywhere; the model-side ops default to the kernel only
on TPU (the CPU dry-run lowers the jnp oracle).  In tests both paths are
compared across shape/dtype sweeps.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from . import dispatch, ref, wire_pack
from .ef_topk import (block_stats, ef_apply, ef_block_stats as
                      _ef_block_stats_kernel, ef_stats_telemetry as
                      _ef_stats_telemetry_kernel, threshold_split as
                      _threshold_split_kernel)
from .flash_attention import flash_attention
from .rmsnorm import rmsnorm
from .rwkv_wkv import wkv_forward
from .wire_pack import pack_words as _pack_words_kernel, \
    unpack_words as _unpack_words_kernel

# --------------------------------------------------------------------------
# registry — the single place that binds op names to implementations
# --------------------------------------------------------------------------

dispatch.register_op(
    "ef_update",
    ref=ref.ef_block_update,
    pallas_interpret=functools.partial(ef_apply, interpret=True),
    pallas_tpu=functools.partial(ef_apply, interpret=False),
    default="pallas")

dispatch.register_op(
    "block_stats",
    ref=lambda x2, k_b: ref.block_abs_topk_threshold(
        x2.reshape(-1), k_b, x2.shape[1]).reshape(-1, 1),
    pallas_interpret=functools.partial(block_stats, interpret=True),
    pallas_tpu=functools.partial(block_stats, interpret=False),
    default="pallas")

dispatch.register_op(
    "ef_stats",
    ref=ref.ef_block_stats,
    pallas_interpret=functools.partial(_ef_block_stats_kernel,
                                       interpret=True),
    pallas_tpu=functools.partial(_ef_block_stats_kernel, interpret=False),
    default="pallas")

dispatch.register_op(
    "ef_stats_telemetry",
    ref=ref.ef_block_stats_telemetry,
    pallas_interpret=functools.partial(_ef_stats_telemetry_kernel,
                                       interpret=True),
    pallas_tpu=functools.partial(_ef_stats_telemetry_kernel,
                                 interpret=False),
    default="pallas")

dispatch.register_op(
    "threshold_split",
    ref=ref.threshold_split,
    pallas_interpret=functools.partial(_threshold_split_kernel,
                                       interpret=True),
    pallas_tpu=functools.partial(_threshold_split_kernel, interpret=False),
    default="pallas")

# pack/unpack run per leaf per step with a rows/ROWS-sized grid, so the
# interpret-mode cost is NOT one tile evaluation like the EF ops — policy
# "backend" keeps CPU runs on the vectorized jnp ref and TPUs on the kernel
# (parity is pinned across impls in tests/test_wire_format.py).
dispatch.register_op(
    "wire_pack",
    ref=ref.pack_fields,
    pallas_interpret=functools.partial(_pack_words_kernel, interpret=True),
    pallas_tpu=functools.partial(_pack_words_kernel, interpret=False),
    default="backend")

dispatch.register_op(
    "wire_unpack",
    ref=ref.unpack_fields,
    pallas_interpret=functools.partial(_unpack_words_kernel, interpret=True),
    pallas_tpu=functools.partial(_unpack_words_kernel, interpret=False),
    default="backend")

def _ref_vjp(kernel: Callable, reference: Callable) -> Callable:
    """``kernel`` forward, ``reference`` backward.

    The model-side kernels are forward-only ``pallas_call``s, which JAX
    cannot transpose; the train step differentiates through them.  The
    VJP recomputes the jnp oracle from the saved inputs and pulls the
    cotangent back through it.  Keyword arguments are static (flags,
    window, eps) and bound before the custom_vjp is formed.
    """
    def op(*args, **static):
        fwd_fn = functools.partial(kernel, **static)
        ref_fn = functools.partial(reference, **static)

        @jax.custom_vjp
        def f(*a):
            return fwd_fn(*a)

        def fwd(*a):
            return fwd_fn(*a), a

        def bwd(a, ct):
            return jax.vjp(ref_fn, *a)[1](ct)

        f.defvjp(fwd, bwd)
        return f(*args)
    return op


for _name, _kernel, _ref in (("attention", flash_attention, ref.mha_reference),
                             ("rmsnorm", rmsnorm, ref.rmsnorm_reference),
                             ("wkv", wkv_forward, ref.wkv_reference)):
    dispatch.register_op(
        _name,
        ref=_ref,
        pallas_interpret=_ref_vjp(
            functools.partial(_kernel, interpret=True), _ref),
        pallas_tpu=_ref_vjp(
            functools.partial(_kernel, interpret=False), _ref),
        default="backend")


# --------------------------------------------------------------------------
# block layout helpers
# --------------------------------------------------------------------------

def _to_blocks(x: jax.Array, block: int):
    """(L?, d) -> (L*nb, block) zero-padded block rows; blocks never span
    the leading (layer) axis.  1D inputs are a single layer."""
    shape = x.shape
    L = math.prod(shape[:-1]) if x.ndim >= 2 else 1
    d = shape[-1] if x.ndim >= 1 else 1
    flat = x.reshape(L, d)
    pad = (-d) % block
    padded = jnp.pad(flat, ((0, 0), (0, pad)))
    nb = (d + pad) // block
    return padded.reshape(L * nb, block), (shape, L, d)


def _from_blocks(blocks: jax.Array, meta) -> jax.Array:
    shape, L, d = meta
    return blocks.reshape(L, -1)[:, :d].reshape(shape)


# --------------------------------------------------------------------------
# EF-compression ops (the paper's per-step hot loop)
# --------------------------------------------------------------------------

def ef_threshold_update(m, g, eta, tau, *, impl: str | None = None):
    """Fused EF accumulate+sparsify against ONE scalar threshold.

    m, g: any shape; returns (sent, m') in m.dtype with the exact identity
    ``sent + m' == m + eta*g``.
    """
    m2, meta = _to_blocks(m.reshape(-1), 1024)
    g2, _ = _to_blocks(g.reshape(-1), 1024)
    tau_r = jnp.broadcast_to(jnp.asarray(tau, jnp.float32),
                             (m2.shape[0],)).reshape(-1, 1)
    sent, mnew = dispatch.call("ef_update", m2, g2,
                               jnp.asarray(eta, jnp.float32), tau_r,
                               impl=impl)
    meta = (m.shape, 1, m.size)
    return _from_blocks(sent, meta), _from_blocks(mnew, meta)


def block_topk_threshold(x, k_b: int, block: int = 1024, *,
                         impl: str | None = None):
    """Per-block k_b-th |.| statistic; (n_blocks,) f32."""
    x2, _ = _to_blocks(x.reshape(-1), block)
    return dispatch.call("block_stats", x2, k_b, impl=impl).reshape(-1)


class EFSplit(NamedTuple):
    """One leaf's fused EF compression (DESIGN.md §3).

    ``sent``/``resid`` have m's shape with ``sent + resid == m + eta*g``
    exactly; ``tau`` is (L*nb, 1); ``moments`` ((L*nb, 2) f32, [sum g^2,
    sum acc^2] per block row) is None without telemetry.

    ``vals``/``idx`` are the wire pairs pass 1 ranked, each (L, nb*k_b):
    per layer row, block by block, the block's top-k_b of |acc| in
    lax.top_k order — values f32, indices int32 flat into [0, d) with
    padding lanes clamped to d - 1 — the layout of
    ``compression.block_extract_sparse``.  For f32 operands they equal
    ``block_extract_sparse(sent)`` bit for bit: ``sent`` is ``acc`` on
    every entry at or above its block's tau and 0 elsewhere, and both
    rankings break ties by the lowest lane.  Entries tied at tau past the
    k_b-th stay in ``sent`` but off the wire.
    """

    sent: jax.Array
    resid: jax.Array
    tau: jax.Array
    vals: jax.Array
    idx: jax.Array
    moments: jax.Array | None = None


def _leaf_pairs(vals, lanes, meta, block: int):
    """A leaf's (k_b, L*nb) pass-1 pairs -> (L, nb*k_b) wire rows."""
    _, L, d = meta
    k_b = vals.shape[0]
    lanes = lanes.T.reshape(L, -1, k_b)
    base = jnp.arange(lanes.shape[1], dtype=jnp.int32)[None, :, None] * block
    idx = jnp.minimum((lanes + base).reshape(L, -1), d - 1)
    return vals.T.reshape(L, -1), idx


def fused_ef_compress(m, g, eta, gamma: float, block: int = 1024, *,
                      telemetry: bool = False,
                      impl: str | None = None) -> EFSplit:
    """The full two-pass fused EF compression (DESIGN.md §3) of one leaf.

    Per 1024-wide block b of ``acc = m + eta*g`` (blocks never span the
    leading layer axis of an (L, d) leaf): tau_b = k_b-th largest |acc_b|
    with k_b = round(gamma*block); sent keeps entries with |acc| >= tau_b
    and m' carries the rest; pass 1 also hands out the wire pairs
    (:class:`EFSplit`).

    ``telemetry`` (DESIGN.md §10): pass 1 additionally reduces the dense
    telemetry moments [sum g^2, sum acc^2] per block row on the same
    streamed operands — no extra HBM sweep.
    """
    return fused_ef_compress_batched([m], [g], eta, gamma, block,
                                     telemetry=telemetry, impl=impl)[0]


def fused_ef_compress_batched(ms, gs, eta, gamma: float, block: int = 1024,
                              *, telemetry: bool = False,
                              impl: str | None = None) -> list[EFSplit]:
    """Batched :func:`fused_ef_compress` over a LIST of (L_i, d_i) leaf
    pairs — ONE pass-1 launch and ONE pass-2 launch for the whole list
    (bucket-shaped launches, DESIGN.md §11).

    Every op in the two-pass scheme is block-row-local (blocks never span
    rows, thresholds/moments/pairs are per block row, the EF update is
    elementwise against its row's tau), so concatenating all leaves' block
    rows changes the launch geometry and nothing else: the returned
    per-leaf :class:`EFSplit` list is bit-identical to per-leaf
    :func:`fused_ef_compress` calls.
    """
    k_b = max(1, int(round(gamma * block)))
    blocks_m, blocks_g, metas, offs = [], [], [], [0]
    for m, g in zip(ms, gs):
        m2, meta = _to_blocks(m, block)
        g2, _ = _to_blocks(g, block)
        blocks_m.append(m2)
        blocks_g.append(g2)
        metas.append(meta)
        offs.append(offs[-1] + m2.shape[0])
    cat_m = jnp.concatenate(blocks_m, axis=0)
    cat_g = jnp.concatenate(blocks_g, axis=0)
    eta = jnp.asarray(eta, jnp.float32)
    moments = None
    if telemetry:
        tau, vals, lanes, moments = dispatch.call(
            "ef_stats_telemetry", cat_m, cat_g, eta, k_b, impl=impl)
    else:
        tau, vals, lanes = dispatch.call("ef_stats", cat_m, cat_g, eta, k_b,
                                         impl=impl)
    sent, mnew = dispatch.call("ef_update", cat_m, cat_g, eta, tau,
                               impl=impl)
    out = []
    for i, meta in enumerate(metas):
        rows = slice(offs[i], offs[i + 1])
        out.append(EFSplit(
            _from_blocks(sent[rows], meta), _from_blocks(mnew[rows], meta),
            tau[rows], *_leaf_pairs(vals[:, rows], lanes[:, rows], meta,
                                    block),
            None if moments is None else moments[rows]))
    return out


def threshold_split_blocks(x, tau, block: int = 1024, *,
                           impl: str | None = None):
    """Dense split of x into (sent, residual) against per-block tau.

    x: (d,) or (L, d); tau: (L*nb, 1) from :func:`block_topk_threshold`.
    ``sent + residual == x`` exactly.
    """
    x2, meta = _to_blocks(x, block)
    sent, res = dispatch.call("threshold_split", x2, tau, impl=impl)
    return _from_blocks(sent, meta), _from_blocks(res, meta)


# --------------------------------------------------------------------------
# wire pack/unpack (the packed payload codec's data-parallel core)
# --------------------------------------------------------------------------

def pack_fields(fields, bits: int, *, counts=None, period: int = 0,
                impl: str | None = None):
    """Pack (R, n) uint32 bit-fields into (R, ceil(n*bits/32)) uint32 words.

    ``bits`` in {4, 8, 16, 32}; n is zero-padded up to a whole word here, so
    callers slice by field count on unpack.  Layout per kernels/ref.py:
    little-endian fields within each word.

    ``counts`` + static ``period`` (ragged payloads, DESIGN.md §9): per-row
    valid counts — field j is zeroed when ``j % period >= counts[row]``,
    inside the ref/Pallas implementations' streaming pass.
    """
    if counts is not None and period <= 0:
        raise ValueError("ragged pack needs a positive period")
    if bits >= 32:
        out = fields.astype(jnp.uint32)
        if counts is not None:
            out = jnp.where(ref._count_mask(*out.shape, counts, period),
                            out, 0)
        return out
    F = 32 // bits
    R, n = fields.shape
    W = -(-n // F)
    pad = W * F - n
    if pad:
        fields = jnp.pad(fields, ((0, 0), (0, pad)))
    return dispatch.call("wire_pack", fields, bits, counts, period,
                         impl=impl)


def unpack_fields(words, n: int, bits: int, *, counts=None, period: int = 0,
                  impl: str | None = None):
    """Inverse of :func:`pack_fields`: (R, W) words -> first ``n`` fields,
    masked beyond the per-row valid ``counts`` when given."""
    if counts is not None and period <= 0:
        raise ValueError("ragged unpack needs a positive period")
    if bits >= 32:
        out = words.astype(jnp.uint32)
        if counts is not None:
            out = jnp.where(ref._count_mask(*out.shape, counts, period),
                            out, 0)
        return out
    out = dispatch.call("wire_unpack", words, bits, counts, period,
                        impl=impl)
    return out[:, :n]


def pack_fields_stream(fields, bits: int, *, impl: str | None = None):
    """Pack a FLAT word-aligned field stream — (N,) uint32 with N a
    multiple of 32//bits — into (N*bits/32,) uint32 words in ONE
    bucket-shaped launch (DESIGN.md §11).

    Packing is word-local, so this equals row-by-row :func:`pack_fields`
    on any row structure whose sections are whole words: the concatenated
    (already count-masked and zero-padded-to-word) field sections of every
    payload row of every leaf in a bucket go through a single kernel
    launch, and each leaf slices its exact words back out.
    """
    fields = fields.astype(jnp.uint32)
    if bits >= 32:
        return fields
    F = 32 // bits
    (n,) = fields.shape
    if n % F:
        raise ValueError(f"stream of {n} {bits}-bit fields is not "
                         f"word-aligned (need a multiple of {F})")
    W = n // F
    R, C = wire_pack.stream_shape(W)
    pad = R * C - W
    if pad:
        fields = jnp.concatenate(
            [fields, jnp.zeros((pad * F,), jnp.uint32)])
    words = dispatch.call("wire_pack", fields.reshape(R, C * F), bits,
                          None, 0, impl=impl)
    return words.reshape(-1)[:W]


def unpack_fields_stream(words, bits: int, *, impl: str | None = None):
    """Inverse of :func:`pack_fields_stream`: (W,) uint32 words -> the
    (W*32/bits,) uint32 field stream, one bucket-shaped launch."""
    words = words.astype(jnp.uint32)
    if bits >= 32:
        return words
    F = 32 // bits
    (W,) = words.shape
    R, C = wire_pack.stream_shape(W)
    pad = R * C - W
    if pad:
        words = jnp.concatenate([words, jnp.zeros((pad,), jnp.uint32)])
    fields = dispatch.call("wire_unpack", words.reshape(R, C), bits,
                           None, 0, impl=impl)
    return fields.reshape(-1)[:W * F]


# --------------------------------------------------------------------------
# model-side ops
# --------------------------------------------------------------------------

def attention(q, k, v, *, causal: bool = True, window: int | None = None,
              scale: float | None = None, q_offset: int | None = None,
              impl: str | None = None):
    """MHA (B,H,S,D)x(B,H,Sk,D). GQA: broadcast kv heads before calling.

    ``q_offset`` (absolute position of the first query, may be traced) is
    taken by the oracle only; the kernel places queries at the trailing
    Sq positions."""
    kw = dict(causal=causal, window=window, scale=scale)
    if q_offset is not None:
        kw["q_offset"] = q_offset
    return dispatch.call("attention", q, k, v, impl=impl, **kw)


def rms_norm(x, w, *, eps: float = 1e-6, impl: str | None = None):
    return dispatch.call("rmsnorm", x, w, eps=eps, impl=impl)


def wkv(r, k, v, w, u, s0, *, impl: str | None = None):
    """RWKV-6 WKV recurrence (see rwkv_wkv.py). Returns (y, final_state)."""
    return dispatch.call("wkv", r, k, v, w, u, s0, impl=impl)
