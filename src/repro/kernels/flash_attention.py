"""Pallas TPU flash-attention forward kernel (serving hot path).

Online-softmax tiling [Dao '22], adapted to the TPU memory hierarchy:

* grid = (batch*heads, Sq/BQ); the KV sequence is walked *inside* the kernel
  with a ``fori_loop`` so the (BQ, D) query tile, the running (BQ, 1)
  max/denominator and the (BQ, D) accumulator all stay in VMEM/VREGs;
* K/V tiles are streamed HBM->VMEM by the BlockSpec pipeline, (BK, D) at a
  time, with D padded to a 128-lane multiple so the (BQ, BK) logits matmul
  lands on the MXU;
* causal + sliding-window masking is applied per tile; tiles entirely outside
  the (causal, window) band are skipped via the loop bounds — this is what
  makes the sliding-window variant sub-quadratic.

Used for prefill; decode uses the seq-sharded flash-decode combine in
``repro/models/attention.py`` (a different memory layout problem).
Validated in interpret mode against ``ref.mha_reference``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, causal: bool,
                  window: int | None, bq: int, bk: int, sk: int,
                  q_offset: int):
    qi = pl.program_id(1)
    # NB: refs are indexed with slices (pl.ds / [...]), never bare Python
    # ints — interpret-mode discharge chokes on raw int indices.
    q = q_ref[...][0].astype(jnp.float32) * scale       # (BQ, D)
    D = q.shape[-1]

    # Query i sits at absolute position q_offset + i (q_offset = Sk - Sq:
    # rectangular Q<K means the queries are the *last* Sq positions).
    q_start = qi * bq + q_offset
    # KV tile range intersecting the causal/window band of this Q tile.
    lo = 0
    if window is not None:
        lo = jnp.maximum(q_start - (window - 1), 0) // bk
    hi = pl.cdiv(sk, bk)
    if causal:
        hi = jnp.minimum(hi, pl.cdiv(q_start + bq, bk))

    def body(kj, carry):
        acc, m_i, l_i = carry
        kv_rows = (pl.ds(0, 1), pl.ds(kj * bk, bk), slice(None))
        k = k_ref[kv_rows][0].astype(jnp.float32)       # (BK, D)
        v = v_ref[kv_rows][0].astype(jnp.float32)
        s = q @ k.T                                      # (BQ, BK) on MXU
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < sk
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_i - m_new)
        l_new = alpha * l_i + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + p @ v
        return acc, m_new, l_new

    init = (jnp.zeros((bq, D), jnp.float32),
            jnp.full((bq, 1), NEG_INF, jnp.float32),
            jnp.zeros((bq, 1), jnp.float32))
    acc, m_i, l_i = jax.lax.fori_loop(lo, hi, body, init)
    o_ref[...] = (acc / jnp.maximum(l_i, 1e-30)).astype(o_ref.dtype)[None]


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "bq", "bk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    interpret: bool = True) -> jax.Array:
    """q: (B, H, Sq, D); k, v: (B, H, Sk, D) (GQA pre-broadcast in ops.py)."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    # ragged lengths (a shifted-by-one training sequence) pad up to whole
    # tiles: padded keys fail the in-kernel ``kpos < sk`` mask, padded
    # query rows are sliced off
    sq_p = -(-Sq // bq) * bq
    sk_p = -(-Sk // bk) * bk

    def rows(x, s, s_p):
        x = x.reshape(B * H, s, D)
        return jnp.pad(x, ((0, 0), (0, s_p - s), (0, 0))) if s_p > s else x

    grid = (B * H, sq_p // bq)
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, sk=Sk,
                               q_offset=Sk - Sq)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk_p, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, sk_p, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, sq_p, D), q.dtype),
        interpret=interpret,
    )(rows(q, Sq, sq_p), rows(k, Sk, sk_p), rows(v, Sk, sk_p))
    return out[:, :Sq].reshape(B, H, Sq, D)
