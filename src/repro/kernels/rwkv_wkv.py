"""Pallas TPU kernel for the RWKV-6 WKV recurrence — VMEM-resident state.

The roofline analysis (EXPERIMENTS §Roofline) shows rwkv6 *training* is
memory-bound 200x over compute: the per-token ``lax.scan`` reads and writes
the (K, V) = (64, 64) f32 state from HBM at every one of seq*layers steps
(1.6 TB/chip/step at 4k x 24L).  The structural fix is a kernel that keeps
the state in VMEM for the whole sequence:

    grid = (B*H,); each program owns one (batch, head) pair;
    blocks: r/k/v/w: (1, S, K) streamed HBM->VMEM once; y written once;
    the (K, V) state lives in registers/VMEM across the fori_loop.

HBM traffic per layer drops from 2*S*K*V*4 (state) + streams to just the
5 linear streams — a ~60x reduction of the dominant term (analytic; the
CPU dry-run lowers the jnp path, see kernels/ops.py note).

Semantics (per head, per step; w, u per-channel on the K axis):

    y_t = r_t . (S + diag(u) k_t^T v_t)
    S  <- diag(w_t) S + k_t^T v_t
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref,
                *, seq_len: int):
    # NB: refs are indexed with slices (pl.ds / [...]), never bare Python
    # ints — interpret-mode discharge chokes on raw int indices.
    S = s0_ref[...][0].astype(jnp.float32)         # (K, V)
    u = u_ref[...][0].astype(jnp.float32)          # (K,)

    def _step(ref, t):
        return ref[pl.ds(0, 1), pl.ds(t, 1), :][0, 0]

    def body(t, S):
        rt = _step(r_ref, t).astype(jnp.float32)   # (K,)
        kt = _step(k_ref, t).astype(jnp.float32)
        vt = _step(v_ref, t).astype(jnp.float32)   # (V,)
        wt = _step(w_ref, t).astype(jnp.float32)   # (K,)
        kv = kt[:, None] * vt[None, :]             # (K, V) outer
        y = jnp.sum(rt[:, None] * (S + u[:, None] * kv), axis=0)
        y_ref[pl.ds(0, 1), pl.ds(t, 1), :] = y.astype(y_ref.dtype)[None, None]
        return wt[:, None] * S + kv

    S = jax.lax.fori_loop(0, seq_len, body, S)
    sT_ref[...] = S.astype(sT_ref.dtype)[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv_forward(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
                u: jax.Array, s0: jax.Array, *, interpret: bool = True):
    """r/k/v: (B, S, H, K|V); w: (B, S, H, K) decay in (0,1); u: (H, K);
    s0: (B, H, K, V).  Returns (y: (B, S, H, V), sT: (B, H, K, V))."""
    B, S, H, K = r.shape
    V = v.shape[-1]
    rr = r.transpose(0, 2, 1, 3).reshape(B * H, S, K)
    kk = k.transpose(0, 2, 1, 3).reshape(B * H, S, K)
    vv = v.transpose(0, 2, 1, 3).reshape(B * H, S, V)
    ww = w.transpose(0, 2, 1, 3).reshape(B * H, S, K)
    uu = jnp.broadcast_to(u[None], (B, H, K)).reshape(B * H, K)
    ss = s0.reshape(B * H, K, V)

    seq_spec = pl.BlockSpec((1, S, K), lambda i: (i, 0, 0))
    val_spec = pl.BlockSpec((1, S, V), lambda i: (i, 0, 0))
    y, sT = pl.pallas_call(
        functools.partial(_wkv_kernel, seq_len=S),
        grid=(B * H,),
        in_specs=[seq_spec, seq_spec, val_spec, seq_spec,
                  pl.BlockSpec((1, K), lambda i: (i, 0)),
                  pl.BlockSpec((1, K, V), lambda i: (i, 0, 0))],
        out_specs=(val_spec, pl.BlockSpec((1, K, V), lambda i: (i, 0, 0))),
        out_shape=(jax.ShapeDtypeStruct((B * H, S, V), jnp.float32),
                   jax.ShapeDtypeStruct((B * H, K, V), jnp.float32)),
        interpret=interpret,
    )(rr, kk, vv, ww, uu, ss)
    return (y.reshape(B, H, S, V).transpose(0, 2, 1, 3),
            sT.reshape(B, H, K, V))
