"""Attention: GQA with rope, query-chunked full/sliding-window for
train/prefill, masked-cache attention for decode.

Decode cache layout (see DESIGN.md §4): ``(B, S_max, H_kv, hd)`` with the
*sequence* dimension sharded over the model axis (and over data too for
batch=1 long-context).  Decode attention is written as plain einsums +
masked softmax; under pjit the partitioner turns the seq-dim reductions
into the flash-decoding (partial max/sum + small all-reduce) schedule.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import dispatch, ops
from repro.utils import DP, TP, hint
from .layers import apply_rope, dense, he_init


class KVCache(NamedTuple):
    k: jax.Array      # (B, S_max, H_kv, hd) — compute dtype or int8
    v: jax.Array      # (B, S_max, H_kv, hd)
    k_scale: Any = () # (B, S_max, H_kv, 1) f32 absmax scales (int8 only)
    v_scale: Any = ()

    @property
    def quantized(self) -> bool:
        return hasattr(self.k_scale, "ndim")


def quantize_kv(x: jax.Array):
    """Per-(position, head) absmax int8 quantization of a K/V tensor."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0 + 1e-30
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jax.Array, scale, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def maybe_quantize_cache(kv: "KVCache", cfg) -> "KVCache":
    if cfg.kv_cache_dtype != "int8":
        return kv
    kq, ks = quantize_kv(kv.k)
    vq, vs = quantize_kv(kv.v)
    return KVCache(k=kq, v=vq, k_scale=ks, v_scale=vs)


def init_attn(key, cfg: ModelConfig, dtype, d_model: int | None = None):
    D = d_model or cfg.d_model
    hd = cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": {"w": he_init(ks[0], (D, cfg.n_heads * hd), dtype)},
        "wk": {"w": he_init(ks[1], (D, cfg.n_kv_heads * hd), dtype)},
        "wv": {"w": he_init(ks[2], (D, cfg.n_kv_heads * hd), dtype)},
        "wo": {"w": he_init(ks[3], (cfg.n_heads * hd, D), dtype)},
    }
    if cfg.qkv_bias:
        for n, d_out in (("wq", cfg.n_heads * hd), ("wk", cfg.n_kv_heads * hd),
                         ("wv", cfg.n_kv_heads * hd)):
            p[n]["b"] = jnp.zeros((d_out,), dtype)
    return p


def _project_qkv(p, x, cfg: ModelConfig, pos):
    B, S, _ = x.shape
    hd = cfg.hd
    q = dense(p["wq"], x).reshape(B, S, cfg.n_heads, hd)
    k = dense(p["wk"], x).reshape(B, S, cfg.n_kv_heads, hd)
    v = dense(p["wv"], x).reshape(B, S, cfg.n_kv_heads, hd)
    if not cfg.attn_free:
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    q = hint(q, DP, None, TP, None)
    k = hint(k, DP, None, TP, None)
    v = hint(v, DP, None, TP, None)
    return q, k, v


def _expand_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B, S, H_kv, hd) -> (B, S, H, hd) by GQA group broadcast."""
    B, S, Hkv, hd = k.shape
    rep = n_heads // Hkv
    if rep == 1:
        return k
    return jnp.broadcast_to(k[:, :, :, None, :],
                            (B, S, Hkv, rep, hd)).reshape(B, S, n_heads, hd)


def _sdpa(q, k, v, cfg: ModelConfig, causal: bool, window: int | None):
    """q,k,v: (B, S, H, hd) -> (B, Sq, H, hd); query-chunked if long."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    qT = q.transpose(0, 2, 1, 3)
    kT = k.transpose(0, 2, 1, 3)
    vT = v.transpose(0, 2, 1, 3)
    chunk = cfg.attn_chunk
    # the flash kernel tiles the queries itself; query chunking only bounds
    # the oracle's (Sq, Sk) logits
    if Sq <= chunk or dispatch.resolve("attention") != "ref":
        out = ops.attention(qT, kT, vT, causal=causal, window=window)
    else:
        pad = (-Sq) % chunk
        qp = jnp.pad(qT, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else qT
        nq = (Sq + pad) // chunk

        def one(i):
            qi = jax.lax.dynamic_slice_in_dim(qp, i * chunk, chunk, axis=2)
            off = i * chunk + (Sk - Sq)
            return ops.attention(qi, kT, vT, causal=causal, window=window,
                                 q_offset=off, impl="ref")
        out = jax.lax.map(one, jnp.arange(nq)) \
            .transpose(1, 2, 0, 3, 4).reshape(B, H, Sq + pad, hd)
        if pad:
            out = out[:, :, :Sq]
    return out.transpose(0, 2, 1, 3)


def attention_block(p, x, cfg: ModelConfig, *, pos=None, causal=True,
                    window: int | None = None):
    """Full-sequence attention (train/prefill). Returns (out, KVCache)."""
    B, S, _ = x.shape
    if pos is None:
        pos = jnp.arange(S)
    q, k, v = _project_qkv(p, x, cfg, pos)
    win = window if window is not None else (cfg.sliding_window or None)
    out = _sdpa(q, _expand_kv(k, cfg.n_heads), _expand_kv(v, cfg.n_heads),
                cfg, causal, win)
    out = out.reshape(B, S, cfg.n_heads * cfg.hd)
    y = dense(p["wo"], out)
    return hint(y, DP, None, None), KVCache(k=k, v=v)


def decode_attention_block(p, x, cache: KVCache, cur_len, cfg: ModelConfig,
                           window: int | None = None):
    """One-token decode against a cache.

    x: (B, 1, D); cache.k/v: (B, S_max, H_kv, hd); cur_len: scalar — number
    of valid history tokens; the new token is written at index cur_len.
    Returns (out (B,1,D), updated cache).
    """
    B = x.shape[0]
    hd = cfg.hd
    pos = jnp.full((B, 1), cur_len, jnp.int32)
    q, k_new, v_new = _project_qkv(p, x, cfg, pos)

    S_max = cache.k.shape[1]
    onehot = (jnp.arange(S_max) == cur_len)[None, :, None, None]
    if cache.quantized:
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        k_store = hint(jnp.where(onehot, kq, cache.k), DP, TP, None, None)
        v_store = hint(jnp.where(onehot, vq, cache.v), DP, TP, None, None)
        ks_store = jnp.where(onehot, ks, cache.k_scale)
        vs_store = jnp.where(onehot, vs, cache.v_scale)
        new_cache = KVCache(k=k_store, v=v_store, k_scale=ks_store,
                            v_scale=vs_store)
        k_all = dequantize_kv(k_store, ks_store, x.dtype)
        v_all = dequantize_kv(v_store, vs_store, x.dtype)
    else:
        k_all = jnp.where(onehot, k_new.astype(cache.k.dtype), cache.k)
        v_all = jnp.where(onehot, v_new.astype(cache.v.dtype), cache.v)
        k_all = hint(k_all, DP, TP, None, None)   # seq-sharded cache
        v_all = hint(v_all, DP, TP, None, None)
        new_cache = KVCache(k=k_all, v=v_all)

    # GQA grouped score: (B, Hkv, G, hd) x (B, S, Hkv, hd)
    G = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(B, cfg.n_kv_heads, G, hd)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                        k_all.astype(jnp.float32)) / (hd ** 0.5)
    kpos = jnp.arange(S_max)[None, None, None, :]
    valid = kpos <= cur_len
    if window:
        valid &= kpos > cur_len - window
    scores = jnp.where(valid, scores, -1e30)
    # softmax over the (model-sharded) seq axis -> flash-decode combine
    m = jnp.max(scores, axis=-1, keepdims=True)
    p_ = jnp.exp(scores - m)
    denom = jnp.sum(p_, axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bskd->bkgd", p_, v_all.astype(jnp.float32))
    out = (out / denom).reshape(B, 1, cfg.n_heads * hd)
    y = dense(p["wo"], out.astype(x.dtype))
    return hint(y, DP, None, None), new_cache


# ------------------------------ cross attention ------------------------------

def init_cross_attn(key, cfg: ModelConfig, dtype, kv_dim: int | None = None):
    """Cross-attention: queries from the stream, K/V from memory (encoder
    output / image patches)."""
    D = cfg.d_model
    kvd = kv_dim or D
    hd = cfg.hd
    ks = jax.random.split(key, 4)
    return {
        "wq": {"w": he_init(ks[0], (D, cfg.n_heads * hd), dtype)},
        "wk": {"w": he_init(ks[1], (kvd, cfg.n_kv_heads * hd), dtype)},
        "wv": {"w": he_init(ks[2], (kvd, cfg.n_kv_heads * hd), dtype)},
        "wo": {"w": he_init(ks[3], (cfg.n_heads * hd, D), dtype)},
    }


def cross_attention_block(p, x, memory, cfg: ModelConfig,
                          kv: KVCache | None = None):
    """x: (B, Sq, D); memory: (B, Sm, D_kv). kv: precomputed memory K/V
    (decode path — memory is static). Returns (out, KVCache over memory)."""
    B, Sq, _ = x.shape
    hd = cfg.hd
    q = dense(p["wq"], x).reshape(B, Sq, cfg.n_heads, hd)
    if kv is None:
        Sm = memory.shape[1]
        k = dense(p["wk"], memory).reshape(B, Sm, cfg.n_kv_heads, hd)
        v = dense(p["wv"], memory).reshape(B, Sm, cfg.n_kv_heads, hd)
        kv = KVCache(k=k, v=v)
    out = _sdpa(q, _expand_kv(kv.k, cfg.n_heads),
                _expand_kv(kv.v, cfg.n_heads), cfg, causal=False, window=None)
    out = out.reshape(B, Sq, cfg.n_heads * hd)
    return hint(dense(p["wo"], out), DP, None, None), kv
