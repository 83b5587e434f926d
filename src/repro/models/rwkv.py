"""RWKV-6 "Finch" block — attention-free linear-recurrence time mixing with
data-dependent decay, plus channel mixing.  [arXiv:2404.05892]

Per head (hd = head size), per token:

    S_t  = diag(w_t) S_{t-1} + k_t^T v_t        (S: (hd_k, hd_v))
    y_t  = r_t (S_{t-1} + diag(u) k_t^T v_t)

with w_t = exp(-exp(w_base + lora_w(x_t))) — the *data-dependent* decay that
distinguishes RWKV-6 — and token-shift ddlerp mixing for the r/k/v/w/g
projections.  Train/prefill is a ``lax.scan`` over time carrying S (the
sequential dependency is inherent; the per-step body is (hd x hd) outer
products on the VPU/MXU); decode is the same body once.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.utils import DP, TP, hint
from .layers import he_init

MIX = ("r", "k", "v", "w", "g")


class RWKVState(NamedTuple):
    tm_prev: jax.Array   # (B, D) last token entering time-mix
    cm_prev: jax.Array   # (B, D) last token entering channel-mix
    wkv: jax.Array       # (B, H, hd, hd) recurrent state


def _dims(cfg: ModelConfig):
    hd = cfg.hd
    H = cfg.d_model // hd
    return H, hd


def init_rwkv6(key, cfg: ModelConfig, dtype):
    D = cfg.d_model
    H, hd = _dims(cfg)
    r = cfg.rwkv_lora_rank
    ks = jax.random.split(key, 12)
    p = {
        "mu": {c: (0.5 * jnp.ones((D,), jnp.float32)) for c in MIX},
        "lora_A": {c: he_init(ks[i], (D, r), dtype) for i, c in enumerate(MIX)},
        "lora_B": {c: (jnp.zeros((r, D), dtype)) for c in MIX},
        "wr": {"w": he_init(ks[5], (D, D), dtype)},
        "wk": {"w": he_init(ks[6], (D, D), dtype)},
        "wv": {"w": he_init(ks[7], (D, D), dtype)},
        "wg": {"w": he_init(ks[8], (D, D), dtype)},
        "wo": {"w": he_init(ks[9], (D, D), dtype)},
        "w_base": jnp.full((D,), -2.0, jnp.float32),
        "u": (0.1 * jax.random.normal(ks[10], (H, hd))).astype(jnp.float32),
        "ln_w": jnp.ones((D,), jnp.float32),
        "ln_b": jnp.zeros((D,), jnp.float32),
        "cm_k": {"w": he_init(ks[11], (D, cfg.d_ff), dtype)},
        "cm_v": {"w": he_init(ks[0], (cfg.d_ff, D), dtype)},
        "mu_cm": 0.5 * jnp.ones((D,), jnp.float32),
    }
    return p


def _ddlerp(p, c, x, xx):
    """Data-dependent lerp between x and shifted xx for channel c."""
    mix = p["mu"][c] + jnp.tanh(x @ p["lora_A"][c].astype(x.dtype)) \
        @ p["lora_B"][c].astype(x.dtype)
    return x + (xx - x) * mix.astype(x.dtype)


def _group_norm(y, w, b, H, hd, eps=1e-5):
    """Per-head layer norm of (..., H, hd) flattened output."""
    shape = y.shape
    yr = y.reshape(*shape[:-1], H, hd).astype(jnp.float32)
    mean = jnp.mean(yr, -1, keepdims=True)
    var = jnp.var(yr, -1, keepdims=True)
    yr = (yr - mean) * jax.lax.rsqrt(var + eps)
    out = yr.reshape(shape) * w + b
    return out


def time_mix(p, x, cfg: ModelConfig, state: RWKVState):
    """x: (B, L, D). Returns (y, new_state). Scan over time."""
    B, L, D = x.shape
    H, hd = _dims(cfg)
    # token shift: x_{t-1} with the carried boundary token
    xx = jnp.concatenate([state.tm_prev[:, None, :].astype(x.dtype),
                          x[:, :-1]], axis=1)
    xr = _ddlerp(p, "r", x, xx)
    xk = _ddlerp(p, "k", x, xx)
    xv = _ddlerp(p, "v", x, xx)
    xw = _ddlerp(p, "w", x, xx)
    xg = _ddlerp(p, "g", x, xx)

    r = (xr @ p["wr"]["w"].astype(x.dtype)).reshape(B, L, H, hd)
    k = (xk @ p["wk"]["w"].astype(x.dtype)).reshape(B, L, H, hd)
    v = (xv @ p["wv"]["w"].astype(x.dtype)).reshape(B, L, H, hd)
    g = jax.nn.silu(xg @ p["wg"]["w"].astype(x.dtype))
    r = hint(r, DP, None, TP, None)
    k = hint(k, DP, None, TP, None)
    v = hint(v, DP, None, TP, None)

    # data-dependent decay (B, L, H, hd), in (0,1)
    wdec = p["w_base"] + (jnp.tanh(xw @ p["lora_A"]["w"].astype(x.dtype))
                          @ p["lora_B"]["w"].astype(x.dtype)).astype(jnp.float32)
    wdec = jnp.exp(-jnp.exp(wdec.astype(jnp.float32))).reshape(B, L, H, hd)

    # on TPU the VMEM-resident WKV kernel (kernels/rwkv_wkv.py) removes the
    # per-step HBM state round-trip that makes the scan memory-bound
    y4, S_final = ops.wkv(r.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), wdec, p["u"], state.wkv)
    y = y4.reshape(B, L, D)
    y = _group_norm(y, p["ln_w"], p["ln_b"], H, hd).astype(x.dtype) * g
    out = hint(y @ p["wo"]["w"].astype(x.dtype), DP, None, None)
    new_state = state._replace(tm_prev=x[:, -1].astype(jnp.float32),
                               wkv=S_final)
    return out, new_state


def channel_mix(p, x, state: RWKVState):
    B, L, D = x.shape
    xx = jnp.concatenate([state.cm_prev[:, None, :].astype(x.dtype),
                          x[:, :-1]], axis=1)
    xk = x + (xx - x) * p["mu_cm"].astype(x.dtype)
    h = jnp.square(jax.nn.relu(xk @ p["cm_k"]["w"].astype(x.dtype)))
    h = hint(h, DP, None, TP)
    y = hint(h @ p["cm_v"]["w"].astype(x.dtype), DP, None, None)
    return y, state._replace(cm_prev=x[:, -1].astype(jnp.float32))


def init_rwkv_state(cfg: ModelConfig, B: int) -> RWKVState:
    H, hd = _dims(cfg)
    return RWKVState(tm_prev=jnp.zeros((B, cfg.d_model), jnp.float32),
                     cm_prev=jnp.zeros((B, cfg.d_model), jnp.float32),
                     wkv=jnp.zeros((B, H, hd, hd), jnp.float32))
