"""Basic layers: init helpers, norms, rotary embeddings, MLPs, embeddings.

Everything is functional: params are nested dicts of jnp arrays; layer
functions take ``(params, x, cfg)``.  Tensor-parallel sharding is expressed
with ``utils.hint`` symbolic constraints ("dp"/"tp") so the same code runs on
bare CPU, inside manual-over-data shard_map, or under full-auto pjit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.utils import DP, TP, hint


def he_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in or shape[0]
    return (jax.random.normal(key, shape) / jnp.sqrt(fan_in)).astype(dtype)


def init_dense(key, d_in, d_out, dtype, bias=False):
    p = {"w": he_init(key, (d_in, d_out), dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x, tp_dim: str | None = None):
    """x @ w (+ b). tp_dim: which side is tensor-parallel ("out"|"in"|None)."""
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    if tp_dim == "out":
        y = hint(y, DP, None, TP)
    return y


def rms_norm(p, x, eps: float):
    return ops.rms_norm(x, p["w"], eps=eps)


def init_rms_norm(d, dtype):
    return {"w": jnp.ones((d,), dtype)}


# ------------------------------ rotary --------------------------------------

def rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd); pos: (S,) or (B, S) absolute positions."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)
    if pos.ndim == 1:
        ang = pos[:, None].astype(jnp.float32) * freqs[None, :]    # (S, hd/2)
        ang = ang[None, :, None, :]
    else:
        ang = pos[..., None].astype(jnp.float32) * freqs
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------ MLP (SwiGLU) ---------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff: int | None = None, dtype=None):
    d_ff = d_ff or cfg.d_ff
    dtype = dtype or jnp.dtype(cfg.param_dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wg": he_init(k1, (cfg.d_model, d_ff), dtype),
        "wi": he_init(k2, (cfg.d_model, d_ff), dtype),
        "wo": he_init(k3, (d_ff, cfg.d_model), dtype),
    }


def mlp(p, x):
    """SwiGLU; hidden dim is tensor-parallel."""
    h = jax.nn.silu(x @ p["wg"].astype(x.dtype)) * (x @ p["wi"].astype(x.dtype))
    h = hint(h, DP, None, TP)
    y = h @ p["wo"].astype(x.dtype)
    return hint(y, DP, None, None)


# ------------------------------ embeddings -----------------------------------

def init_embed(key, cfg: ModelConfig, dtype):
    return {"w": (jax.random.normal(key, (cfg.padded_vocab, cfg.d_model))
                  * 0.02).astype(dtype)}


def embed(p, tokens, cfg: ModelConfig):
    """Token embedding; the table is sharded on d_model (tp) so the gather
    stays local and no vocab all-gather is generated."""
    w = hint(p["w"], None, TP)
    out = jnp.take(w, tokens, axis=0).astype(jnp.dtype(cfg.compute_dtype))
    return hint(out, DP, None, None)


def init_lm_head(key, cfg: ModelConfig, dtype):
    return {"w": he_init(key, (cfg.d_model, cfg.padded_vocab), dtype,
                         fan_in=cfg.d_model)}


def lm_head(p, x, true_vocab: int | None = None):
    """Vocab-parallel projection; logits stay sharded on vocab. Padded
    vocab columns (table rounded to a 256 multiple) are masked to -inf."""
    logits = (x @ p["w"].astype(x.dtype)).astype(jnp.float32)
    V = logits.shape[-1]
    if true_vocab is not None and true_vocab < V:
        mask = jnp.arange(V) < true_vocab
        logits = jnp.where(mask, logits, -1e30)
    return hint(logits, DP, None, TP)


def softmax_xent(logits: jax.Array, targets: jax.Array,
                 mask: jax.Array | None = None) -> jax.Array:
    """Stable CE over a (possibly vocab-sharded) logits tensor."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
