"""Architecture module of the pre-norm decoder-only LM (dense or
sparse-expert layers): the plain reference of its loss, its weight layout,
its model FLOPs and the published ``config.json`` keys it reads.  A
configuration file names it with ``"reference": "transformer_lm"``; the
benchmark's shared code reaches the architecture only through these names.

Straightforward ``jax.numpy``; it imports nothing of the program.  The
equations are those of a pre-norm Llama-style decoder, as the program's
configurations state them:

    x_0     = E[tokens]                                (no embedding scale)
    h       = rms(x) * g,   rms(x) = x / sqrt(mean(x^2) + eps)
    q, k, v = h Wq, h Wk, h Wv;  rotary on q, k (half-split pairs,
              freq_i = theta^(-2i/hd));  k, v repeated over the head groups
    a       = softmax(q k^T / sqrt(hd) + causal mask) v
    x      += a Wo
    x      += silu(h' Wg) * (h' Wi) Wo                 (dense layer)
    x      += sum_top-k gate_e * FFN_e(h')             (sparse-expert layer)
    logits  = rms(x_L) W_head over the true vocabulary; loss = mean CE

Sparse-expert layers route each token to its ``experts_per_token``
largest router probabilities (gates renormalised to sum 1) and keep, per
expert, only the first ``C = ceil(T k / E) * capacity_factor`` (token,
slot) assignments in token order, as the configuration's capacity states;
the Switch load-balance term ``E * coef * sum(density * mean prob)`` is
added to the loss.

``compute`` is the precision the products run in: ``"float32"`` (at
``highest`` precision, under the caller's context) is the reference of
a float32 configuration and of a bfloat16 one; ``"bfloat16"`` (products
and activations in bfloat16 from the stored weights, normalisation and
softmax in float32) is the control of a float32 configuration and
``"float8_e4m3fn"`` (every matmul operand rounded to e4m3 after scaling by
its absolute maximum, products accumulated in float32, activations in
bfloat16) that of a bfloat16 one.  The weights and their updates keep the
configuration's types in every case.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.flops import attention_pairs
from bench.weights import padded_vocab


FP8 = "float8_e4m3fn"


def _e4m3(a):
    """``a`` rounded to e4m3 on a per-tensor absmax scale (448 = e4m3 max)."""
    s = jax.lax.stop_gradient(jnp.max(jnp.abs(a.astype(jnp.float32)))
                              / 448.0 + 1e-30)
    return ((a.astype(jnp.float32) / s).astype(FP8).astype(jnp.float32)
            * s).astype(jnp.bfloat16)


@jax.custom_vjp
def _fp8(a):
    """A matmul operand in e4m3; its cotangent passes through unrounded
    (a cast's own transpose would round it unscaled, to zero)."""
    return _e4m3(a)


_fp8.defvjp(lambda a: (_e4m3(a), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_cotangent(y):
    """The identity; the cotangent that reaches a product is rounded to
    e4m3 on its own scale, so the backward's products run in e4m3 too."""
    return y


_fp8_cotangent.defvjp(lambda y: (y, None),
                      lambda _, g: (_e4m3(g).astype(g.dtype),))


def _mm_fp8(spec, a, b):
    return _fp8_cotangent(jnp.einsum(
        spec, _fp8(a), _fp8(b),
        preferred_element_type=jnp.float32).astype(a.dtype))


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x: (B, S, H, hd), rotary over half-split pairs, in float32."""
    S, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                           -1).astype(x.dtype)


def _attention(lp, h, m, mm):
    B, S, D = h.shape
    H, KV = m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or D // H
    q = mm("bsd,de->bse", h, lp["wq"]["w"]).reshape(B, S, H, hd)
    k = mm("bsd,de->bse", h, lp["wk"]["w"]).reshape(B, S, KV, hd)
    v = mm("bsd,de->bse", h, lp["wv"]["w"]).reshape(B, S, KV, hd)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = mm("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    a = mm("bhqk,bkhd->bqhd", p.astype(h.dtype), v)
    return mm("bse,ed->bsd", a.reshape(B, S, H * hd), lp["wo"]["w"])


def _mlp(lp, h, mm):
    u = jax.nn.silu(mm("bsd,df->bsf", h, lp["wg"])) \
        * mm("bsd,df->bsf", h, lp["wi"])
    return mm("bsf,fd->bsd", u, lp["wo"])


def _experts(lp, h, m, mm):
    B, S, D = h.shape
    E, k = m["n_experts"], m["experts_per_token"]
    T = B * S
    x = h.reshape(T, D)
    probs = jax.nn.softmax(x.astype(jnp.float32) @ lp["router"]["w"]
                           .astype(jnp.float32), axis=-1)
    gate, eid = jax.lax.top_k(probs, k)
    gate = gate / (jnp.sum(gate, -1, keepdims=True) + 1e-9)
    density = jnp.mean(jax.nn.one_hot(eid[:, 0], E), axis=0)
    aux = jnp.sum(density * jnp.mean(probs, 0)) * E \
        * m.get("router_aux_coef", 0.01)
    cap = min(T, max(1, int(-(-T * k // E) * m.get("capacity_factor", 1.25))))
    # rank of each (token, slot) among its expert's assignments, token order
    onehot = jax.nn.one_hot(eid.reshape(-1), E, dtype=jnp.int32)
    rank = jnp.sum((jnp.cumsum(onehot, 0) - 1) * onehot, -1).reshape(T, k)
    w = jnp.where(rank < cap, gate, 0.0)                         # (T, k)
    gates = jnp.sum(jnp.where(eid[:, :, None] == jnp.arange(E), w[:, :, None],
                              0.0), 1)                           # (T, E)

    @jax.checkpoint
    def expert(y, e):
        """Add expert e's output, weighted by its gate, to every token."""
        u = jax.nn.silu(mm("td,df->tf", x, lp["wg"][e])) \
            * mm("td,df->tf", x, lp["wi"][e])
        f = mm("tf,fd->td", u, lp["wo"][e]).astype(jnp.float32)
        return y + gates[:, e, None] * f, None

    y, _ = jax.lax.scan(expert, jnp.zeros((T, D), jnp.float32),
                        jnp.arange(E))
    return y.astype(h.dtype).reshape(B, S, D), aux


def loss(params, tokens, m: dict, compute: str = "float32"):
    """Mean next-token cross-entropy (+ the load-balance term)."""
    mm = _mm_fp8 if compute == FP8 else jnp.einsum
    dt = jnp.dtype(jnp.bfloat16 if compute == FP8 else compute)
    # e4m3 rounds every matmul operand itself; the weights keep their types
    p = params if compute == FP8 else jax.tree.map(lambda a: a.astype(dt),
                                                   params)
    eps = m["norm_eps"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = p["embed"]["w"][inputs].astype(dt)

    @jax.checkpoint
    def layer(carry, lp):
        x, aux = carry
        x = x + _attention(lp["attn"], _rms(x, lp["attn_norm"]["w"], eps), m,
                           mm)
        h = _rms(x, lp["mlp_norm"]["w"], eps)
        if "moe" in lp:
            y, a = _experts(lp["moe"], h, m, mm)
            aux = aux + a
        else:
            y = _mlp(lp["mlp"], h, mm)
        return (x + y, aux), None

    (x, aux), _ = jax.lax.scan(layer, (x, jnp.float32(0.0)), p["blocks"])
    x = _rms(x, p["final_norm"]["w"], eps)
    head = p["lm_head"]["w"] if "lm_head" in p else p["embed"]["w"].T
    logits = mm("bsd,dv->bsv", x, head[:, :m["vocab_size"]]) \
        .astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(lse - gold) + aux


# published config.json key -> the program's configuration key
published_keys = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
                  "intermediate_size": "moe_d_ff",
                  "num_attention_heads": "n_heads",
                  "num_key_value_heads": "n_kv_heads",
                  "num_local_experts": "n_experts",
                  "num_experts_per_tok": "experts_per_token",
                  "vocab_size": "vocab_size", "rope_theta": "rope_theta",
                  "rms_norm_eps": "norm_eps",
                  "tie_word_embeddings": "tie_embeddings",
                  "torch_dtype": "param_dtype"}


def _hd(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def layout(m: dict) -> dict:
    """Tree of ``(shape, dtype, kind)``, layers stacked on a leading axis;
    kind is ``embed`` | ``ones`` | ``fan_in`` (``bench/weights.py``); the
    router in float32."""
    D, L, V = m["d_model"], m["n_layers"], padded_vocab(m["vocab_size"])
    hd = _hd(m)
    H, KV = m["n_heads"], m["n_kv_heads"]
    dt = m["param_dtype"]
    block = {
        "attn_norm": {"w": ((L, D), dt, "ones")},
        "attn": {"wq": {"w": ((L, D, H * hd), dt, "fan_in")},
                 "wk": {"w": ((L, D, KV * hd), dt, "fan_in")},
                 "wv": {"w": ((L, D, KV * hd), dt, "fan_in")},
                 "wo": {"w": ((L, H * hd, D), dt, "fan_in")}},
        "mlp_norm": {"w": ((L, D), dt, "ones")},
    }
    if m["family"] == "moe":
        E, F = m["n_experts"], m["moe_d_ff"]
        block["moe"] = {"router": {"w": ((L, D, E), "float32", "fan_in")},
                        "wg": ((L, E, D, F), dt, "fan_in"),
                        "wi": ((L, E, D, F), dt, "fan_in"),
                        "wo": ((L, E, F, D), dt, "fan_in")}
    elif m["family"] == "dense":
        F = m["d_ff"]
        block["mlp"] = {"wg": ((L, D, F), dt, "fan_in"),
                        "wi": ((L, D, F), dt, "fan_in"),
                        "wo": ((L, F, D), dt, "fan_in")}
    else:
        raise ValueError(f"no weight layout for family {m['family']!r}")
    tree = {"embed": {"w": ((V, D), dt, "embed")},
            "final_norm": {"w": ((D,), dt, "ones")},
            "blocks": block}
    if not m.get("tie_embeddings", False):
        tree["lm_head"] = {"w": ((D, V), dt, "fan_in")}
    return tree


# Model FLOPs per trained token count the matmuls of the published shapes,
# forward and backward (6 per weight that a token meets): attention's four
# projections, the MLP's three (for a sparse-expert layer, those of the
# ``experts_per_token`` experts a token is routed to, plus the router) and
# the output head over the true vocabulary.  The input-embedding lookup
# adds none.  Attention's score and value products add ``4 * H * hd`` per
# attended (query, key) pair forward, 3x that forward and backward; causal
# masking halves them: a row of S positions attends S(S+1)/2 pairs.
# Armijo trial forwards and recomputation are not counted.

def matmul_params(m: dict) -> int:
    """Weights a token is multiplied by, once each."""
    D, hd = m["d_model"], _hd(m)
    H, KV = m["n_heads"], m["n_kv_heads"]
    attn = D * (H + 2 * KV) * hd + H * hd * D
    if m["family"] == "moe":
        ffn = m["experts_per_token"] * 3 * D * m["moe_d_ff"] \
            + D * m["n_experts"]
    else:
        ffn = 3 * D * m["d_ff"]
    return m["n_layers"] * (attn + ffn) + D * m["vocab_size"]


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward model FLOPs per trained token at ``seq``
    positions per row."""
    attn = 3 * 4 * m["n_heads"] * _hd(m) * attention_pairs(seq) / seq
    return 6.0 * matmul_params(m) + m["n_layers"] * attn


def flash_forward_flops(m: dict, rows: int, seq: int) -> float:
    """One causal attention forward over ``rows`` rows (all layers' calls
    are alike; this is one call)."""
    return 4.0 * rows * m["n_heads"] * _hd(m) * attention_pairs(seq)
