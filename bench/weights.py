"""Seeded weights for a configuration, made on the device in one jitted call.

The benchmark owns the weights: the program and the reference both start
from this tree.  Its layout is the decoder-only LM layout the reference
names (``reference/transformer_lm.py``); the harness checks that the
program's ``model.init`` gives the same tree of shapes and dtypes.

Distributions: embeddings N(0, 0.02^2); every matrix N(0, 1/fan_in) with
fan_in its input width; norm scales 1; the router in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 256) * 256


def layout(m: dict) -> dict:
    """Tree of ``(shape, dtype, kind)``; kind is ``embed`` | ``ones`` |
    ``fan_in`` (normal scaled by the second-to-last dim)."""
    D, L, V = m["d_model"], m["n_layers"], padded_vocab(m["vocab_size"])
    hd = m.get("head_dim") or D // m["n_heads"]
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


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[2], str)


def abstract(m: dict):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s[0], jnp.dtype(s[1])),
                        layout(m), is_leaf=_is_spec)


def shapes(m: dict) -> dict:
    """Leaf path (``blocks.attn.wq.w``) -> shape."""
    from bench.reference.csgd import path_names
    tree = abstract(m)
    return dict(zip(path_names(tree),
                    (x.shape for x in jax.tree.leaves(tree))))


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole-number seed (64 bits and more)."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def make(m: dict, seed: int, shardings=None):
    """The weights for ``seed``, on the device, in one jitted call."""
    specs, treedef = jax.tree.flatten(layout(m), is_leaf=_is_spec)
    key_words = jax.random.key_data(seed_key(seed))

    def build(kw):
        key = jax.random.wrap_key_data(kw, impl="threefry2x32")
        out = []
        for i, (shape, dt, kind) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            if kind == "ones":
                x = jnp.ones(shape, jnp.float32)
            elif kind == "embed":
                x = 0.02 * jax.random.normal(k, shape, jnp.float32)
            else:
                x = jax.random.normal(k, shape, jnp.float32) \
                    / np.sqrt(shape[-2])
            out.append(x.astype(jnp.dtype(dt)))
        return treedef.unflatten(out)

    out_sh = None
    if shardings is not None:
        out_sh = shardings
    return jax.jit(build, out_shardings=out_sh)(key_words)
