"""Seeded weights for a configuration, made on the device in one jitted call.

The benchmark owns the weights: the program and the reference both start
from this tree.  Its layout is ``layout(m)`` of the configuration's
architecture module (``bench/reference/<name>.py``, resolved by
``manifest.resolve``): a tree of ``(shape, dtype, kind)``.  The harness
checks that the program's ``model.init`` gives the same tree of shapes and
dtypes.

Leaf ``i`` draws from ``fold_in(key, i)``.  Kinds every layout may use:
``embed`` N(0, 0.02^2); ``fan_in`` N(0, 1/fan_in) with fan_in the
second-to-last dim (a matrix's input width); ``ones`` 1.  Any other kind
is drawn by the module's ``init(kind, key, shape)``.  Each draw is cast
to the leaf's type.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


KINDS = {"ones", "embed", "fan_in"}


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 256) * 256


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[2], str)


def abstract(arch, m: dict):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s[0], jnp.dtype(s[1])),
                        arch.layout(m), is_leaf=_is_spec)


def shapes(arch, m: dict) -> dict:
    """Leaf path (``blocks.attn.wq.w``) -> shape."""
    from bench.reference.csgd import path_names
    tree = abstract(arch, m)
    return dict(zip(path_names(tree),
                    (x.shape for x in jax.tree.leaves(tree))))


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole-number seed (64 bits and more)."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def make(arch, m: dict, seed: int, shardings=None):
    """The weights for ``seed``, on the device, in one jitted call."""
    specs, treedef = jax.tree.flatten(arch.layout(m), is_leaf=_is_spec)
    own = {kind for _, _, kind in specs} - KINDS
    if own and not hasattr(arch, "init"):
        raise ValueError(f"weight kinds {sorted(own)}: {arch.__name__} "
                         f"has no init")
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
            elif kind == "fan_in":
                x = jax.random.normal(k, shape, jnp.float32) \
                    / np.sqrt(shape[-2])
            else:
                x = arch.init(kind, k, shape)
            out.append(x.astype(jnp.dtype(dt)))
        return treedef.unflatten(out)

    return jax.jit(build, out_shardings=shardings)(key_words)
