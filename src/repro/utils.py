"""Small shared utilities: sharding hints, tree helpers, dtype handling."""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

PyTree = Any

# Symbolic axis names used throughout the model code; resolved against the
# active mesh at trace time.  "dp" = all data-parallel axes present
# (('pod','data') or ('data',)), "tp" = the tensor/model axis.
DP = "dp"
TP = "tp"


def _active_axes() -> tuple[tuple[str, ...], str | None]:
    mesh = jax.sharding.get_abstract_mesh()
    manual = set(mesh.manual_axes)
    names = [a for a in mesh.axis_names if a not in manual]
    dp = tuple(a for a in names if a in ("pod", "data", "replica"))
    tp = "model" if "model" in names else None
    return dp, tp


def hint(x: jax.Array, *spec: Any) -> jax.Array:
    """Sharding constraint with symbolic axes; no-op without a mesh.

    spec entries: None, "dp", "tp", or ("dp","tp"). Axes not present in the
    current (non-manual) mesh are dropped, so the same model code runs on a
    bare CPU, inside a manual-over-data shard_map, or under full-auto pjit.
    """
    dp, tp = _active_axes()
    if not dp and tp is None:
        return x

    def resolve(e):
        if e is None:
            return None
        if isinstance(e, tuple):
            axes: list[str] = []
            for s in e:
                r = resolve(s)
                if r is None:
                    continue
                axes.extend(r if isinstance(r, tuple) else (r,))
            return tuple(axes) or None
        if e == DP:
            return dp or None
        if e == TP:
            return tp
        return e if e in (list(dp) + [tp]) else None

    resolved = tuple(resolve(e) for e in spec)
    if all(e is None for e in resolved):
        return x
    return jax.lax.with_sharding_constraint(x, P(*resolved))


def cast_tree(tree: PyTree, dtype) -> PyTree:
    dt = jnp.dtype(dtype)
    return jax.tree.map(
        lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree)


def tree_size(tree: PyTree) -> int:
    return sum(x.size for x in jax.tree.leaves(tree))


def tree_bytes(tree: PyTree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def split_like(key: jax.Array, tree: PyTree) -> PyTree:
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, list(keys))
