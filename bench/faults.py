"""Faults planted under the timed path, for the control runs and the
tests: each wraps the harness's call of the program's step or its feed
of tokens.  A sound check has to read each of them as not correct."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def unchanged(call):
    """The step returns its state unchanged (its metrics still come)."""
    def step(params, state, batch):
        keep = jax.tree.map(jnp.copy, (params, state))
        _, _, metrics = call(params, state, batch)
        return keep[0], keep[1], metrics
    return step


def half_batch(workers: int):
    """Each worker's second half of rows replaced by its first half: the
    mean is taken over half of the batch."""
    def feed(tokens):
        out = tokens.copy()
        rows = tokens.shape[0] // workers
        h = rows // 2
        for w in range(workers):
            out[w * rows + h:(w + 1) * rows] = tokens[w * rows:w * rows + h]
        return out
    return feed
