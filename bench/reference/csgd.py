"""Plain reference of one CSGD-ASSS training step over W workers.

It imports nothing of the program.  The step follows the paper's
Algorithm 3 with Algorithm 1's scaled Armijo search (arXiv:2207.10046),
as the traffic's optimizer settings state them.  Each worker w, on its
own rows:

    g_w      = grad f_w(x)
    alpha_w  : first of  amax, amax*rho, amax*rho^2, ...  (amax = clip(omega
               * alpha_prev_w)) with  f_w(x - alpha g_w) <= f_w(x) - sigma
               alpha ||g_w||^2,  at most ``max_backtracks`` evaluations
    acc_w    = m_w + a * alpha_w * g_w
    sent_w   = per layer row, per ``block``-wide block, the k_b = round(gamma
               * block) entries of largest |acc_w|;  rows under 1000
               entries (or whose blocks would ship as much) are sent whole
    m_w     <- acc_w - sent_w
    x       <- x - mean_w sent_w

The parameter write is in the parameters' own dtype; everything else is
float32.  Leaves under ``blocks`` carry a leading layer axis and are
compressed per layer.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Optimizer:
    gamma: float = 0.01
    block: int = 1024
    value_bits: int = 32
    min_compress: int = 1000
    sigma: float = 0.1
    rho: float = 0.8
    omega: float = 1.2
    a_scale: float = 0.3
    alpha0: float = 0.1
    max_backtracks: int = 40
    alpha_min: float = 1e-8

    @property
    def k_b(self) -> int:
        return max(1, int(round(self.gamma * self.block)))

    def rows(self, path: str, shape) -> tuple[int, int]:
        """(L, d): stacked leaves are compressed per layer row."""
        if path.startswith("blocks") and len(shape) >= 2:
            d = 1
            for n in shape[1:]:
                d *= n
            return shape[0], d
        d = 1
        for n in shape:
            d *= n
        return 1, d

    def sent_whole(self, d: int) -> bool:
        nb = -(-d // self.block)
        return d < self.min_compress or nb * self.k_b >= d

    def wire_bytes(self, shapes: dict) -> int:
        """Bytes one worker puts on the wire per step: whole rows as float32;
        compressed rows as 16-bit block-local indices and ``value_bits``
        values, each section padded to 32-bit words, a scale word for
        values of 8 bits or fewer."""
        total = 0
        for path, shape in shapes.items():
            L, d = self.rows(path, shape)
            if self.sent_whole(d):
                total += L * d * 4
                continue
            k = -(-d // self.block) * self.k_b
            words = -(-k * 16 // 32) + -(-k * self.value_bits // 32) \
                + (1 if self.value_bits <= 8 else 0)
            total += L * words * 4
        return total


def path_names(tree) -> list[str]:
    return [jax.tree_util.keystr(p, simple=True, separator=".")
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _compress(path: str, acc: jax.Array, opt: Optimizer):
    L, d = opt.rows(path, acc.shape)
    if opt.sent_whole(d):
        return acc, jnp.zeros_like(acc)
    x = acc.reshape(L, d)
    pad = (-d) % opt.block
    blocks = jnp.pad(x, ((0, 0), (0, pad))).reshape(L, -1, opt.block)
    _, idx = jax.lax.top_k(jnp.abs(blocks), opt.k_b)
    keep = jnp.zeros(blocks.shape, bool)
    keep = jnp.put_along_axis(keep, idx, True, axis=-1, inplace=False)
    sent = jnp.where(keep, blocks, 0.0).reshape(L, -1)[:, :d]
    sent = sent.reshape(acc.shape)
    return sent, acc - sent


def worker(loss_fn, opt: Optimizer, params, memory, alpha_prev, tokens):
    """One worker's half of a step: (loss, alpha, n_evals, sent, memory')."""
    f0, g = jax.value_and_grad(loss_fn)(params, tokens)
    g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
    gsq = sum(jnp.sum(x * x) for x in jax.tree.leaves(g))
    amax = jnp.clip(opt.omega * alpha_prev, opt.alpha_min, 1e6)

    def trial(alpha):
        cand = jax.tree.map(lambda p, d: p - alpha * d.astype(p.dtype),
                            params, g)
        return loss_fn(cand, tokens).astype(jnp.float32)

    def cond(s):
        alpha, f, n = s
        ok = jnp.isfinite(f) & (f <= f0 - opt.sigma * alpha * gsq)
        return ~ok & (n < opt.max_backtracks) & (alpha > opt.alpha_min)

    def body(s):
        alpha, _, n = s
        alpha = alpha * opt.rho
        return alpha, trial(alpha), n + 1

    alpha, _, n = jax.lax.while_loop(cond, body,
                                     (amax, trial(amax), jnp.int32(1)))
    eta = opt.a_scale * alpha
    names = path_names(g)
    flat_g, tdef = jax.tree.flatten(g)
    flat_m = tdef.flatten_up_to(memory)
    out = [_compress(p, m + eta * x, opt)
           for p, x, m in zip(names, flat_g, flat_m)]
    sent = tdef.unflatten([s for s, _ in out])
    mem = tdef.unflatten([r for _, r in out])
    return f0.astype(jnp.float32), alpha, n, sent, mem


def apply(params, sents: list):
    """x <- x - mean_w sent_w, written in the parameters' dtype."""
    mean = jax.tree.map(lambda *s: sum(s) / len(s), *sents)
    return jax.tree.map(
        lambda p, u: (p.astype(jnp.float32) - u).astype(p.dtype),
        params, mean)
