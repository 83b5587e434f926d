"""Gradient compression operators.

The paper (§II-C) uses the biased per-layer ``top_k`` operator with memory
feedback [Aji & Heafield '17; Stich et al. '18].  Faithful details:

* compression is applied **per layer** (per pytree leaf);
* leaves with fewer than ``min_compress_size`` (=1000, §IV-A) parameters are
  transmitted uncompressed;
* ``gamma = k/d`` is the compression *ratio*; ``k = max(1, round(gamma*d))``.

Two selection strategies are provided:

* ``topk``      — exact per-leaf magnitude top-k (``jax.lax.top_k``), faithful.
* ``block_topk``— TPU-native two-pass block-local threshold selection (the
                  Pallas-kernel path, see ``repro/kernels/ef_topk.py``); k is
                  achieved in expectation, the EF identity stays exact.

Both return a :class:`Sparse` pair (values, indices) — this is what travels
over the wire in the distributed algorithm, so communicated bytes are
``k * (bytes(val) + bytes(idx))`` instead of ``d * bytes(val)``.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

PyTree = Any

#: Leaves smaller than this are not compressed (paper §IV-A, following [8]).
MIN_COMPRESS_SIZE = 1000

#: Integer quantization range per sub-byte/byte value width (symmetric).
QMAX = {8: 127.0, 4: 7.0}


def quant_scale(vals: jax.Array, qmax: float) -> jax.Array:
    """Per-row absmax quantization scale — THE scale formula, shared
    bit-for-bit by :meth:`Compressor.quantize_values` and the packed wire
    codec (repro/comm/wire.py) so dequantized values agree exactly."""
    return jnp.max(jnp.abs(vals), axis=-1, keepdims=True) / qmax + 1e-30


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Sparse:
    """A compressed tensor: flat values + flat int32 indices into the leaf."""

    values: jax.Array   # (k,) or (workers, k) after all_gather
    indices: jax.Array  # (k,) int32
    shape: tuple[int, ...] = dataclasses.field(metadata=dict(static=True))

    @property
    def nbytes_wire(self) -> int:
        return self.values.size * self.values.dtype.itemsize + \
            self.indices.size * self.indices.dtype.itemsize


def leaf_k(d: int, gamma: float) -> int:
    """Number of kept components for a leaf of size d at ratio gamma."""
    if d < MIN_COMPRESS_SIZE:
        return d
    return max(1, int(round(gamma * d)))


# ---------------------------------------------------------------------------
# exact per-leaf top_k (paper-faithful)
# ---------------------------------------------------------------------------

def topk_select(x: jax.Array, k: int) -> Sparse:
    """Exact magnitude top-k of a tensor, flattened. Biased operator (3)."""
    flat = x.reshape(-1)
    if k >= flat.size:
        return Sparse(flat, jnp.arange(flat.size, dtype=jnp.int32), x.shape)
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    idx = idx.astype(jnp.int32)
    return Sparse(flat[idx], idx, x.shape)


def sparse_to_dense(s: Sparse, dtype=None) -> jax.Array:
    """Scatter a Sparse (possibly (workers,k) stacked) back to dense."""
    d = 1
    for n in s.shape:
        d *= n
    vals = s.values.reshape(-1)
    idx = s.indices.reshape(-1)
    dense = jnp.zeros((d,), dtype or vals.dtype).at[idx].add(vals)
    return dense.reshape(s.shape)


# ---------------------------------------------------------------------------
# block-local threshold selection (TPU-native path; jnp reference impl —
# the Pallas kernel in repro/kernels/ef_topk.py implements the same math)
# ---------------------------------------------------------------------------

def block_threshold(x: jax.Array, gamma: float, block: int = 1024) -> jax.Array:
    """Per-tensor magnitude threshold t such that ~gamma*d entries survive.

    Two-pass scheme: block-local exact top-k_b (k_b = ceil(gamma*block)) then
    the global threshold is the k-th largest among the kept candidates.  The
    result keeps between gamma*d and min(1, 2*gamma)*d entries (each block
    contributes at most k_b, at least the global top-k survive).
    """
    flat = jnp.abs(x.reshape(-1))
    d = flat.size
    pad = (-d) % block
    flat = jnp.pad(flat, (0, pad), constant_values=0.0)
    blocks = flat.reshape(-1, block)
    k_b = max(1, int(-(-gamma * block // 1)))  # ceil
    cand, _ = jax.lax.top_k(blocks, k_b)       # (nb, k_b) block-local top
    cand = cand.reshape(-1)
    k = leaf_k(d, gamma)
    k = min(k, cand.size)
    kth, _ = jax.lax.top_k(cand, k)
    return kth[-1]


def threshold_select(x: jax.Array, tau: jax.Array) -> jax.Array:
    """Dense masked selection |x| >= tau (keeps layout; no gather)."""
    return jnp.where(jnp.abs(x) >= tau, x, jnp.zeros_like(x))


def block_extract_sparse(x2d: jax.Array, comp: "Compressor"):
    """Wire pairs via exact per-block top-k_b — THE block extraction of
    the jnp block_topk paths (compress_sparse, compress_leaf).  The fused
    kernel path takes the same pairs from its pass-1 kernel
    (``kernels.ops.EFSplit``), bit-identical to this applied to ``sent``.

    x2d: (L, d) per-layer rows; blocks never span layers.  Returns
    (vals, idx), each (L, nb*k_b), idx flat into [0, d) (clamped — padding
    positions carry zero values).
    """
    L, d = x2d.shape
    block = comp.block
    pad = (-d) % block
    blocks = jnp.pad(x2d, ((0, 0), (0, pad))).reshape(L, -1, block)
    nb = blocks.shape[1]
    k_b = comp.block_k()
    _, bidx = jax.lax.top_k(jnp.abs(blocks), k_b)          # (L, nb, k_b)
    base = (jnp.arange(nb, dtype=jnp.int32) * block)[None, :, None]
    idx = (bidx.astype(jnp.int32) + base).reshape(L, -1)
    idx = jnp.minimum(idx, d - 1)
    vals = jnp.take_along_axis(blocks, bidx, axis=2).reshape(L, -1)
    return vals, idx


# ---------------------------------------------------------------------------
# Compressor objects
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Compressor:
    """Per-leaf compression policy. ``gamma`` is the paper's k/d.

    ``value_bits`` (32|16|8|4, beyond-paper): quantize the transmitted
    top-k *values* on the wire (absmax-scaled); the error-feedback residual
    is computed against the quantized values, so the EF telescoping
    identity is preserved exactly and quantization error is recycled like
    any other compression error.  Transmitted bytes follow the bit-packed
    wire format (DESIGN.md §8, repro/comm/wire.py): per-row header +
    bit-packed index and value sections, so at 8 bits an entry costs
    1 B of value + 2 B of block-local index instead of 4+4 B.

    ``use_kernel``: route the ``block_topk`` hot path through the fused
    Pallas two-pass kernels (repro/kernels/ef_topk.py, dispatched by
    repro/kernels/dispatch.py).  Escape hatch: False falls back to the
    pure-jnp composition.

    ``max_gamma`` (adaptive compression, DESIGN.md §9): when > 0 the wire
    geometry — payload buffers, WireSpec, ``wire_bytes`` — is sized for
    ``max_gamma`` (the static *budget* ``k_max``), while the compression
    level actually applied each round is a **traced** per-round ``gamma_t``
    passed to :meth:`compress_dense` / ``dcsgd.worker_compress_aggregate``.
    Entries ranked beyond the per-round ``k_t <= k_max`` are masked to zero
    and their wire fields zeroed behind a valid-count header word, so the
    payload is ragged-in-content inside a fixed buffer and the static
    ``wire_bytes`` invariant survives as an upper bound; the runtime
    ``effective_wire_bytes`` metric counts what a ragged collective would
    ship.  ``gamma`` stays the *initial* (and non-adaptive) ratio.
    """

    gamma: float = 0.01
    method: str = "topk"            # topk | block_topk | none
    block: int = 1024
    min_compress_size: int = MIN_COMPRESS_SIZE
    value_bits: int = 32
    use_kernel: bool = True
    max_gamma: float = 0.0          # > 0: adaptive budget (DESIGN.md §9)

    @property
    def adaptive(self) -> bool:
        """True when the wire carries per-round valid counts (ragged)."""
        return self.max_gamma > 0.0

    @property
    def geometry_gamma(self) -> float:
        """The gamma that sizes every static buffer/payload (the budget)."""
        return self.max_gamma if self.adaptive else self.gamma

    def k_for(self, d: int) -> int:
        if self.method == "none" or d < self.min_compress_size:
            return d
        return max(1, int(round(self.geometry_gamma * d)))

    def block_k(self) -> int:
        """k_b: entries kept per ``block``-wide block (block_topk)."""
        return max(1, int(round(self.geometry_gamma * self.block)))

    # -- per-round (traced) selection counts, clamped into the budget -------
    def k_t_for(self, d: int, gamma_t: jax.Array) -> jax.Array:
        """Traced per-round k_t for a flat row of size d: round(gamma_t*d)
        clamped into [1, k_max] so the static buffer always fits."""
        k_max = self.k_for(d)
        return jnp.clip(jnp.round(jnp.asarray(gamma_t, jnp.float32) * d),
                        1, k_max).astype(jnp.int32)

    def block_k_t(self, gamma_t: jax.Array) -> jax.Array:
        """Traced per-round per-block valid count, in [1, block_k()]."""
        return jnp.clip(
            jnp.round(jnp.asarray(gamma_t, jnp.float32) * self.block),
            1, self.block_k()).astype(jnp.int32)

    def sparse_k(self, d: int) -> int:
        """Actual number of (value, index) pairs on the wire for a leaf
        of size d — ``block_topk`` ships exactly k_b per (padded) block."""
        k = self.k_for(d)
        if k == d:
            return d
        if self.method == "block_topk":
            nb = -(-d // self.block)
            return nb * self.block_k()
        return k

    def ships_dense(self, d: int) -> bool:
        """True when a row of size d ships uncompressed (pmean, no packed
        payload): no compression method, below the §IV-A size cutoff, or
        block padding pushing the wire entry count past d.  THE
        dense-vs-compressed predicate — shared by both transports of
        ``worker_compress_aggregate`` and ``comm.bucket.build_bucket_plan``
        so the per-leaf and bucketed schedules can never classify a leaf
        differently."""
        return (self.method == "none" or d < self.min_compress_size
                or self.sparse_k(d) >= d)

    def quantize_values(self, vals: jax.Array) -> jax.Array:
        """Simulate wire quantization (returns dequantized f32 values —
        what the receivers reconstruct). Scale is per (leading dims) row.

        Bit-for-bit identical to an encode->decode round trip through the
        packed wire codec (repro/comm/wire.py), which shares this math.
        """
        if self.value_bits >= 32:
            return vals
        if self.value_bits == 16:
            return vals.astype(jnp.bfloat16).astype(vals.dtype)
        qmax = QMAX[self.value_bits]
        scale = quant_scale(vals, qmax)
        q = jnp.clip(jnp.round(vals / scale), -qmax, qmax)
        return (q * scale).astype(vals.dtype)

    @property
    def value_bytes(self) -> int:
        """Nominal per-entry value bytes, rounded up (4-bit packs two
        entries per byte; exact accounting lives in :meth:`wire_bytes`)."""
        return {32: 4, 16: 2, 8: 1, 4: 1}[self.value_bits]

    # -- dense-in dense-out (single-node semantics; update rule (6)) --------
    def compress_dense(self, x: jax.Array,
                       gamma_t: jax.Array | None = None
                       ) -> tuple[jax.Array, jax.Array]:
        """Returns (top_k(x) as dense, residual x - top_k(x)).

        ``gamma_t`` (adaptive compressors only): traced per-round ratio;
        selection runs at the static ``k_max`` budget and entries ranked
        beyond ``k_t = round(gamma_t * d)`` are masked into the residual.
        """
        d = x.size
        if self.method == "none" or d < self.min_compress_size:
            return x, jnp.zeros_like(x)
        if gamma_t is not None and self.adaptive:
            return self._compress_dense_ragged(x, gamma_t)
        if self.method == "topk":
            s = topk_select(x, self.k_for(d))
            if self.value_bits < 32:
                s = Sparse(self.quantize_values(s.values), s.indices,
                           s.shape)
            dense = sparse_to_dense(s, x.dtype)
        elif self.method == "block_topk":
            if self.use_kernel:
                # fused Pallas path: pass-1 per-block stats, pass-2 fused
                # split (1 read + 2 writes) — see repro/kernels/ef_topk.py.
                # Both passes see the same flattened block layout.
                from repro.kernels import ops
                flat = x.reshape(-1)
                tau = ops.block_topk_threshold(flat, self.block_k(),
                                               self.block)
                dense, resid = ops.threshold_split_blocks(
                    flat, tau.reshape(-1, 1), self.block)
                return dense.reshape(x.shape), resid.reshape(x.shape)
            tau = block_threshold(x, self.gamma, self.block)
            dense = threshold_select(x, tau)
        else:
            raise ValueError(f"unknown compression method {self.method!r}")
        return dense, x - dense

    def _compress_dense_ragged(self, x: jax.Array, gamma_t: jax.Array
                               ) -> tuple[jax.Array, jax.Array]:
        """Budget-shaped selection masked to the traced per-round count.

        Both methods produce magnitude-sorted candidates (``lax.top_k``
        sorts descending), so "the first k_t" IS exact top-k_t (flat rows)
        / per-block top-k_b_t (block rows) — the mask only zeroes values,
        never moves them, and masked entries fall into the residual.
        """
        d = x.size
        if self.method == "topk":
            # lax.top_k directly (not topk_select): its k == d early path
            # returns UNSORTED values, and the prefix mask needs the
            # magnitude-descending order.
            flat = x.reshape(-1)
            _, idx = jax.lax.top_k(jnp.abs(flat), self.k_for(d))
            idx = idx.astype(jnp.int32)
            k_t = self.k_t_for(d, gamma_t)
            pos = jnp.arange(idx.shape[-1], dtype=jnp.int32)
            vals = jnp.where(pos < k_t, flat[idx], 0.0)
            if self.value_bits < 32:
                vals = self.quantize_values(vals)       # scale sees valid only
            dense = sparse_to_dense(Sparse(vals, idx, x.shape), x.dtype)
        elif self.method == "block_topk":
            vals, idx = block_extract_sparse(x.reshape(1, -1), self)
            k_b = self.block_k()
            pos = jnp.arange(vals.shape[-1], dtype=jnp.int32)
            vals = jnp.where(pos % k_b < self.block_k_t(gamma_t), vals, 0.0)
            dense = jnp.zeros((d,), jnp.float32).at[idx.reshape(-1)].add(
                vals.reshape(-1)).astype(x.dtype).reshape(x.shape)
        else:
            raise ValueError(f"unknown compression method {self.method!r}")
        return dense, x - dense

    # -- sparse wire format (distributed semantics; Algorithm 3) ------------
    def compress_sparse(self, x: jax.Array) -> Sparse:
        d = x.size
        if self.method in ("none",) or d < self.min_compress_size:
            flat = x.reshape(-1)
            return Sparse(flat, jnp.arange(d, dtype=jnp.int32), x.shape)
        if self.method == "block_topk":
            # block-local exact top-k_b: hardware-aligned, fixed wire size.
            vals, idx = block_extract_sparse(x.reshape(1, -1), self)
            return Sparse(vals.reshape(-1), idx.reshape(-1), x.shape)
        return topk_select(x, self.k_for(d))

    def wire_bytes(self, x_size: int, itemsize: int = 4) -> int:
        """Bytes on the wire for one leaf row — the LITERAL byte length of
        the ``uint32`` payload that ``worker_compress_aggregate`` builds and
        all-gathers over the dp mesh axes (asserted there at trace time).

        Compressed rows follow the bit-packed wire format (DESIGN.md §8):
        per-row header word (sub-byte value quantization only) + bit-packed
        index section (16-bit block-local indices for ``block_topk``) +
        bit-packed value section.  Uncompressed leaves ship dense.
        """
        k = self.sparse_k(x_size)
        # uncompressed leaves ship dense — including rows where block
        # padding pushes nb*k_b past d at large gamma (dcsgd pmean branch)
        if k >= x_size:
            return x_size * itemsize
        from repro.comm.wire import WireSpec  # local import: no cycle
        return WireSpec.for_row(self, x_size).row_bytes

    def leaf_wire_bytes(self, shape: tuple[int, ...],
                        itemsize: int = 4) -> int:
        """Wire bytes for one leaf, mirroring ``worker_compress_aggregate``
        exactly: leaves with ndim >= 2 are scan-stacked and compressed
        *per layer* (the dense/sparse cutoff and the block padding both
        apply to the per-layer size d, not the whole leaf)."""
        L, d = _leaf_geometry(shape)
        return L * self.wire_bytes(d, itemsize)


def _leaf_geometry(shape: tuple[int, ...]) -> tuple[int, int]:
    """(L, d) per-layer view of a leaf shape — THE stacked-leaf convention
    of ``worker_compress_aggregate`` (ndim >= 2: leading axis = layers),
    shared by the static and the effective byte accounting."""
    if len(shape) >= 2:
        L = shape[0]
        d = 1
        for n in shape[1:]:
            d *= n
        return L, d
    return 1, (shape[0] if shape else 1)


def tree_wire_bytes(tree: PyTree, comp: Compressor, itemsize: int = 4) -> int:
    """Total communicated bytes per worker per step for a gradient pytree."""
    return sum(comp.leaf_wire_bytes(leaf.shape, itemsize)
               for leaf in jax.tree.leaves(tree))


def leaf_effective_wire_bytes(comp: Compressor, shape: tuple[int, ...],
                              gamma_t: jax.Array,
                              itemsize: int = 4) -> jax.Array:
    """Traced per-round *useful* wire bytes for one leaf at ``gamma_t`` —
    what a truly ragged collective would ship: the header plus only the
    ``k_t`` valid (index, value) fields, bit-packed (DESIGN.md §9).  For
    non-adaptive compressors this equals :meth:`Compressor.leaf_wire_bytes`
    exactly; dense-shipping leaves cost their dense bytes either way.
    """
    L, d = _leaf_geometry(shape)
    if comp.sparse_k(d) >= d:
        return jnp.float32(L * d * itemsize)
    from repro.comm.wire import WireSpec  # local import: no cycle
    spec = WireSpec.for_row(comp, d)
    if not spec.ragged:
        return jnp.float32(L * spec.row_bytes)
    count = comp.block_k_t(gamma_t) if spec.local \
        else comp.k_t_for(d, gamma_t)
    return jnp.float32(L) * spec.effective_row_bytes(count)


def tree_effective_wire_bytes(tree: PyTree, comp: Compressor,
                              gamma_t: jax.Array,
                              itemsize: int = 4) -> jax.Array:
    """Traced per-round effective bytes for a gradient pytree (the runtime
    counterpart of :func:`tree_wire_bytes`, which stays the static upper
    bound the payload buffers are sized for)."""
    return sum(leaf_effective_wire_bytes(comp, leaf.shape, gamma_t, itemsize)
               for leaf in jax.tree.leaves(tree))


def contraction_gamma(x: jax.Array, compressed: jax.Array) -> jax.Array:
    """Empirical 1 - ||x - C(x)||^2/||x||^2 (Lemma 7 effective gamma)."""
    num = jnp.sum((x - compressed) ** 2)
    den = jnp.sum(x ** 2) + 1e-30
    return 1.0 - num / den
