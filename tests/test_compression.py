"""Unit tests for the top_k / block-local compression operators."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Compressor, block_threshold, contraction_gamma,
                        sparse_to_dense, topk_select, tree_wire_bytes)
from repro.launch.mesh import make_mesh


def test_topk_selects_largest_magnitudes(key):
    x = jax.random.normal(key, (1000,))
    s = topk_select(x, 10)
    dense = sparse_to_dense(s)
    kept = np.sort(np.abs(np.asarray(x)))[-10:]
    np.testing.assert_allclose(np.sort(np.abs(np.asarray(s.values))), kept,
                               rtol=1e-6)
    # kept values preserved exactly (biased operator, eq. (3))
    nz = np.nonzero(np.asarray(dense))[0]
    assert len(nz) == 10
    np.testing.assert_array_equal(np.asarray(dense)[nz],
                                  np.asarray(x)[nz])


def test_topk_k_greater_than_d(key):
    x = jax.random.normal(key, (5,))
    s = topk_select(x, 10)
    np.testing.assert_array_equal(np.asarray(sparse_to_dense(s)),
                                  np.asarray(x))


def test_small_leaves_uncompressed(key):
    comp = Compressor(gamma=0.01)
    x = jax.random.normal(key, (999,))      # < MIN_COMPRESS_SIZE
    sent, resid = comp.compress_dense(x)
    np.testing.assert_array_equal(np.asarray(sent), np.asarray(x))
    assert float(jnp.sum(jnp.abs(resid))) == 0.0


def test_compress_dense_identity(key):
    """sent + residual == input, exactly (EF bookkeeping)."""
    comp = Compressor(gamma=0.05)
    x = jax.random.normal(key, (4096,))
    sent, resid = comp.compress_dense(x)
    np.testing.assert_allclose(np.asarray(sent + resid), np.asarray(x),
                               atol=1e-7)
    assert int(jnp.sum(sent != 0)) == comp.k_for(4096)


@pytest.mark.parametrize("gamma", [0.01, 0.1, 0.5])
def test_contraction_lemma7(key, gamma):
    """||x - top_k(x)||^2 <= (1-gamma)||x||^2 (paper Lemma 7)."""
    comp = Compressor(gamma=gamma)
    for i in range(5):
        x = jax.random.normal(jax.random.fold_in(key, i), (2048,))
        sent, resid = comp.compress_dense(x)
        lhs = float(jnp.sum(resid ** 2))
        rhs = (1 - comp.k_for(2048) / 2048) * float(jnp.sum(x ** 2))
        assert lhs <= rhs + 1e-5


def test_block_threshold_keeps_about_gamma(key):
    x = jax.random.normal(key, (8192,))
    tau = block_threshold(x, gamma=0.05, block=512)
    kept = int(jnp.sum(jnp.abs(x) >= tau))
    assert 0.05 * 8192 * 0.5 <= kept <= 0.05 * 8192 * 2.5


def test_block_topk_sparse_wire(key):
    comp = Compressor(gamma=0.05, method="block_topk", block=256)
    x = jax.random.normal(key, (4096,))
    s = comp.compress_sparse(x)
    # fixed wire size: k_b per block
    assert s.values.size == (4096 // 256) * max(1, round(0.05 * 256))
    dense = sparse_to_dense(s)
    # selected entries preserved exactly
    nz = np.nonzero(np.asarray(dense))[0]
    np.testing.assert_array_equal(np.asarray(dense)[nz], np.asarray(x)[nz])


def test_wire_bytes_accounting():
    comp = Compressor(gamma=0.01)
    tree = {"a": jnp.zeros((100000,)), "b": jnp.zeros((500,))}
    b = tree_wire_bytes(tree, comp)
    assert b == 1000 * 8 + 500 * 4  # k*(val+idx) + dense small leaf


def _run_worker(tree, comp, eta=0.1):
    """worker_compress_aggregate under a real 1-device shard_map (this also
    exercises the lax.axis_size path of ``_dp_size``)."""
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.core.dcsgd import worker_compress_aggregate

    mesh = make_mesh((1,), ("data",))
    mem = jax.tree.map(lambda x: jnp.zeros_like(x), tree)
    spec = jax.tree.map(lambda _: P(), tree)
    f = shard_map(
        partial(worker_compress_aggregate, comp=comp, dp_axes=("data",)),
        mesh=mesh, in_specs=(spec, spec, P()),
        out_specs=(spec, spec, P(), P(), P()),
        axis_names={"data"}, check_vma=False)
    return jax.jit(f)(tree, mem, jnp.float32(eta))


@pytest.mark.parametrize("method", ["topk", "block_topk"])
@pytest.mark.parametrize("value_bits", [32, 8])
def test_wire_bytes_matches_worker_accounting(key, method, value_bits):
    """Compressor.wire_bytes == the bytes actually counted per step by
    worker_compress_aggregate, for every method/value_bits combination."""
    comp = Compressor(gamma=0.05, method=method, value_bits=value_bits,
                      min_compress_size=64, block=256)
    tree = {"a": jax.random.normal(key, (4096,)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (50,)),
            "c": jax.random.normal(jax.random.fold_in(key, 2), (1000,)),
            # stacked leaves: per-layer blocking/padding (d % block != 0)
            # and a per-layer size below the dense cutoff
            "s": jax.random.normal(jax.random.fold_in(key, 3), (4, 1300)),
            "t": jax.random.normal(jax.random.fold_in(key, 4), (4, 60))}
    _, _, wire, eff, _ = _run_worker(tree, comp)
    assert int(wire) == tree_wire_bytes(tree, comp)


def test_worker_aggregate_kernel_parity(key):
    """The fused-kernel block_topk path == the pure-jnp path (use_kernel
    escape hatch) on the same inputs: identical updates, EF memory, wire."""
    tree = {"w": jax.random.normal(key, (2, 2048)),   # stacked (L=2)
            "v": jax.random.normal(jax.random.fold_in(key, 1), (3000,))}
    def mk(use_kernel):
        return Compressor(gamma=0.05, method="block_topk", block=512,
                          min_compress_size=64, use_kernel=use_kernel)
    up_k, mem_k, wire_k, _, tel_k = _run_worker(tree, mk(True))
    up_j, mem_j, wire_j, _, tel_j = _run_worker(tree, mk(False))
    for a, b in zip(jax.tree.leaves(up_k), jax.tree.leaves(up_j)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)
    for a, b in zip(jax.tree.leaves(mem_k), jax.tree.leaves(mem_j)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)
    assert float(wire_k) == float(wire_j)
    # the fused-kernel telemetry moments equal the jnp path's reductions
    for a, b in zip(jax.tree.leaves(tel_k), jax.tree.leaves(tel_j)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_compress_dense_block_topk_kernel_identity(key):
    """Dense block_topk path (fused kernels by default): exact split and
    per-block keep budget."""
    comp = Compressor(gamma=0.05, method="block_topk", block=1024)
    x = jax.random.normal(key, (4096,))
    sent, resid = comp.compress_dense(x)
    np.testing.assert_array_equal(np.asarray(sent) + np.asarray(resid),
                                  np.asarray(x))
    per_block = np.count_nonzero(np.asarray(sent).reshape(4, 1024), axis=1)
    np.testing.assert_array_equal(per_block, np.full(4, comp.block_k()))
    # escape hatch still works (global-threshold jnp composition)
    sent2, resid2 = Compressor(gamma=0.05, method="block_topk", block=1024,
                               use_kernel=False).compress_dense(x)
    np.testing.assert_allclose(np.asarray(sent2 + resid2), np.asarray(x),
                               atol=1e-7)
    # multi-dim leaf whose last dim is no block multiple: both passes must
    # agree on one flattened block layout (regression)
    y = jax.random.normal(jax.random.fold_in(key, 9), (3, 1500))
    sent3, resid3 = comp.compress_dense(y)
    assert sent3.shape == y.shape
    np.testing.assert_array_equal(np.asarray(sent3) + np.asarray(resid3),
                                  np.asarray(y))


def test_support_mean_bitexact_at_full_support(key):
    """Satellite pin (DESIGN.md §13): when every participant ships every
    coordinate, the support count equals n_participants everywhere and the
    support-weighted mean IS the zero-averaging dense mean — the identical
    division on the identical operands, bit-exact."""
    from repro.fed.aggregate import (scatter_with_support,
                                     support_weighted_mean,
                                     zero_averaged_mean)
    N, L, d = 6, 3, 128
    vals = jax.random.normal(key, (N, L, d))      # nonzero a.s.
    idx = jnp.broadcast_to(jnp.arange(d, dtype=jnp.int32), (N, L, d))
    weights = jnp.asarray([1, 1, 0, 1, 0, 1], jnp.float32)
    total, support = scatter_with_support(vals, idx, weights, L, d)
    n_part = jnp.sum(weights)
    np.testing.assert_array_equal(
        np.asarray(support), np.full((L, d), float(n_part), np.float32))
    np.testing.assert_array_equal(
        np.asarray(support_weighted_mean(total, support)),
        np.asarray(zero_averaged_mean(total, n_part)))
    # and with client-disjoint partial coverage they genuinely differ
    # (the zero-averaging defect exists): client i covers its own stripe,
    # so covered coordinates have support 1 or 2, never n_part
    k = d // 4
    pvals = vals[:, :, :k]
    pidx = (jnp.arange(k, dtype=jnp.int32)[None, None, :] * 4
            + jnp.arange(N, dtype=jnp.int32)[:, None, None] % 4)
    pidx = jnp.broadcast_to(pidx, (N, L, k))
    t2, s2 = scatter_with_support(pvals, pidx, weights, L, d)
    sup = np.asarray(support_weighted_mean(t2, s2))
    zav = np.asarray(zero_averaged_mean(t2, n_part))
    assert np.max(np.abs(sup - zav)) > 0.0


def test_cohort_support_equals_mean_at_budget(key):
    """End-to-end satellite pin: the cohort exchange at gamma=1.0 with
    32-bit values (every client sends every coordinate of every
    compressed leaf) produces bit-identical updates and EF memory under
    aggregation='support' and 'mean'."""
    from repro.fed.clients import cohort_compress_aggregate
    comp = Compressor(gamma=1.0, method="topk", min_compress_size=64,
                      use_kernel=False)
    C = 5
    grads = {"w": jax.random.normal(key, (C, 2, 256)),     # stacked lane
             "b": jax.random.normal(jax.random.fold_in(key, 1), (C, 40))}
    mem = jax.tree.map(jnp.zeros_like, grads)
    part = jnp.asarray([1, 0, 1, 1, 1], jnp.float32)
    out = {agg: cohort_compress_aggregate(
        grads, mem, jnp.float32(0.1), comp, None, part, aggregation=agg)
        for agg in ("support", "mean")}
    for a, b in zip(jax.tree.leaves(out["support"][:2]),
                    jax.tree.leaves(out["mean"][:2])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(out["support"][2]) == float(out["mean"][2])  # wire


def test_contraction_gamma_metric(key):
    x = jax.random.normal(key, (2048,))
    comp = Compressor(gamma=0.1)
    sent, _ = comp.compress_dense(x)
    g = float(contraction_gamma(x, sent))
    assert g >= 0.1  # top-k keeps at least gamma of the energy
