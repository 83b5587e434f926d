"""8-virtual-device tests for compression telemetry (DESIGN.md §10): each
dp worker's :class:`CompressionTelemetry` is a function of ITS OWN
(gradient, EF memory, k_t) only — no collective — so the distributed
values must equal a collective-free per-worker simulation even when the
eight workers carry heterogeneous per-round compression levels, and the
pmean'd aggregate must not care how the workers are laid out on the mesh
or permuted across it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.comm import wire as wire_fmt
from repro.core import Compressor
from repro.core.compression import block_extract_sparse
from repro.core.dcsgd import (_per_layer_topk, _scatter_layers,
                              worker_compress_aggregate)
from repro.launch.mesh import make_mesh

W_WORKERS = 8


def _worker_tree(key, n_workers=W_WORKERS):
    ks = jax.random.split(key, 3)
    return {
        "w": jax.random.normal(ks[0], (n_workers, 2, 2048)),  # stacked L=2
        "v": jax.random.normal(ks[1], (n_workers, 3000,)),
        "t": jax.random.normal(ks[2], (n_workers, 50)),       # dense pmean
    }


def _worker_gammas(comp, n_workers=W_WORKERS):
    lo = comp.max_gamma / 8.0
    return jnp.linspace(lo, comp.max_gamma, n_workers).astype(jnp.float32)


def _run_workers(gtree, mtree, gammas, comp, eta=0.1,
                 mesh_shape=(W_WORKERS,), axes=("data",)):
    """Per-worker telemetry (leading worker axis) + the pmean aggregate,
    under a real 8-way manual shard_map with per-worker gamma_t."""
    mesh = make_mesh(mesh_shape, axes)
    lead_axis = axes[0] if len(axes) == 1 else tuple(axes)
    lead = jax.tree.map(lambda _: P(lead_axis), gtree)

    def worker(g, m, gam):
        g = jax.tree.map(lambda x: x[0], g)
        m = jax.tree.map(lambda x: x[0], m)
        *_, tel = worker_compress_aggregate(
            g, m, jnp.float32(eta), comp, tuple(axes), gamma_t=gam[0])
        agg = tel.pmean(tuple(axes))
        return jax.tree.map(lambda x: x[None], tel), agg

    f = shard_map(worker, mesh=mesh,
                  in_specs=(lead, lead, P(lead_axis)),
                  out_specs=(P(lead_axis), P()), axis_names=set(axes),
                  check_vma=False)
    per_worker, agg = jax.jit(f)(gtree, mtree, gammas)
    return (jax.tree.map(np.asarray, per_worker),
            jax.tree.map(np.asarray, agg))


def _simulate_telemetry(gtree, mtree, gammas, comp, eta):
    """Collective-free float64 reference: per worker, redo the leaf loop
    (encode at its OWN k_t -> decode -> residual) and form the four
    ratios from scratch — independent of core/telemetry.py's fused-sum
    bookkeeping."""
    n_workers = next(iter(gtree.values())).shape[0]
    out = {"ef_backlog": [], "cosine": [], "decode_error": [],
           "eff_gamma": []}
    for w in range(n_workers):
        g_sq = acc_sq = resid_sq = own_sq = dot = 0.0
        for name in gtree:
            g = np.asarray(gtree[name][w], np.float64)
            m = np.asarray(mtree[name][w], np.float64)
            g2 = g.reshape(g.shape[0], -1) if g.ndim >= 2 \
                else g.reshape(1, -1)
            m2 = m.reshape(g2.shape)
            L, d = g2.shape
            acc = m2 + eta * g2
            g_sq += float(np.sum(g2 * g2))
            acc_sq += float(np.sum(acc * acc))
            if d < comp.min_compress_size or comp.sparse_k(d) >= d:
                own = acc                       # ships dense: decode == acc
            else:
                accf = jnp.asarray(acc, jnp.float32)
                if comp.method == "block_topk":
                    vals, idx = block_extract_sparse(accf, comp)
                else:
                    vals, idx = _per_layer_topk(accf, comp.k_for(d))
                spec = wire_fmt.WireSpec.for_row(comp, d)
                count = comp.block_k_t(gammas[w]) if spec.local \
                    else comp.k_t_for(d, gammas[w])
                payload = wire_fmt.encode_rows(
                    vals, idx, spec, counts=jnp.broadcast_to(count, (L,)))
                v2, i2 = wire_fmt.decode_rows(payload, spec)
                own = np.asarray(
                    _scatter_layers(v2, i2, L, d, jnp.float32), np.float64)
            resid = acc - own
            resid_sq += float(np.sum(resid * resid))
            own_sq += float(np.sum(own * own))
            dot += float(np.sum(own * g2))
        out["ef_backlog"].append(np.sqrt(resid_sq / g_sq))
        out["cosine"].append(dot / np.sqrt(own_sq * g_sq))
        out["decode_error"].append(np.sqrt(resid_sq / acc_sq))
        out["eff_gamma"].append(1.0 - resid_sq / acc_sq)
    return {k: np.asarray(v) for k, v in out.items()}


@pytest.mark.parametrize("method,value_bits", [
    ("block_topk", 32), ("block_topk", 8), ("topk", 32),
])
def test_per_worker_telemetry_matches_simulation(key, method, value_bits):
    """Eight workers, eight different k_t: every worker's telemetry equals
    the collective-free reference computed from its own leaves alone."""
    comp = Compressor(gamma=0.05, max_gamma=0.05, method=method, block=512,
                      min_compress_size=64, value_bits=value_bits)
    gtree = _worker_tree(key)
    mtree = jax.tree.map(
        lambda x: jax.random.normal(jax.random.fold_in(key, x.size),
                                    x.shape) * 0.1, gtree)
    gammas = _worker_gammas(comp)
    tel, agg = _run_workers(gtree, mtree, gammas, comp)
    ref = _simulate_telemetry(gtree, mtree, gammas, comp, 0.1)
    for field in ref:
        got = np.asarray(getattr(tel, field))
        assert got.shape == (W_WORKERS,)
        np.testing.assert_allclose(got, ref[field], rtol=1e-4, atol=1e-6,
                                   err_msg=field)
        # the aggregate is the plain worker mean
        np.testing.assert_allclose(np.asarray(getattr(agg, field)),
                                   ref[field].mean(), rtol=1e-4,
                                   atol=1e-6, err_msg=field)
    # heterogeneous k_t leave a visible footprint: the lowest-gamma worker
    # carries strictly more backlog than the full-budget one
    assert tel.ef_backlog[0] > tel.ef_backlog[-1]


def test_aggregate_permutation_invariant_across_meshes(key):
    """The psum'd aggregate must not depend on (a) how the 8 workers fold
    onto the dp mesh axes — (8,) vs (4, 2) — or (b) the order the workers
    are laid out in; per-worker telemetry must permute along."""
    comp = Compressor(gamma=0.05, max_gamma=0.05, method="block_topk",
                      block=512, min_compress_size=64, value_bits=8)
    gtree = _worker_tree(key)
    mtree = jax.tree.map(lambda x: jnp.zeros_like(x), gtree)
    gammas = _worker_gammas(comp)

    tel_1d, agg_1d = _run_workers(gtree, mtree, gammas, comp)
    tel_2d, agg_2d = _run_workers(gtree, mtree, gammas, comp,
                                  mesh_shape=(4, 2), axes=("pod", "data"))
    # same per-worker values on both mesh layouts...
    for a, b in zip(jax.tree.leaves(tel_1d), jax.tree.leaves(tel_2d)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=0)
    # ... and mesh-layout-invariant aggregates (reduction order may differ)
    for a, b in zip(jax.tree.leaves(agg_1d), jax.tree.leaves(agg_2d)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)

    perm = np.asarray([3, 0, 7, 5, 1, 6, 2, 4])
    ptree = jax.tree.map(lambda x: x[perm], gtree)
    pmem = jax.tree.map(lambda x: x[perm], mtree)
    tel_p, agg_p = _run_workers(ptree, pmem, gammas[jnp.asarray(perm)], comp)
    for a, b in zip(jax.tree.leaves(tel_p), jax.tree.leaves(tel_1d)):
        np.testing.assert_allclose(a, b[perm], rtol=1e-6, atol=0)
    for a, b in zip(jax.tree.leaves(agg_p), jax.tree.leaves(agg_1d)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
