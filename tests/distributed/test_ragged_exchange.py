"""8-virtual-device tests for RAGGED packed exchange (DESIGN.md §9): dp
workers carrying *different* per-round valid counts k_t round-trip through
the fixed-budget packed all_gather and aggregate correctly — the case the
static wire format of PR 2 could not express.

Every worker's payload buffer has the same static shape (the max_gamma
budget), but each row's count header word carries that worker's own k_t;
receivers decode each gathered row by its own header, so heterogeneous
compression levels need no ragged collective.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.comm import wire as wire_fmt
from repro.core import Compressor, tree_wire_bytes
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
    """Distinct per-worker levels spanning the budget (incl. its edges)."""
    lo = comp.max_gamma / 8.0
    return jnp.linspace(lo, comp.max_gamma, n_workers).astype(jnp.float32)


def _run_workers(gtree, mtree, gammas, comp, eta=0.1):
    """worker_compress_aggregate under a real 8-way manual shard_map with a
    per-worker gamma_t carried in as a sharded (W,) array."""
    mesh = make_mesh((W_WORKERS,), ("data",))
    lead = jax.tree.map(lambda _: P("data"), gtree)
    rep = jax.tree.map(lambda _: P(), gtree)

    def worker(g, m, gam):
        g = jax.tree.map(lambda x: x[0], g)
        m = jax.tree.map(lambda x: x[0], m)
        upd, newm, wire, eff, _ = worker_compress_aggregate(
            g, m, jnp.float32(eta), comp, ("data",), gamma_t=gam[0])
        return (upd, jax.tree.map(lambda x: x[None], newm), wire,
                eff[None])

    f = shard_map(worker, mesh=mesh, in_specs=(lead, lead, P("data")),
                  out_specs=(rep, lead, P(), P("data")),
                  axis_names={"data"}, check_vma=False)
    return jax.jit(f)(gtree, mtree, gammas)


def _simulate(gtree, mtree, gammas, comp, eta):
    """Collective-free reference: per worker, mask to ITS k_t -> encode
    with ITS count -> decode -> scatter; then average across workers."""
    upds, mems = {}, {}
    for name in gtree:
        g_all, m_all = gtree[name], mtree[name]
        n_workers = g_all.shape[0]
        dense_sum = None
        mem_w = []
        for w in range(n_workers):
            g, m = g_all[w], m_all[w]
            g2 = g.reshape(g.shape[0], -1) if g.ndim >= 2 \
                else g.reshape(1, -1)
            m2 = m.reshape(g2.shape)
            L, d = g2.shape
            acc = m2.astype(jnp.float32) + eta * g2.astype(jnp.float32)
            if d < comp.min_compress_size or comp.sparse_k(d) >= d:
                dense = acc
                mem_w.append(jnp.zeros_like(m))
            else:
                if comp.method == "block_topk":
                    vals, idx = block_extract_sparse(acc, comp)
                else:
                    vals, idx = _per_layer_topk(acc, comp.k_for(d))
                spec = wire_fmt.WireSpec.for_row(comp, d)
                count = comp.block_k_t(gammas[w]) if spec.local \
                    else comp.k_t_for(d, gammas[w])
                payload = wire_fmt.encode_rows(
                    vals, idx, spec,
                    counts=jnp.broadcast_to(count, (L,)))
                assert payload.nbytes == L * comp.wire_bytes(d)
                v2, i2 = wire_fmt.decode_rows(payload, spec)
                dense = _scatter_layers(v2, i2, L, d, jnp.float32)
                mem_w.append((acc - dense).reshape(m.shape))
            dense_sum = dense if dense_sum is None else dense_sum + dense
        upds[name] = (dense_sum / n_workers).reshape(g_all.shape[1:])
        mems[name] = jnp.stack(mem_w)
    return upds, mems


@pytest.mark.parametrize("method,value_bits", [
    ("block_topk", 32), ("block_topk", 8), ("topk", 32), ("topk", 16),
])
def test_heterogeneous_kt_exchange_matches_simulation(key, method,
                                                      value_bits):
    """Eight workers, eight different k_t, one fixed-size all_gather: the
    distributed mean/EF state equal the per-worker simulation."""
    comp = Compressor(gamma=0.05, max_gamma=0.05, method=method, block=512,
                      min_compress_size=64, value_bits=value_bits)
    gtree = _worker_tree(key)
    mtree = jax.tree.map(
        lambda x: jax.random.normal(jax.random.fold_in(key, x.size),
                                    x.shape) * 0.1, gtree)
    gammas = _worker_gammas(comp)
    upd, newm, wire, eff = _run_workers(gtree, mtree, gammas, comp)
    upd_ref, mem_ref = _simulate(gtree, mtree, gammas, comp, 0.1)
    for name in gtree:
        np.testing.assert_allclose(np.asarray(upd[name]),
                                   np.asarray(upd_ref[name]), atol=1e-6,
                                   err_msg=name)
        np.testing.assert_allclose(np.asarray(newm[name]),
                                   np.asarray(mem_ref[name]), atol=1e-6,
                                   err_msg=name)
    # the gathered buffer is still the full static budget for everyone ...
    squeezed = jax.tree.map(lambda x: x[0], gtree)
    assert int(wire) == tree_wire_bytes(squeezed, comp)
    # ... but effective bytes are per-worker and strictly increasing with
    # gamma_t (dense small leaves contribute a constant floor)
    eff = np.asarray(eff)
    assert eff.shape == (W_WORKERS,)
    assert np.all(np.diff(eff) >= 0) and eff[0] < eff[-1]
    assert eff[-1] <= float(wire)


def test_heterogeneous_kt_ef_identity(key):
    """Per worker at its own k_t: decode(own payload) + m' == m + eta*g,
    reconstructed from the distributed outputs alone."""
    comp = Compressor(gamma=0.05, max_gamma=0.05, method="block_topk",
                      block=512, min_compress_size=64, value_bits=8)
    gtree = _worker_tree(key)
    mtree = jax.tree.map(lambda x: jnp.zeros_like(x), gtree)
    gammas = _worker_gammas(comp)
    eta = 0.1
    upd, newm, _, _ = _run_workers(gtree, mtree, gammas, comp, eta=eta)
    for name in gtree:
        acc = eta * np.asarray(gtree[name], np.float32)   # m == 0
        own = acc - np.asarray(newm[name], np.float32)    # EF identity
        np.testing.assert_allclose(own.mean(axis=0), np.asarray(upd[name]),
                                   atol=1e-6, err_msg=name)
