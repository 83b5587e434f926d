"""8-virtual-device parity for the bucketed transport (DESIGN.md §11):
the bucketed exchange must be BIT-EXACT against the per-leaf reference
schedule on a real multi-worker mesh — updates, per-worker EF memory,
and byte counters — including heterogeneous per-worker k_t riding the
ragged count headers, on both (8,) and (4, 2) dp meshes.

Telemetry is pinned to <= 8 ulp instead: its ratios come from f32
reductions (``sum(moments[:, 0])`` etc.) whose inputs are bit-identical
across transports, but XLA does not pin f32 reduction/fusion order across
two different programs, and a handful of independent 1-ulp reduce
differences propagate through the sqrt/divide ratios (measured: up to
4 ulp under heterogeneous k_t)
(see DESIGN.md §11).  Everything a param update or byte counter touches
is elementwise or layout-preserving, hence exactly equal."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.core import Compressor
from repro.core.dcsgd import worker_compress_aggregate
from repro.core.telemetry import CompressionTelemetry
from repro.launch.mesh import make_mesh

W_WORKERS = 8


def _worker_tree(key, n_workers=W_WORKERS):
    ks = jax.random.split(key, 5)
    return {
        "w": jax.random.normal(ks[0], (n_workers, 2, 2048)),   # stacked
        "v": jax.random.normal(ks[1], (n_workers, 3000)),
        "t": jax.random.normal(ks[2], (n_workers, 50)),        # dense
        "u": jax.random.normal(ks[3], (n_workers, 40)),        # dense
        "big": jax.random.normal(ks[4], (n_workers, 70000)),   # 32-bit idx
    }


def _run(gtree, mtree, gammas, comp, transport,
         mesh_shape=(W_WORKERS,), axes=("data",), eta=0.1):
    mesh = make_mesh(mesh_shape, axes)
    lead_axis = axes[0] if len(axes) == 1 else tuple(axes)
    lead = jax.tree.map(lambda _: P(lead_axis), gtree)
    rep = jax.tree.map(lambda _: P(), gtree)
    tel_lead = jax.tree.map(lambda _: P(lead_axis),
                            CompressionTelemetry.init(abstract=True))
    use_gamma = gammas is not None
    if gammas is None:
        gammas = jnp.zeros((W_WORKERS,), jnp.float32)

    def worker(g, m, gam):
        g = jax.tree.map(lambda x: x[0], g)
        m = jax.tree.map(lambda x: x[0], m)
        upd, newm, wire, eff, tel = worker_compress_aggregate(
            g, m, jnp.float32(eta), comp, tuple(axes),
            gamma_t=gam[0] if use_gamma else None, transport=transport)
        return (upd, jax.tree.map(lambda x: x[None], newm), wire,
                eff[None], jax.tree.map(lambda x: x[None], tel))

    f = shard_map(worker, mesh=mesh,
                  in_specs=(lead, lead, P(lead_axis)),
                  out_specs=(rep, lead, P(), P(lead_axis), tel_lead),
                  axis_names=set(axes), check_vma=False)
    return jax.jit(f)(gtree, mtree, gammas)


def _assert_tree_equal(a, b, msg, maxulp=0):
    for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        if maxulp:
            np.testing.assert_array_max_ulp(np.asarray(u), np.asarray(v),
                                            maxulp=maxulp)
        else:
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v),
                                          err_msg=msg)


@pytest.mark.parametrize("method,value_bits,use_kernel", [
    ("block_topk", 8, True), ("block_topk", 32, False),
    ("topk", 16, True), ("topk", 32, True),
])
def test_bucketed_equals_perleaf_8workers(key, method, value_bits,
                                          use_kernel):
    comp = Compressor(gamma=0.05, method=method, block=512,
                      min_compress_size=64, value_bits=value_bits,
                      use_kernel=use_kernel)
    gtree = _worker_tree(key)
    mtree = jax.tree.map(
        lambda x: jax.random.normal(jax.random.fold_in(key, x.size),
                                    x.shape) * 0.1, gtree)
    ref = _run(gtree, mtree, None, comp, "perleaf")
    got = _run(gtree, mtree, None, comp, "bucketed")
    for name, a, b in zip(("updates", "memory", "wire", "eff",
                           "telemetry"), ref, got):
        _assert_tree_equal(a, b, f"{method}/{value_bits}: {name}",
                           maxulp=8 if name == "telemetry" else 0)


@pytest.mark.parametrize("mesh_shape,axes", [
    ((W_WORKERS,), ("data",)), ((4, 2), ("pod", "data")),
])
def test_bucketed_heterogeneous_kt_bit_exact(key, mesh_shape, axes):
    """Eight workers, eight different k_t (the ragged headers inside the
    bucket), on single- and multi-axis dp meshes: every output of the
    bucketed transport is bit-identical to the per-leaf path."""
    comp = Compressor(gamma=0.05, max_gamma=0.05, method="block_topk",
                      block=512, min_compress_size=64, value_bits=8)
    gtree = _worker_tree(key)
    mtree = jax.tree.map(
        lambda x: jax.random.normal(jax.random.fold_in(key, x.size + 1),
                                    x.shape) * 0.1, gtree)
    gammas = jnp.linspace(comp.max_gamma / 8.0, comp.max_gamma,
                          W_WORKERS).astype(jnp.float32)
    ref = _run(gtree, mtree, gammas, comp, "perleaf", mesh_shape, axes)
    got = _run(gtree, mtree, gammas, comp, "bucketed", mesh_shape, axes)
    for name, a, b in zip(("updates", "memory", "wire", "eff",
                           "telemetry"), ref, got):
        _assert_tree_equal(a, b, f"{mesh_shape}: {name}",
                           maxulp=8 if name == "telemetry" else 0)
    # the per-worker effective bytes really are heterogeneous
    eff = np.asarray(got[3]).reshape(-1)
    assert eff[0] < eff[-1]
