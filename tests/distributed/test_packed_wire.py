"""8-virtual-device tests for the bit-packed wire exchange (DESIGN.md §8).

The acceptance contract: ``Compressor.wire_bytes`` equals the LITERAL byte
length of the uint32 payload that ``worker_compress_aggregate`` all-gathers
over the dp mesh axes, and the distributed mean/EF state equal a
per-worker single-device simulation of the same encode->gather->decode
pipeline, for every supported ``value_bits``.
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
    """Per-worker distinct gradients: leaves carry a leading worker axis."""
    ks = jax.random.split(key, 4)
    return {
        "w": jax.random.normal(ks[0], (n_workers, 2, 2048)),  # stacked L=2
        "v": jax.random.normal(ks[1], (n_workers, 3000)),
        # below the compression cutoff: ships dense via pmean
        "t": jax.random.normal(ks[2], (n_workers, 50)),
    }


def _run_workers(gtree, mtree, comp, eta=0.1, mesh_shape=(W_WORKERS,),
                 axes=("data",)):
    """worker_compress_aggregate under a real 8-way manual shard_map."""
    mesh = make_mesh(mesh_shape, axes)
    lead_axis = axes[0] if len(axes) == 1 else tuple(axes)
    lead = jax.tree.map(lambda _: P(lead_axis), gtree)
    rep = jax.tree.map(lambda _: P(), gtree)

    def worker(g, m):
        g = jax.tree.map(lambda x: x[0], g)
        m = jax.tree.map(lambda x: x[0], m)
        upd, newm, wire, eff, _ = worker_compress_aggregate(
            g, m, jnp.float32(eta), comp, tuple(axes))
        return upd, jax.tree.map(lambda x: x[None], newm), wire, eff

    f = shard_map(worker, mesh=mesh, in_specs=(lead, lead),
                  out_specs=(rep, lead, P(), P()), axis_names=set(axes),
                  check_vma=False)
    return jax.jit(f)(gtree, mtree)


def _simulate(gtree, mtree, comp, eta):
    """Single-device reference: per worker, compress -> encode -> decode ->
    scatter; then average.  Uses the same library codec, NO collectives."""
    upds, mems = {}, {}
    for name in gtree:
        g_all, m_all = gtree[name], mtree[name]
        n_workers = g_all.shape[0]
        dense_sum = None
        mem_w = []
        for w in range(n_workers):
            g, m = g_all[w], m_all[w]
            g2 = g.reshape(g.shape[0], -1) if g.ndim >= 2 else g.reshape(1, -1)
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
                payload = wire_fmt.encode_rows(vals, idx, spec)
                # the acceptance criterion, on the actual buffer:
                assert payload.nbytes == L * comp.wire_bytes(d)
                assert payload.nbytes == L * spec.row_bytes
                v2, i2 = wire_fmt.decode_rows(payload, spec)
                dense = _scatter_layers(v2, i2, L, d, jnp.float32)
                mem_w.append((acc - dense).reshape(m.shape))
            dense_sum = dense if dense_sum is None else dense_sum + dense
        upds[name] = (dense_sum / n_workers).reshape(g_all.shape[1:])
        mems[name] = jnp.stack(mem_w)
    return upds, mems


@pytest.mark.parametrize("method,value_bits", [
    ("block_topk", 32), ("block_topk", 8), ("block_topk", 4),
    ("topk", 32), ("topk", 16),
])
def test_packed_exchange_matches_simulation(key, method, value_bits):
    comp = Compressor(gamma=0.05, method=method, block=512,
                      min_compress_size=64, value_bits=value_bits)
    gtree = _worker_tree(key)
    mtree = jax.tree.map(
        lambda x: jax.random.normal(jax.random.fold_in(key, x.size),
                                    x.shape) * 0.1, gtree)
    upd, newm, wire, _ = _run_workers(gtree, mtree, comp)
    upd_ref, mem_ref = _simulate(gtree, mtree, comp, 0.1)

    squeezed = jax.tree.map(lambda x: x[0], gtree)
    assert int(wire) == tree_wire_bytes(squeezed, comp)

    for name in gtree:
        np.testing.assert_allclose(np.asarray(upd[name]),
                                   np.asarray(upd_ref[name]), atol=1e-6,
                                   err_msg=name)
        np.testing.assert_allclose(np.asarray(newm[name]),
                                   np.asarray(mem_ref[name]), atol=1e-6,
                                   err_msg=name)


def test_ef_identity_through_packed_exchange(key):
    """Per worker: decode(own payload) + m' == m + eta*g, reconstructed from
    the distributed outputs alone (update = mean of own contributions)."""
    comp = Compressor(gamma=0.05, method="block_topk", block=512,
                      min_compress_size=64, value_bits=8)
    gtree = _worker_tree(key)
    mtree = jax.tree.map(lambda x: jnp.zeros_like(x), gtree)
    eta = 0.1
    upd, newm, _, _ = _run_workers(gtree, mtree, comp, eta=eta)
    for name in gtree:
        acc = eta * np.asarray(gtree[name], np.float32)   # m == 0
        own = acc - np.asarray(newm[name], np.float32)    # EF identity
        np.testing.assert_allclose(own.mean(axis=0), np.asarray(upd[name]),
                                   atol=1e-6, err_msg=name)


def test_packed_exchange_two_axis_mesh(key):
    """('pod','data') dp axes: the gathered payload reshapes to one worker
    axis; accounting and parity hold on the 4x2 mesh."""
    comp = Compressor(gamma=0.05, method="block_topk", block=512,
                      min_compress_size=64, value_bits=8)
    gtree = _worker_tree(key)
    mtree = jax.tree.map(lambda x: jnp.zeros_like(x), gtree)
    upd, newm, wire, _ = _run_workers(gtree, mtree, comp, mesh_shape=(4, 2),
                                   axes=("pod", "data"))
    upd_ref, mem_ref = _simulate(gtree, mtree, comp, 0.1)
    squeezed = jax.tree.map(lambda x: x[0], gtree)
    assert int(wire) == tree_wire_bytes(squeezed, comp)
    for name in gtree:
        np.testing.assert_allclose(np.asarray(upd[name]),
                                   np.asarray(upd_ref[name]), atol=1e-6,
                                   err_msg=name)
        np.testing.assert_allclose(np.asarray(newm[name]),
                                   np.asarray(mem_ref[name]), atol=1e-6,
                                   err_msg=name)


def test_gathered_buffer_is_the_accounted_bytes(key):
    """The all_gather operand for a compressed leaf is a uint32 payload of
    exactly wire_bytes bytes — inspected in the jaxpr of the worker fn."""
    comp = Compressor(gamma=0.05, method="block_topk", block=512,
                      min_compress_size=64, value_bits=8)
    d = 3000
    g = jax.random.normal(key, (d,))
    m = jnp.zeros((d,))
    mesh = make_mesh((W_WORKERS,), ("data",))

    def worker(g, m):
        return worker_compress_aggregate(g, m, jnp.float32(0.1), comp,
                                         ("data",))

    f = shard_map(worker, mesh=mesh, in_specs=(P(), P()),
                  out_specs=(P(), P(), P(), P(), P()), axis_names={"data"},
                  check_vma=False)
    jaxpr = jax.make_jaxpr(f)(g, m)
    # the all_gather sits inside the shard_map sub-jaxpr, so check the
    # whole jaxpr text for a uint32 operand of the expected row width
    spec = wire_fmt.WireSpec.for_row(comp, d)
    txt = str(jaxpr)
    assert f"u32[1,{spec.row_words}]" in txt or \
        f"u32[{spec.row_words}]" in txt, txt[:2000]
    assert spec.row_bytes == comp.wire_bytes(d)
