"""Cohort-over-dp parity: the vmap'd shard_map exchange against the
collective-free NumPy reference (tests/federated/reference.py).

64 heterogeneous clients — per-client step sizes AND per-client adaptive
gamma (so every client ships a different k_t through the one fixed-shape
gather) — run on both an (8,) dp mesh and a (4, 2) two-axis mesh (the
multi-axis ``gather_packed`` reshape path).  The aggregated update must
match the float64 oracle to float32 tolerance, and the per-client EF
memory must match to within one float32 ulp: the residual is pure float32
arithmetic on both sides (see reference.py) — XLA fuses the EF
accumulate into an fma, numpy rounds the product separately — so
anything beyond roundoff means the client-id/gather-row mapping or the
own-slice EF contract broke.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.comm.bucket import build_bucket_plan
from jax import shard_map
from repro.core import Compressor
from repro.fed.clients import cohort_compress_aggregate, per_client_wire_bytes
from repro.fed.sampling import participation_mask
from repro.launch.mesh import make_mesh

from reference import simulate_cohort

N_CLIENTS = 64

MESHES = {
    "dp8": ((8,), ("data",)),
    "pod4x2": ((4, 2), ("pod", "data")),
}


def _cohort(seed=0):
    """(N, ...) client-leading leaves: one stacked, one flat compressed,
    one dense small — every lane kind of the bucket plan."""
    rng = np.random.default_rng(seed)
    grads = {
        "w": rng.standard_normal((N_CLIENTS, 3, 1200)).astype(np.float32),
        "v": rng.standard_normal((N_CLIENTS, 4096)).astype(np.float32),
        "t": rng.standard_normal((N_CLIENTS, 60)).astype(np.float32),
    }
    mem = {k: (0.1 * rng.standard_normal(v.shape)).astype(np.float32)
           for k, v in grads.items()}
    eta_c = np.linspace(0.1, 0.5, N_CLIENTS, dtype=np.float32)
    gamma_c = np.linspace(0.02, 0.2, N_CLIENTS, dtype=np.float32)
    part = participation_mask(N_CLIENTS, 3, seed=11, mode="fixed",
                              clients_per_round=48)
    return grads, mem, eta_c, gamma_c, part


def _run_mesh(mesh_name, grads, mem, eta_c, gamma_c, part, comp,
              aggregation):
    shape, axes = MESHES[mesh_name]
    mesh = make_mesh(shape, axes)
    dp_axes = axes
    lead = P(axes)
    tlead = jax.tree.map(lambda _: lead, grads)
    trep = jax.tree.map(lambda _: P(), grads)
    fn = functools.partial(cohort_compress_aggregate, comp=comp,
                           dp_axes=dp_axes, aggregation=aggregation)
    f = shard_map(
        lambda g, m, e, gc, p: fn(g, m, e, participation=p, gamma_c=gc),
        mesh=mesh, in_specs=(tlead, tlead, lead, lead, P()),
        out_specs=(trep, tlead, P(), P()),
        axis_names=set(axes), check_vma=False)
    return jax.jit(f)(grads, mem, jnp.asarray(eta_c),
                      jnp.asarray(gamma_c), jnp.asarray(part))


@pytest.mark.parametrize("mesh_name", sorted(MESHES))
@pytest.mark.parametrize("aggregation", ["support", "mean"])
def test_cohort_parity_adaptive(mesh_name, aggregation):
    comp = Compressor(gamma=0.02, method="topk", min_compress_size=1000,
                      value_bits=32, use_kernel=False, max_gamma=0.2)
    grads, mem, eta_c, gamma_c, part = _cohort()
    upd, new_mem, wire, eff = _run_mesh(
        mesh_name, grads, mem, eta_c, gamma_c, part, comp, aggregation)
    ref_upd, ref_mem = simulate_cohort(grads, mem, eta_c, gamma_c, part,
                                       comp, aggregation)
    for name in grads:
        np.testing.assert_allclose(
            np.asarray(upd[name], np.float64), ref_upd[name],
            rtol=2e-6, atol=2e-6, err_msg=f"update leaf {name!r}")
        np.testing.assert_allclose(
            np.asarray(new_mem[name]), ref_mem[name], rtol=0, atol=5e-7,
            err_msg=f"EF memory leaf {name!r}")

    leaves = [v.shape[1:] for v in grads.values()]
    plan = build_bucket_plan(leaves,
                             [len(s) >= 2 for s in leaves], comp)
    n_part = float(part.sum())
    assert float(wire) == n_part * per_client_wire_bytes(plan)
    # heterogeneous k_t: ragged effective bytes strictly below budget
    assert 0.0 < float(eff) < float(wire)


@pytest.mark.parametrize("mesh_name", sorted(MESHES))
def test_cohort_parity_nonadaptive(mesh_name):
    comp = Compressor(gamma=0.1, method="topk", min_compress_size=1000,
                      value_bits=32, use_kernel=False)
    grads, mem, eta_c, gamma_c, part = _cohort(seed=7)
    gamma0 = np.zeros(N_CLIENTS, np.float32)   # ignored: non-ragged wire
    upd, new_mem, wire, eff = _run_mesh(
        mesh_name, grads, mem, eta_c, gamma0, part, comp, "support")
    ref_upd, ref_mem = simulate_cohort(grads, mem, eta_c, gamma0, part,
                                       comp, "support")
    for name in grads:
        np.testing.assert_allclose(
            np.asarray(upd[name], np.float64), ref_upd[name],
            rtol=2e-6, atol=2e-6, err_msg=f"update leaf {name!r}")
        np.testing.assert_allclose(
            np.asarray(new_mem[name]), ref_mem[name], rtol=0, atol=5e-7,
            err_msg=f"EF memory leaf {name!r}")
    assert 0.0 < float(eff) <= float(wire)


def test_mesh_invariance():
    """Same cohort on (8,) and (4,2) — identical wire accounting and
    update within summation-order tolerance."""
    comp = Compressor(gamma=0.02, method="topk", min_compress_size=1000,
                      value_bits=32, use_kernel=False, max_gamma=0.2)
    grads, mem, eta_c, gamma_c, part = _cohort(seed=3)
    outs = {name: _run_mesh(name, grads, mem, eta_c, gamma_c, part,
                            comp, "support") for name in MESHES}
    a, b = outs["dp8"], outs["pod4x2"]
    for name in grads:
        np.testing.assert_allclose(np.asarray(a[0][name]),
                                   np.asarray(b[0][name]),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(a[1][name]),
                                      np.asarray(b[1][name]))
    assert float(a[2]) == float(b[2])
    np.testing.assert_allclose(float(a[3]), float(b[3]), rtol=1e-6)
