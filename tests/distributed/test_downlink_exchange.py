"""8-virtual-device parity for the compressed downlink (DESIGN.md §15).

The downlink is a *physically simulated* server: the bucketed aggregate is
bit-identical on every worker, so the server compress/EF runs replicated
with NO extra collective.  That claim is exactly testable:

* the downlink-enabled exchange must be bit-exact against a
  **collective-free oracle** — ``apply_downlink`` called on the host on
  the reference exchange's replicated mean (the uplink itself is pinned
  bit-exact against the per-leaf schedule in test_bucketed_exchange.py)
  — on both (8,) and (4, 2) dp meshes;
* the uplink outputs (EF memory, wire/effective bytes, telemetry) must be
  UNTOUCHED by enabling the downlink — ``downlink="dense"`` stays the
  bit-exact reference because compression is purely post-aggregate;
* at equal gamma the accounted ``up_eff + down_eff`` must come in
  strictly below the dense downlink charge the reference path pays;
* the server EF residual must actually recycle: round two with the
  carried state differs from round two with a zeroed server memory.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.comm.downlink import (DownlinkCtx, DownlinkState,
                                 apply_downlink, dense_downlink_bytes,
                                 downlink_plan, downlink_wire_bytes,
                                 init_downlink_state)
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


def _flat_geometry(gtree):
    flat, _ = jax.tree.flatten(jax.tree.map(lambda x: x[0], gtree))
    return [x.shape for x in flat], [x.ndim >= 2 for x in flat]


def _fresh_state(gtree, comp, gamma0):
    shapes, flags = _flat_geometry(gtree)
    return init_downlink_state(shapes, flags, comp, gamma0)


def _run(gtree, mtree, gammas, comp, dl_state=None,
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
    with_dl = dl_state is not None

    def worker(g, m, gam, dls):
        g = jax.tree.map(lambda x: x[0], g)
        m = jax.tree.map(lambda x: x[0], m)
        out = worker_compress_aggregate(
            g, m, jnp.float32(eta), comp, tuple(axes),
            gamma_t=gam[0] if use_gamma else None,
            downlink_ctx=DownlinkCtx(state=dls) if with_dl else None)
        upd, newm, wire, eff, tel = out[:5]
        res = (upd, jax.tree.map(lambda x: x[None], newm), wire,
               eff[None], jax.tree.map(lambda x: x[None], tel))
        if with_dl:
            res = res + (out[5],)
        return res

    dls_in = dl_state if with_dl else DownlinkState(
        memory=jnp.zeros((0,), jnp.float32), gamma=jnp.float32(0.0))
    dl_spec = DownlinkState(memory=P(), gamma=P())
    out_specs = (rep, lead, P(), P(lead_axis), tel_lead)
    if with_dl:
        from repro.comm.downlink import DownlinkResult
        out_specs = out_specs + (DownlinkResult(dl_spec, P(), P()),)
    f = shard_map(worker, mesh=mesh,
                  in_specs=(lead, lead, P(lead_axis), dl_spec),
                  out_specs=out_specs, axis_names=set(axes),
                  check_vma=False)
    return jax.jit(f)(gtree, mtree, gammas, dls_in)


def _assert_tree_equal(a, b, msg):
    for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v),
                                      err_msg=msg)


@pytest.mark.parametrize("mesh_shape,axes", [
    ((W_WORKERS,), ("data",)), ((4, 2), ("pod", "data")),
])
@pytest.mark.parametrize("comp", [
    Compressor(gamma=0.05, method="block_topk", block=512,
               min_compress_size=64, value_bits=8),
    Compressor(gamma=0.05, max_gamma=0.05, method="topk",
               min_compress_size=64, value_bits=32),
], ids=["block8", "ragged_topk32"])
def test_downlink_matches_collective_free_oracle(key, comp, mesh_shape,
                                                 axes):
    gtree = _worker_tree(key)
    mtree = jax.tree.map(
        lambda x: jax.random.normal(jax.random.fold_in(key, x.size),
                                    x.shape) * 0.1, gtree)
    gammas = (jnp.linspace(comp.max_gamma / 8.0, comp.max_gamma, W_WORKERS)
              .astype(jnp.float32) if comp.adaptive else None)
    dl0 = _fresh_state(gtree, comp, comp.gamma)
    assert dl0.memory.size > 0

    ref = _run(gtree, mtree, gammas, comp, None, mesh_shape, axes)
    got = _run(gtree, mtree, gammas, comp, dl0, mesh_shape, axes)

    # 1) enabling the downlink leaves every uplink output untouched —
    #    the dense-downlink reference path is bit-exact by construction
    for name, a, b in zip(("memory", "wire", "eff", "telemetry"),
                          ref[1:5], got[1:5]):
        _assert_tree_equal(a, b, f"uplink {name} changed")

    # 2) the mesh downlink == the pure host oracle on the reference mean
    shapes, flags = _flat_geometry(gtree)
    flat_ref, treedef = jax.tree.flatten(ref[0])
    want_upd, want_state, want_wire, want_eff = apply_downlink(
        flat_ref, flags, comp, dl0)
    dl_res = got[5]
    _assert_tree_equal(treedef.unflatten(want_upd), got[0],
                       f"{mesh_shape}: downlinked updates")
    np.testing.assert_array_equal(np.asarray(want_state.memory),
                                  np.asarray(dl_res.state.memory))
    assert float(dl_res.wire_bytes) == float(want_wire)
    assert float(dl_res.eff_wire_bytes) == float(want_eff)

    # 3) static budget matches the plan-level accounting
    plan = downlink_plan(shapes, flags, comp)
    assert float(dl_res.wire_bytes) == downlink_wire_bytes(plan)

    # 4) the downlink really changed the applied update (it compresses)
    same = all(np.array_equal(np.asarray(u), np.asarray(v))
               for u, v in zip(jax.tree.leaves(ref[0]),
                               jax.tree.leaves(got[0])))
    assert not same


def test_up_plus_down_beats_dense_downlink(key):
    """The acceptance inequality: at equal uplink/downlink gamma the
    accounted compressed round trip (up_eff + down_eff) must come in
    strictly below what the dense downlink alone charges per link."""
    comp = Compressor(gamma=0.05, max_gamma=0.05, method="block_topk",
                      block=512, min_compress_size=64, value_bits=8)
    gtree = _worker_tree(key)
    mtree = jax.tree.map(lambda x: x * 0.1, gtree)
    g = jnp.full((W_WORKERS,), comp.gamma, jnp.float32)
    dl0 = _fresh_state(gtree, comp, comp.gamma)
    out = _run(gtree, mtree, g, comp, dl0)
    up_eff = float(np.asarray(out[3])[0])
    down_eff = float(out[5].eff_wire_bytes)
    shapes, _ = _flat_geometry(gtree)
    dense_down = dense_downlink_bytes(shapes)
    assert up_eff + down_eff < dense_down, \
        (up_eff, down_eff, dense_down)
    # and the compressed downlink itself undercuts its dense reference
    assert down_eff < dense_down


def test_server_ef_recycles_across_rounds(key):
    """Round 2 with the carried server residual must differ from round 2
    with a zeroed server memory — the EF loop is live, not decorative."""
    comp = Compressor(gamma=0.05, method="block_topk", block=512,
                      min_compress_size=64, value_bits=8)
    gtree = _worker_tree(key)
    mtree = jax.tree.map(jnp.zeros_like, gtree)
    dl0 = _fresh_state(gtree, comp, comp.gamma)

    out1 = _run(gtree, mtree, None, comp, dl0)
    st1 = out1[5].state
    assert float(jnp.sum(st1.memory ** 2)) > 0.0

    gtree2 = jax.tree.map(lambda x: x * 0.5, gtree)
    mem2 = out1[1]
    carried = _run(gtree2, mem2, None, comp,
                   DownlinkState(memory=st1.memory, gamma=st1.gamma))
    zeroed = _run(gtree2, mem2, None, comp, dl0)
    same = all(np.array_equal(np.asarray(u), np.asarray(v))
               for u, v in zip(jax.tree.leaves(carried[0]),
                               jax.tree.leaves(zeroed[0])))
    assert not same
