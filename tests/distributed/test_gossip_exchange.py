"""8-virtual-device parity for the gossip transport (DESIGN.md §12).

Three contracts:

* **EF / byte parity with bucketed** — gossip runs the IDENTICAL
  selection + encode stage (repro/core/leafmath.select_and_encode), so on
  identical per-worker inputs its EF memory and wire/effective byte
  counters must be BIT-EXACT against ``transport="bucketed"`` (telemetry
  <= 8 ulp, same caveat as test_bucketed_exchange.py).  The updates
  legitimately differ: neighborhood consensus mean vs global mean.
* **mixing-matrix simulation parity** — K full steps of the gossip
  optimizer on 8 virtual workers (per-worker quadratic dynamics
  ``g_i = x_i - c_i``) must track a collective-free NumPy/float64
  simulation that applies ``Topology.mixing_matrix()`` rows to the
  decoded payloads — proving the ppermute schedule + uniform Metropolis
  weights really implement the doubly-stochastic mix, EF recursion and
  AdaGossip step the docs claim (method="topk", value_bits=32 so the
  wire is value-exact and float64 is a valid reference).
* **consensus contraction** — repeated uncompressed ``gossip_mix``
  rounds contract the consensus error monotonically (spectral gap > 0)
  and match ``Topology.mix_reference`` to ~1e-6 absolute per round
  (same difference form, but XLA may contract ``x + w * acc`` into an
  fma, which shifts near-zero outputs by many ulp); a constant tree is
  a bit-exact fixed point (every permuted difference is literally
  zero).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.comm.gossip import (GossipConfig, GossipCtx, GossipState,
                               gossip_mix)
from repro.comm.topology import build_topology
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


def _run_bucketed(gtree, mtree, comp, eta=0.1):
    mesh = make_mesh((W_WORKERS,), ("data",))
    lead = jax.tree.map(lambda _: P("data"), gtree)
    rep = jax.tree.map(lambda _: P(), gtree)
    tel_lead = jax.tree.map(lambda _: P("data"),
                            CompressionTelemetry.init(abstract=True))

    def worker(g, m):
        g = jax.tree.map(lambda x: x[0], g)
        m = jax.tree.map(lambda x: x[0], m)
        upd, newm, wire, eff, tel = worker_compress_aggregate(
            g, m, jnp.float32(eta), comp, ("data",), transport="bucketed")
        return (upd, jax.tree.map(lambda x: x[None], newm), wire,
                eff[None], jax.tree.map(lambda x: x[None], tel))

    f = shard_map(worker, mesh=mesh, in_specs=(lead, lead),
                  out_specs=(rep, lead, P(), P("data"), tel_lead),
                  axis_names={"data"}, check_vma=False)
    return jax.jit(f)(gtree, mtree)


def _run_gossip(gtree, mtree, comp, topology, eta=0.1):
    mesh = make_mesh((W_WORKERS,), ("data",))
    topo = build_topology(topology, W_WORKERS)
    cfg = GossipConfig(topology=topology)
    lead = jax.tree.map(lambda _: P("data"), gtree)
    tel_lead = jax.tree.map(lambda _: P("data"),
                            CompressionTelemetry.init(abstract=True))

    def worker(g, m, v):
        g = jax.tree.map(lambda x: x[0], g)
        m = jax.tree.map(lambda x: x[0], m)
        ctx = GossipCtx(topology=topo, cfg=cfg,
                        state=GossipState(v=v[0], lr=jnp.float32(0.0)))
        upd, newm, wire, eff, tel, st = worker_compress_aggregate(
            g, m, jnp.float32(eta), comp, ("data",), transport="gossip",
            transport_ctx=ctx)
        return (jax.tree.map(lambda x: x[None], upd),
                jax.tree.map(lambda x: x[None], newm), wire, eff[None],
                jax.tree.map(lambda x: x[None], tel),
                jax.tree.map(lambda x: x[None], st))

    f = shard_map(worker, mesh=mesh, in_specs=(lead, lead, P("data")),
                  out_specs=(lead, lead, P(), P("data"), tel_lead,
                             P("data")),
                  axis_names={"data"}, check_vma=False)
    return jax.jit(f)(gtree, mtree, jnp.zeros((W_WORKERS,), jnp.float32))


def _assert_tree_equal(a, b, msg, maxulp=0):
    for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        if maxulp:
            np.testing.assert_array_max_ulp(np.asarray(u), np.asarray(v),
                                            maxulp=maxulp)
        else:
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v),
                                          err_msg=msg)


@pytest.mark.parametrize("topology", ["ring", "exp"])
@pytest.mark.parametrize("method,value_bits", [("block_topk", 8),
                                               ("topk", 32)])
def test_gossip_ef_bytes_match_bucketed(key, topology, method, value_bits):
    """Identical selection stage => bit-identical per-worker EF memory and
    byte counters, even though the consensus updates differ."""
    comp = Compressor(gamma=0.05, method=method, block=512,
                      min_compress_size=64, value_bits=value_bits)
    gtree = _worker_tree(key)
    mtree = jax.tree.map(
        lambda x: jax.random.normal(jax.random.fold_in(key, x.size),
                                    x.shape) * 0.1, gtree)
    ref = _run_bucketed(gtree, mtree, comp)
    got = _run_gossip(gtree, mtree, comp, topology)
    _assert_tree_equal(ref[1], got[1], f"{topology}/{method}: EF memory")
    _assert_tree_equal(ref[2], got[2], f"{topology}/{method}: wire")
    _assert_tree_equal(ref[3], got[3], f"{topology}/{method}: eff")
    _assert_tree_equal(ref[4], got[4], "telemetry", maxulp=8)
    # the consensus mean is NOT the global mean on these graphs — the
    # parity above is a selection/EF contract, not update equality
    upd_ref = np.asarray(jax.tree.leaves(ref[0])[0])
    upd_got = np.asarray(jax.tree.leaves(got[0])[0])[0]
    assert not np.allclose(upd_ref, upd_got)


def _np_topk_decode(acc, k):
    """float64 reference of per_layer_topk + scatter: keep the k largest
    |entries| per row, zero the rest."""
    out = np.zeros_like(acc)
    for r in range(acc.shape[0]):
        idx = np.argsort(-np.abs(acc[r]))[:k]
        out[r, idx] = acc[r, idx]
    return out


@pytest.mark.parametrize("topology", ["ring", "exp"])
def test_gossip_steps_match_mixing_matrix_simulation(key, topology):
    """K optimizer steps on the mesh == collective-free float64 simulation
    driven by Topology.mixing_matrix()."""
    L, D, DB, K, eta = 4, 256, 48, 5, 0.1
    topo = build_topology(topology, W_WORKERS)
    cfg = GossipConfig(topology=topology)
    comp = Compressor(gamma=0.05, method="topk", value_bits=32,
                      min_compress_size=64)
    k = comp.k_for(D)
    ks = jax.random.split(key, 4)
    x0 = {"w": jax.random.normal(ks[0], (W_WORKERS, L, D)),
          "b": jax.random.normal(ks[1], (W_WORKERS, DB))}
    c = {"w": jax.random.normal(ks[2], (W_WORKERS, L, D)),
         "b": jax.random.normal(ks[3], (W_WORKERS, DB))}

    mesh = make_mesh((W_WORKERS,), ("data",))
    lead = jax.tree.map(lambda _: P("data"), x0)

    def worker(x, m, v, tgt):
        x = jax.tree.map(lambda t: t[0], x)
        m = jax.tree.map(lambda t: t[0], m)
        tgt = jax.tree.map(lambda t: t[0], tgt)
        g = jax.tree.map(jnp.subtract, x, tgt)
        ctx = GossipCtx(topology=topo, cfg=cfg,
                        state=GossipState(v=v[0], lr=jnp.float32(0.0)))
        upd, newm, _, _, _, st = worker_compress_aggregate(
            g, m, jnp.float32(eta), comp, ("data",), transport="gossip",
            transport_ctx=ctx)
        newx = jax.tree.map(jnp.subtract, x, upd)

        def lift(t):
            return jax.tree.map(lambda y: y[None], t)

        return lift(newx), lift(newm), st.v[None]

    step = jax.jit(shard_map(
        worker, mesh=mesh, in_specs=(lead, lead, P("data"), lead),
        out_specs=(lead, lead, P("data")), axis_names={"data"},
        check_vma=False))

    xs, ms = x0, jax.tree.map(jnp.zeros_like, x0)
    vs = jnp.zeros((W_WORKERS,), jnp.float32)
    for _ in range(K):
        xs, ms, vs = step(xs, ms, vs, c)

    # ---- float64 reference: mixing-matrix rows over decoded payloads ---
    Wmat = topo.mixing_matrix()                      # (n, n) float64
    xw = np.asarray(x0["w"], np.float64)
    xb = np.asarray(x0["b"], np.float64)
    cw = np.asarray(c["w"], np.float64)
    cb = np.asarray(c["b"], np.float64)
    mw = np.zeros_like(xw)
    v = np.zeros(W_WORKERS)
    n_tot = L * D + DB
    for _ in range(K):
        acc_w = mw + eta * (xw - cw)                 # (W, L, D)
        dec = np.stack([_np_topk_decode(acc_w[i], k)
                        for i in range(W_WORKERS)])
        acc_b = eta * (xb - cb)                      # dense EF stays zero
        mix_w = np.einsum("ij,jld->ild", Wmat, dec)
        mix_b = Wmat @ acc_b
        e_w, e_b = mix_w - dec, mix_b - acc_b
        err = (e_w.reshape(W_WORKERS, -1) ** 2).sum(1) \
            + (e_b ** 2).sum(1)
        v = cfg.beta * v + (1.0 - cfg.beta) * err / n_tot
        lr = np.minimum(cfg.lr_max, cfg.consensus_lr / (np.sqrt(v)
                                                        + cfg.eps))
        xw = xw - (dec + lr[:, None, None] * e_w)
        xb = xb - (acc_b + lr[:, None] * e_b)
        mw = acc_w - dec

    np.testing.assert_allclose(np.asarray(xs["w"]), xw, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(xs["b"]), xb, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(ms["w"]), mw, rtol=1e-4,
                               atol=1e-5)
    assert np.all(np.asarray(ms["b"]) == 0.0)        # dense: no EF
    np.testing.assert_allclose(np.asarray(vs), v, rtol=1e-4)


def _one_mix_round(tree, topo, lr=1.0):
    mesh = make_mesh((W_WORKERS,), ("data",))
    lead = jax.tree.map(lambda _: P("data"), tree)

    def w(t):
        t = jax.tree.map(lambda x: x[0], t)
        out = gossip_mix(t, topo, "data", lr=lr)
        return jax.tree.map(lambda x: x[None], out)

    f = shard_map(w, mesh=mesh, in_specs=(lead,), out_specs=lead,
                  axis_names={"data"}, check_vma=False)
    return jax.jit(f)(tree)


def _consensus_err(tree):
    return max(float(np.max(np.abs(x - x.mean(0))))
               for x in map(np.asarray, jax.tree.leaves(tree)))


@pytest.mark.parametrize("topology", ["ring", "exp"])
def test_gossip_mix_contracts_and_matches_reference(key, topology):
    """Uncompressed consensus rounds: monotone contraction toward the
    mean, few-ulp parity with Topology.mix_reference, and a bit-exact
    constant fixed point."""
    topo = build_topology(topology, W_WORKERS)
    ks = jax.random.split(key, 2)
    cur = {"a": jax.random.normal(ks[0], (W_WORKERS, 32)),
           "b": jax.random.normal(ks[1], (W_WORKERS, 3, 7))}
    errs = [_consensus_err(cur)]
    for _ in range(6):
        # reference from the SAME round input (cumulative comparison
        # would compound the per-round fma drift)
        ref = jax.tree.map(lambda z: topo.mix_reference(np.asarray(z)),
                           cur)
        cur = _one_mix_round(cur, topo)
        for u, v in zip(jax.tree.leaves(cur), jax.tree.leaves(ref)):
            np.testing.assert_allclose(
                np.asarray(u), v, rtol=1e-6, atol=1e-6,
                err_msg=f"{topology}: mix_reference parity")
        errs.append(_consensus_err(cur))
    assert all(b < a for a, b in zip(errs, errs[1:])), errs

    const = jax.tree.map(
        lambda x: jnp.broadcast_to(x[:1], x.shape) * 1.0, cur)
    mixed = _one_mix_round(const, topo)
    _assert_tree_equal(mixed, const, f"{topology}: constant fixed point")
