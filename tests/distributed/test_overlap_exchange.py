"""8-virtual-device parity for the overlap transport (DESIGN.md §14).

The pinned contracts:

* the chunked ring all-gather is BIT-IDENTICAL to ``lax.all_gather`` /
  ``gather_packed`` on single- and multi-axis dp meshes, at divisible and
  non-divisible chunk counts;
* ``delay=0`` is a bit-exact drop-in for ``transport="bucketed"`` —
  updates, per-worker EF memory, wire and effective bytes — including
  heterogeneous per-worker k_t riding the ragged count headers, on both
  (8,) and (4, 2) dp meshes (telemetry to <= 8 ulp, same reduction-order
  caveat as tests/distributed/test_bucketed_exchange.py);
* ``delay=1`` double-buffering: the warm-up step applies a ZERO update
  (the initial zero payload) while the EF memory stays bit-exact vs
  bucketed (selection/EF are always current), and step t+1 applies step
  t's bucketed aggregate bit-exactly with the carried effective bytes;
* a delay-1 quadratic trajectory converges to within 5% (+ noise floor)
  of the bucketed trajectory's suboptimality — the golden convergence
  pair for the one-step-stale aggregation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.comm.overlap import OverlapConfig, OverlapCtx, init_overlap_state
from repro.comm.ring import ring_all_gather
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


def _mem_tree(key, gtree):
    return jax.tree.map(
        lambda x: jax.random.normal(jax.random.fold_in(key, x.size + 1),
                                    x.shape) * 0.1, gtree)


def _hetero_gammas(comp):
    return jnp.linspace(comp.max_gamma / 8.0, comp.max_gamma,
                        W_WORKERS).astype(jnp.float32)


def _init_state(gtree, comp, n_workers=W_WORKERS):
    flat = jax.tree.leaves(jax.tree.map(lambda x: x[0], gtree))
    st = init_overlap_state([x.shape for x in flat],
                            [x.ndim >= 2 for x in flat], comp)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_workers,) + x.shape), st)


def _run(gtree, mtree, gammas, comp, transport, cfg=None, state=None,
         mesh_shape=(W_WORKERS,), axes=("data",), eta=0.1):
    """One exchange; for the overlap transport also returns the new
    (W, ...)-batched carried state as a trailing element."""
    mesh = make_mesh(mesh_shape, axes)
    lead_axis = axes[0] if len(axes) == 1 else tuple(axes)
    lead = jax.tree.map(lambda _: P(lead_axis), gtree)
    rep = jax.tree.map(lambda _: P(), gtree)
    tel_lead = jax.tree.map(lambda _: P(lead_axis),
                            CompressionTelemetry.init(abstract=True))
    use_gamma = gammas is not None
    if gammas is None:
        gammas = jnp.zeros((W_WORKERS,), jnp.float32)
    overlap = transport == "overlap"

    def worker(g, m, gam, st):
        g = jax.tree.map(lambda x: x[0], g)
        m = jax.tree.map(lambda x: x[0], m)
        kw = {}
        if overlap:
            kw["transport_ctx"] = OverlapCtx(
                cfg=cfg, state=jax.tree.map(lambda x: x[0], st))
        out = worker_compress_aggregate(
            g, m, jnp.float32(eta), comp, tuple(axes),
            gamma_t=gam[0] if use_gamma else None, transport=transport,
            **kw)
        upd, newm, wire, eff, tel = out[:5]
        wrapped = (upd, jax.tree.map(lambda x: x[None], newm), wire,
                   eff[None], jax.tree.map(lambda x: x[None], tel))
        if overlap:
            wrapped += (jax.tree.map(lambda x: x[None], out[5]),)
        return wrapped

    if state is None:
        state = _init_state(gtree, comp) if overlap else ()
    st_spec = jax.tree.map(lambda _: P(lead_axis), state)
    out_specs = (rep, lead, P(), P(lead_axis), tel_lead)
    if overlap:
        out_specs += (st_spec,)
    f = shard_map(worker, mesh=mesh,
                  in_specs=(lead, lead, P(lead_axis), st_spec),
                  out_specs=out_specs,
                  axis_names=set(axes), check_vma=False)
    return jax.jit(f)(gtree, mtree, gammas, state)


def _assert_tree_equal(a, b, msg, maxulp=0):
    for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        if maxulp:
            np.testing.assert_array_max_ulp(np.asarray(u), np.asarray(v),
                                            maxulp=maxulp)
        else:
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v),
                                          err_msg=msg)


# ---------------------------------------------------------------------------
# ring gather parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh_shape,axes", [
    ((W_WORKERS,), ("data",)), ((4, 2), ("pod", "data")),
])
@pytest.mark.parametrize("n_chunks", [1, 3, 7])
def test_ring_gather_matches_all_gather(mesh_shape, axes, n_chunks):
    """The chunked ring assembles the EXACT (W, total_words) buffer the
    flat all_gather produces, including non-divisible chunking and
    ring-of-rings multi-axis meshes."""
    total_words = 1000
    rng = np.random.default_rng(7)
    payload = jnp.asarray(
        rng.integers(0, 2**32, (W_WORKERS, total_words), dtype=np.uint32))
    mesh = make_mesh(mesh_shape, axes)
    lead = axes[0] if len(axes) == 1 else tuple(axes)

    def via_ring(p):
        return ring_all_gather(p[0], axes, n_chunks)

    def via_gather(p):
        return jax.lax.all_gather(p[0], axes).reshape(-1, total_words)

    outs = []
    for fn in (via_ring, via_gather):
        f = shard_map(fn, mesh=mesh, in_specs=(P(lead),),
                      out_specs=P(), axis_names=set(axes), check_vma=False)
        outs.append(np.asarray(jax.jit(f)(payload)))
    np.testing.assert_array_equal(outs[0], outs[1])


# ---------------------------------------------------------------------------
# delay=0: bit-exact bucketed parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh_shape,axes", [
    ((W_WORKERS,), ("data",)), ((4, 2), ("pod", "data")),
])
@pytest.mark.parametrize("n_chunks", [1, 3])
def test_overlap_delay0_bit_exact_vs_bucketed(key, mesh_shape, axes,
                                              n_chunks):
    """delay=0 over the ring is a bit-exact drop-in for the bucketed
    transport under heterogeneous per-worker k_t (the ragged headers)."""
    comp = Compressor(gamma=0.05, max_gamma=0.05, method="block_topk",
                      block=512, min_compress_size=64, value_bits=8)
    gtree = _worker_tree(key)
    mtree = _mem_tree(key, gtree)
    gammas = _hetero_gammas(comp)
    ref = _run(gtree, mtree, gammas, comp, "bucketed",
               mesh_shape=mesh_shape, axes=axes)
    got = _run(gtree, mtree, gammas, comp, "overlap",
               cfg=OverlapConfig(n_chunks=n_chunks, delay=0),
               mesh_shape=mesh_shape, axes=axes)
    for name, a, b in zip(("updates", "memory", "wire", "eff",
                           "telemetry"), ref, got[:5]):
        _assert_tree_equal(a, b, f"{mesh_shape}/nc={n_chunks}: {name}",
                           maxulp=8 if name == "telemetry" else 0)
    # the new carried state holds THIS step's encoded payload + eff bytes
    assert float(got[5].seeded[0]) == 1.0
    np.testing.assert_array_equal(np.asarray(got[5].eff_wire),
                                  np.asarray(ref[3]))


# ---------------------------------------------------------------------------
# delay=1: double-buffer semantics
# ---------------------------------------------------------------------------

def test_overlap_delay1_warmup_and_staleness(key):
    """Step 1 (warm-up, zero carried payload): zero update, EF memory
    bit-exact vs bucketed (selection is current).  Step 2: applies step
    1's bucketed aggregate bit-exactly, reporting the carried effective
    bytes; EF again bit-exact vs bucketed on the step-2 inputs."""
    comp = Compressor(gamma=0.05, max_gamma=0.05, method="block_topk",
                      block=512, min_compress_size=64, value_bits=8)
    cfg = OverlapConfig(n_chunks=2, delay=1)
    gtree1 = _worker_tree(key)
    mtree1 = _mem_tree(key, gtree1)
    gtree2 = _worker_tree(jax.random.fold_in(key, 1))
    gammas = _hetero_gammas(comp)

    buck1 = _run(gtree1, mtree1, gammas, comp, "bucketed")
    ov1 = _run(gtree1, mtree1, gammas, comp, "overlap", cfg=cfg)

    # warm-up: zero update on every leaf, EF bit-exact vs bucketed
    for u in jax.tree.leaves(ov1[0]):
        np.testing.assert_array_equal(np.asarray(u), 0.0)
    _assert_tree_equal(buck1[1], ov1[1], "warmup EF")
    # wire is static (the full buffer crosses the wire every step);
    # effective bytes describe the zero payload actually shipped
    np.testing.assert_array_equal(np.asarray(buck1[2]),
                                  np.asarray(ov1[2]))
    assert float(np.asarray(ov1[3])[0]) <= float(np.asarray(buck1[3])[0])
    assert all(float(s) == 1.0 for s in np.asarray(ov1[5].seeded))

    # step 2 (same memory as bucketed — EFs matched bitwise above)
    buck2 = _run(gtree2, buck1[1], gammas, comp, "bucketed")
    ov2 = _run(gtree2, ov1[1], gammas, comp, "overlap", cfg=cfg,
               state=ov1[5])
    # the applied aggregate IS step 1's bucketed mean, bit for bit
    _assert_tree_equal(buck1[0], ov2[0], "delay-1 aggregate")
    # EF stays current: bit-exact vs bucketed on the step-2 inputs
    _assert_tree_equal(buck2[1], ov2[1], "step-2 EF")
    # the reported effective bytes are the carried step-1 ones
    np.testing.assert_array_equal(np.asarray(ov2[3]), np.asarray(buck1[3]))


# ---------------------------------------------------------------------------
# golden delay-1 convergence pair (quadratic)
# ---------------------------------------------------------------------------

def test_overlap_delay1_quadratic_convergence(key):
    """Fixed-gamma compressed SGD on a worker-heterogeneous quadratic:
    the delay-1 overlapped trajectory's suboptimality after T steps stays
    within 5% (+ noise floor) of the synchronous bucketed trajectory's —
    the golden pair pinning that one-step staleness does not degrade
    convergence (DESIGN.md §14)."""
    d = 512
    T = 120
    eta = 0.1
    comp = Compressor(gamma=0.25, method="block_topk", block=128,
                      min_compress_size=64, value_bits=32)
    ka, kb = jax.random.split(key)
    a_w = 0.5 + jax.random.uniform(ka, (W_WORKERS, d))      # diag Hessians
    b_w = jax.random.normal(kb, (W_WORKERS, d))
    a_bar, b_bar = jnp.mean(a_w, 0), jnp.mean(b_w, 0)
    x_star = b_bar / a_bar

    def f_global(x):
        return float(jnp.mean(jnp.sum(
            0.5 * a_w * x[None] ** 2 - b_w * x[None], axis=1)))
    f_star = f_global(x_star)

    def trajectory(transport, cfg=None):
        x = jnp.zeros((d,))
        mem = {"x": jnp.zeros((W_WORKERS, d))}
        state = _init_state({"x": jnp.zeros((W_WORKERS, d))}, comp) \
            if transport == "overlap" else None
        for _ in range(T):
            g = {"x": a_w * x[None] - b_w}
            out = _run(g, mem, None, comp, transport, cfg=cfg,
                       state=state, eta=eta)
            x = x - out[0]["x"]
            mem = out[1]
            if transport == "overlap":
                state = out[5]
        return f_global(x) - f_star

    gap_sync = trajectory("bucketed")
    gap_stale = trajectory("overlap", OverlapConfig(n_chunks=2, delay=1))
    assert gap_sync >= 0 and gap_stale >= 0
    assert gap_stale <= 1.05 * gap_sync + 5e-4, (gap_stale, gap_sync)
