"""8-virtual-device contracts for the hostile-wire layer (DESIGN.md §16).

The pinned guarantees:

* **faults-off bit-exactness** — the always-on verdict/quarantine layer
  is a bit-exact no-op on a clean wire: every transport's exchange
  produces identical updates, EF memory and byte counters whether the
  guards run or are compiled out (``guards_disabled()``), on (8,) and
  (4, 2) dp meshes (gossip is single-axis by construction, so it pins
  (8,) only).  Telemetry gets the usual <= 8 ulp allowance — the two
  arms are *different programs* and XLA does not pin f32
  reduction/fusion order across programs (same caveat as
  tests/distributed/test_bucketed_exchange.py); gossip updates get the
  same allowance because its AdaGossip consensus step is fed by a
  global f32 reduction (see ``_assert_outputs_equal``).
* **the "faulty" wrapper is inert outside its burst window** — same
  bit-exactness against the unwrapped transport.
* **campaign replay across mesh shapes** — the ``(seed, step, lane,
  row)`` keying makes an in-window campaign corrupt the same rows to
  the same effect on (8,) and (4, 2) meshes.
* **train-step invariance** — end to end, the guarded default (verdict
  layer + breaker) leaves parameters bit-identical to the unguarded
  legacy step on a clean run, and the lowered HLO carries EXACTLY the
  same collective counts per transport: the guards add zero
  collectives (``guards_disabled()`` is a trace-time switch, so each
  arm is traced/lowered inside its own context).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.comm.faults import FaultConfig, FaultCtx, guards_disabled
from repro.core import Compressor
from repro.core.dcsgd import worker_compress_aggregate
from repro.core.telemetry import CompressionTelemetry
from repro.launch.mesh import make_mesh

W_WORKERS = 8

MESHES = [((W_WORKERS,), ("data",)), ((4, 2), ("pod", "data"))]

# gossip's ppermute schedule is single-axis by construction (it raises
# on multi-axis dp meshes), so it only rides the (8,) variant
TRANSPORT_MESHES = [
    (t, ms, ax)
    for t in ("bucketed", "perleaf", "gossip", "overlap")
    for ms, ax in MESHES
    if not (t == "gossip" and len(ms) > 1)
]


def _worker_tree(key, n_workers=W_WORKERS):
    ks = jax.random.split(key, 3)
    return {
        "w": jax.random.normal(ks[0], (n_workers, 2, 2048)),   # stacked
        "v": jax.random.normal(ks[1], (n_workers, 3000)),
        "t": jax.random.normal(ks[2], (n_workers, 50)),        # dense
    }


def _mem_tree(key, gtree):
    return jax.tree.map(
        lambda x: jax.random.normal(jax.random.fold_in(key, x.size + 1),
                                    x.shape) * 0.1, gtree)


def _run(gtree, mtree, comp, transport, mesh_shape=(W_WORKERS,),
         axes=("data",), fault_cfg=None, step=0, eta=0.1):
    """One exchange on a real mesh; stateful transports get a fresh ctx,
    ``fault_cfg`` wraps the transport in "faulty".  Returns
    (upd, new_mem, wire, eff, telemetry) — carried transport state (and
    the faulty wrapper's passthrough) is dropped inside the worker."""
    mesh = make_mesh(mesh_shape, axes)
    lead_axis = axes[0] if len(axes) == 1 else tuple(axes)
    lead = jax.tree.map(lambda _: P(lead_axis), gtree)
    rep = jax.tree.map(lambda _: P(), gtree)
    tel_lead = jax.tree.map(lambda _: P(lead_axis),
                            CompressionTelemetry.init(abstract=True))
    gossip = transport == "gossip"

    def inner_ctx():
        # built OUTSIDE the traced worker: init_overlap_state's geometry
        # bookkeeping needs concrete shapes, and closure constants are
        # identical across both comparison arms anyway
        if transport == "gossip":
            from repro.comm.gossip import (GossipConfig, GossipCtx,
                                           GossipState)
            from repro.comm.topology import build_topology
            return GossipCtx(topology=build_topology("ring", W_WORKERS),
                             cfg=GossipConfig(topology="ring"),
                             state=GossipState.init(()))
        if transport == "overlap":
            from repro.comm.overlap import (OverlapConfig, OverlapCtx,
                                            init_overlap_state)
            flat = jax.tree.leaves(jax.tree.map(lambda x: x[0], gtree))
            st = init_overlap_state([x.shape for x in flat],
                                    [x.ndim >= 2 for x in flat], comp)
            return OverlapCtx(cfg=OverlapConfig(n_chunks=2), state=st)
        return None

    ctx0 = inner_ctx()

    def worker(g, m):
        g = jax.tree.map(lambda x: x[0], g)
        m = jax.tree.map(lambda x: x[0], m)
        t_name, t_ctx = transport, ctx0
        if fault_cfg is not None:
            t_name = "faulty"
            t_ctx = FaultCtx(cfg=fault_cfg, step=jnp.int32(step),
                             inner=transport, inner_ctx=t_ctx)
        out = worker_compress_aggregate(
            g, m, jnp.float32(eta), comp, tuple(axes),
            transport=t_name, transport_ctx=t_ctx)
        upd, newm, wire, eff, tel = out[:5]
        if gossip:     # per-worker consensus update: export the lead axis
            upd = jax.tree.map(lambda x: x[None], upd)
        return (upd, jax.tree.map(lambda x: x[None], newm), wire,
                eff[None], jax.tree.map(lambda x: x[None], tel))

    f = shard_map(worker, mesh=mesh, in_specs=(lead, lead),
                  out_specs=(lead if gossip else rep, lead, P(),
                             P(lead_axis), tel_lead),
                  axis_names=set(axes), check_vma=False)
    return jax.jit(f)(gtree, mtree)


def _assert_outputs_equal(ref, got, msg, upd_maxulp=0):
    """Bit-exact everywhere; telemetry <= 8 ulp (module docstring).
    ``upd_maxulp`` relaxes the UPDATES only — needed for gossip, whose
    AdaGossip consensus step ``lr_t`` is fed by a global f32 reduction
    (``err_sq``) whose order XLA does not pin across two different
    programs, so every update coordinate inherits ~1 ulp of lr_t noise;
    gossip EF memory and byte counters stay exactly equal (they never
    touch lr_t)."""
    for name, a, b in zip(("updates", "memory", "wire", "eff",
                           "telemetry"), ref, got):
        maxulp = 8 if name == "telemetry" else (
            upd_maxulp if name == "updates" else 0)
        for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            if maxulp:
                np.testing.assert_array_max_ulp(np.asarray(u),
                                                np.asarray(v),
                                                maxulp=maxulp)
            else:
                np.testing.assert_array_equal(
                    np.asarray(u), np.asarray(v),
                    err_msg=f"{msg}: {name}")


@pytest.mark.parametrize("transport,mesh_shape,axes", TRANSPORT_MESHES)
def test_guarded_decode_bit_exact_on_clean_wire(key, transport,
                                                mesh_shape, axes):
    """The §16 faults-off guarantee, per transport, per mesh: the decode
    verdicts + quarantine change NOTHING on an honest wire."""
    comp = Compressor(gamma=0.05, method="block_topk", block=512,
                      min_compress_size=64, value_bits=8)
    gtree = _worker_tree(key)
    mtree = _mem_tree(key, gtree)
    guarded = _run(gtree, mtree, comp, transport, mesh_shape, axes)
    with guards_disabled():
        legacy = _run(gtree, mtree, comp, transport, mesh_shape, axes)
    _assert_outputs_equal(legacy, guarded,
                          f"{transport}@{mesh_shape} guarded-vs-legacy",
                          upd_maxulp=8 if transport == "gossip" else 0)
    # guards really ran: rows_quarantined exists and counted zero
    assert float(np.sum(np.asarray(guarded[4].rows_quarantined))) == 0.0


@pytest.mark.parametrize("mesh_shape,axes", MESHES)
@pytest.mark.parametrize("transport", ["bucketed", "perleaf"])
def test_faulty_wrapper_inert_outside_window(key, transport, mesh_shape,
                                             axes):
    """A hot campaign whose burst window excludes this step reproduces
    the unwrapped transport bit-for-bit on a real multi-worker mesh."""
    comp = Compressor(gamma=0.05, method="block_topk", block=512,
                      min_compress_size=64, value_bits=8)
    gtree = _worker_tree(key)
    mtree = _mem_tree(key, gtree)
    cfg = FaultConfig(p_bitflip=1.0, p_nonfinite=1.0, start_step=50)
    got = _run(gtree, mtree, comp, transport, mesh_shape, axes,
               fault_cfg=cfg, step=0)
    ref = _run(gtree, mtree, comp, transport, mesh_shape, axes)
    _assert_outputs_equal(ref, got, f"{transport}@{mesh_shape} inert")


def test_campaign_replays_bit_exact_across_mesh_shapes(key):
    """(seed, step, lane, row) keying is mesh-shape independent: the SAME
    campaign on (8,) and (4, 2) corrupts the same rows with the same
    outcome — updates, post-quarantine EF memory, quarantine counts."""
    comp = Compressor(gamma=0.05, method="block_topk", block=512,
                      min_compress_size=64, value_bits=8)
    gtree = _worker_tree(key)
    mtree = _mem_tree(key, gtree)
    cfg = FaultConfig(seed=11, p_nonfinite=0.6, p_zero_row=0.2)
    (m1, a1), (m2, a2) = MESHES
    ref = _run(gtree, mtree, comp, "bucketed", m1, a1, fault_cfg=cfg)
    got = _run(gtree, mtree, comp, "bucketed", m2, a2, fault_cfg=cfg)
    _assert_outputs_equal(ref, got, "campaign replay (8,) vs (4,2)")
    # the campaign really fired, and the guarded decode kept it finite
    assert float(np.sum(np.asarray(ref[4].rows_quarantined))) > 0.0
    for leaf in jax.tree.leaves(ref[:2]):
        assert np.all(np.isfinite(np.asarray(leaf)))
    # and the quarantined aggregate differs from the clean one
    clean = _run(gtree, mtree, comp, "bucketed", m1, a1)
    diff = any(np.any(np.asarray(u) != np.asarray(v))
               for u, v in zip(jax.tree.leaves(ref[0]),
                               jax.tree.leaves(clean[0])))
    assert diff


# ---------------------------------------------------------------------------
# train-step level: bit-exact params + unchanged collective budget
# ---------------------------------------------------------------------------

def _train_setup(transport, max_consecutive_skips=25):
    from repro.configs import get_smoke_config
    from repro.configs.base import OptimizerConfig, RunConfig, ShapeConfig
    from repro.core import ArmijoConfig
    from jax import set_mesh
    from repro.launch.train_step import (build_train_step, init_opt_state,
                                         opt_state_shardings)
    from repro.models import build_model
    from repro.sharding import param_shardings

    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = get_smoke_config("qwen1.5-4b")
    m = build_model(cfg)
    comp = Compressor(gamma=0.1, method="block_topk", block=256,
                      min_compress_size=64)
    run = RunConfig(
        model=cfg, shape=ShapeConfig("t", 32, 8, "train"),
        optimizer=OptimizerConfig(
            kind="csgd_asss", armijo=ArmijoConfig(), compressor=comp,
            transport=transport,
            max_consecutive_skips=max_consecutive_skips))
    # uncommitted: an array made under set_mesh is committed replicated,
    # which the step's batch-over-dp in_shardings reject
    batch = {"tokens": jnp.ones((8, 32), jnp.int32)}
    with set_mesh(mesh):
        params = m.init(jax.random.PRNGKey(0))
        params = jax.device_put(params, param_shardings(params, mesh))
        st = init_opt_state(params, run, 4,
                            stacked_mask=m.stacked_mask(params))
        st = jax.device_put(st, opt_state_shardings(st, params, mesh, run))
        step = build_train_step(m, run, mesh)(params, batch)
    return step, params, st, batch, mesh


def _run_steps(transport, guarded, n=2):
    """n real steps; the unguarded arm is the pre-§16 legacy step —
    verdict layer traced out AND breaker off — so BOTH setup and
    execution (where jit actually traces) sit inside the context."""
    import contextlib

    from jax import set_mesh

    ctx = contextlib.nullcontext() if guarded else guards_disabled()
    with ctx:
        step, params, st, batch, mesh = _train_setup(
            transport, max_consecutive_skips=25 if guarded else 0)
        with set_mesh(mesh):
            for _ in range(n):
                params, st, metrics = step(params, st, batch)
    return params, metrics


@pytest.mark.parametrize("transport", ["bucketed", "gossip"])
def test_train_step_guarded_bit_exact_params(transport):
    """Two full train steps, guarded default vs legacy unguarded: the
    parameter trajectory is bit-identical and the health counters report
    a clean run."""
    p_g, m_g = _run_steps(transport, guarded=True)
    p_u, _ = _run_steps(transport, guarded=False)
    for a, b in zip(jax.tree.leaves(p_g), jax.tree.leaves(p_u)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=transport)
    assert float(m_g["steps_skipped"]) == 0.0
    assert float(m_g["consecutive_skips"]) == 0.0
    assert float(m_g["rows_quarantined"]) == 0.0
    assert float(m_g["last_good_step"]) >= 0.0      # a good step wrote


AG = '"stablehlo.all_gather"'
AR = '"stablehlo.all_reduce"'
CP = '"stablehlo.collective_permute"'


@pytest.mark.parametrize("transport", ["bucketed", "perleaf", "gossip",
                                       "overlap"])
def test_train_step_guards_add_zero_collectives(transport):
    """The HLO pin: per transport, the guarded train step lowers to
    EXACTLY the legacy step's collective counts — the verdict layer and
    the breaker are collective-free by construction."""
    import contextlib

    def lower(guarded):
        ctx = contextlib.nullcontext() if guarded else guards_disabled()
        with ctx:
            step, params, st, batch, _ = _train_setup(
                transport, max_consecutive_skips=25 if guarded else 0)
            return step.lower(params, st, batch).as_text()

    g = lower(True)
    u = lower(False)
    for op in (AG, AR, CP):
        assert g.count(op) == u.count(op), (transport, op, g.count(op),
                                            u.count(op))
