"""HLO-level regression pins for the bucketed transport (DESIGN.md §11):
a future change must not silently fall back to per-leaf collectives.

Two levels:

* the lowered exchange itself — ``stablehlo.all_gather`` count equals the
  plan's gather count (ONE flat gather for every bucket; <= 2 by
  construction) and the ``pmean`` family (``stablehlo.all_reduce``) for
  dense small leaves is exactly 1;
* the lowered 8-virtual-device TRAIN STEP — the all-gather budget stays
  <= 2 end to end (metric pmeans lower to all_reduce, so the all_gather
  count is attributable to the exchange alone).

The per-leaf reference transport is lowered side by side to prove the
counters really count (it shows one collective per leaf).

The gossip transport (DESIGN.md §12) gets the same treatment: the lowered
exchange must contain exactly ``degree`` ``stablehlo.collective_permute``
ops (one neighbor ``ppermute`` per graph edge class — ring: 2) and ZERO
all_gathers / all_reduces: dense small leaves ride the permuted payload
buffer, and a global collective sneaking back in would silently
re-centralize the serverless path.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.comm.bucket import build_bucket_plan
from repro.core import Compressor
from repro.core.dcsgd import worker_compress_aggregate
from repro.launch.mesh import make_mesh

W_WORKERS = 8
AG = '"stablehlo.all_gather"'
AR = '"stablehlo.all_reduce"'
CP = '"stablehlo.collective_permute"'


def _tree(key):
    ks = jax.random.split(key, 5)
    return {
        "w": jax.random.normal(ks[0], (2, 2048)),
        "v": jax.random.normal(ks[1], (3000,)),
        "t": jax.random.normal(ks[2], (50,)),        # dense
        "u": jax.random.normal(ks[3], (40,)),        # dense
        "big": jax.random.normal(ks[4], (70000,)),   # 32-bit idx (topk)
    }


def _lower_exchange(tree, comp, transport):
    mesh = make_mesh((W_WORKERS,), ("data",))
    mem = jax.tree.map(jnp.zeros_like, tree)
    spec = jax.tree.map(lambda _: P(), tree)
    f = shard_map(
        functools.partial(worker_compress_aggregate, comp=comp,
                          dp_axes=("data",), transport=transport),
        mesh=mesh, in_specs=(spec, spec, P()),
        out_specs=(spec, spec, P(), P(), P()), axis_names={"data"},
        check_vma=False)
    return jax.jit(f).lower(tree, mem, jnp.float32(0.1)).as_text()


@pytest.mark.parametrize("method", ["block_topk", "topk"])
def test_exchange_collective_counts(key, method):
    comp = Compressor(gamma=0.05, method=method, block=512,
                      min_compress_size=64, value_bits=8)
    tree = _tree(key)
    leaves = jax.tree.leaves(tree)
    plan = build_bucket_plan([x.shape for x in leaves],
                             [x.ndim >= 2 for x in leaves], comp)
    n_compressed, n_dense = len(plan.compressed_ids), len(plan.dense_ids)
    assert n_compressed == 3 and n_dense == 2
    assert len(plan.buckets) <= 2

    txt = _lower_exchange(tree, comp, "bucketed")
    # ONE flat all_gather for every bucket; ONE pmean for every dense leaf
    assert txt.count(AG) == plan.n_gathers == 1, txt.count(AG)
    assert txt.count(AR) == 1, txt.count(AR)

    # the reference schedule shows the counters have teeth: one collective
    # per leaf (this is the regression the bucketed path deletes)
    ref = _lower_exchange(tree, comp, "perleaf")
    assert ref.count(AG) == n_compressed
    assert ref.count(AR) == n_dense


def _lower_downlink_exchange(tree, comp):
    from repro.comm.downlink import (DownlinkCtx, DownlinkResult,
                                     DownlinkState, init_downlink_state)

    mesh = make_mesh((W_WORKERS,), ("data",))
    leaves = jax.tree.leaves(tree)
    dls = init_downlink_state([x.shape for x in leaves],
                              [x.ndim >= 2 for x in leaves], comp,
                              comp.gamma)
    mem = jax.tree.map(jnp.zeros_like, tree)
    spec = jax.tree.map(lambda _: P(), tree)
    dl_spec = DownlinkState(memory=P(), gamma=P())

    # the server state must be a traced INPUT (same reasoning as the
    # overlap lowering below: a constant would let XLA fold the EF away)
    def worker(g, m, eta, s):
        return worker_compress_aggregate(
            g, m, eta, comp, ("data",),
            downlink_ctx=DownlinkCtx(state=s))

    f = shard_map(
        worker, mesh=mesh,
        in_specs=(spec, spec, P(), dl_spec),
        out_specs=(spec, spec, P(), P(), P(),
                   DownlinkResult(dl_spec, P(), P())),
        axis_names={"data"}, check_vma=False)
    return jax.jit(f).lower(tree, mem, jnp.float32(0.1), dls).as_text()


@pytest.mark.parametrize("method", ["block_topk", "topk"])
def test_downlink_exchange_adds_no_collective(key, method):
    """DESIGN.md §15: the compressed downlink is a physically simulated
    server — replicated recompute, ZERO additional collectives.  The
    lowered downlink exchange must show the exact same budget as the
    plain bucketed exchange: ONE flat all_gather, ONE dense pmean."""
    comp = Compressor(gamma=0.05, method=method, block=512,
                      min_compress_size=64, value_bits=8)
    tree = _tree(key)
    txt = _lower_downlink_exchange(tree, comp)
    assert txt.count(AG) == 1, txt.count(AG)
    assert txt.count(AR) == 1, txt.count(AR)
    assert txt.count(CP) == 0, txt.count(CP)


def _lower_gossip(tree, comp, topology):
    from repro.comm.gossip import GossipConfig, GossipCtx, GossipState
    from repro.comm.topology import build_topology

    mesh = make_mesh((W_WORKERS,), ("data",))
    ctx = GossipCtx(topology=build_topology(topology, W_WORKERS),
                    cfg=GossipConfig(topology=topology),
                    state=GossipState.init(()))
    mem = jax.tree.map(jnp.zeros_like, tree)
    spec = jax.tree.map(lambda _: P(), tree)
    f = shard_map(
        functools.partial(worker_compress_aggregate, comp=comp,
                          dp_axes=("data",), transport="gossip",
                          transport_ctx=ctx),
        mesh=mesh, in_specs=(spec, spec, P()),
        out_specs=(spec, spec, P(), P(), P(), P()), axis_names={"data"},
        check_vma=False)
    return jax.jit(f).lower(tree, mem, jnp.float32(0.1)).as_text(), ctx


@pytest.mark.parametrize("topology,degree", [("ring", 2), ("exp", 5)])
def test_gossip_exchange_collective_counts(key, topology, degree):
    """Gossip lowers to exactly `degree` neighbor permutes and NOTHING
    global — no all_gather, no all_reduce (dense leaves ride the permuted
    payload buffer instead of a pmean)."""
    comp = Compressor(gamma=0.05, method="block_topk", block=512,
                      min_compress_size=64, value_bits=8)
    txt, ctx = _lower_gossip(_tree(key), comp, topology)
    assert ctx.topology.degree == degree
    assert txt.count(CP) == degree, txt.count(CP)
    assert txt.count(AG) == 0, txt.count(AG)
    assert txt.count(AR) == 0, txt.count(AR)


def _lower_overlap(tree, comp, n_chunks, delay, mesh_shape=(W_WORKERS,),
                   axes=("data",)):
    from repro.comm.overlap import (OverlapConfig, OverlapCtx,
                                    init_overlap_state)

    mesh = make_mesh(mesh_shape, axes)
    leaves = jax.tree.leaves(tree)
    st = init_overlap_state([x.shape for x in leaves],
                            [x.ndim >= 2 for x in leaves], comp,
                            abstract=True)
    st = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), st)
    cfg = OverlapConfig(n_chunks=n_chunks, delay=delay)
    mem = jax.tree.map(jnp.zeros_like, tree)
    spec = jax.tree.map(lambda _: P(), tree)

    # the carried state must be a traced INPUT: a zero-constant closure
    # would let XLA fold the delay=1 dense pmean away
    def worker(g, m, eta, s):
        return worker_compress_aggregate(
            g, m, eta, comp, axes, transport="overlap",
            transport_ctx=OverlapCtx(cfg=cfg, state=s))

    f = shard_map(
        worker, mesh=mesh,
        in_specs=(spec, spec, P(), jax.tree.map(lambda _: P(), st)),
        out_specs=(spec, spec, P(), P(), P(),
                   jax.tree.map(lambda _: P(), st)),
        axis_names=set(axes), check_vma=False)
    return jax.jit(f).lower(tree, mem, jnp.float32(0.1), st).as_text()


@pytest.mark.parametrize("mesh_shape,axes,n_chunks", [
    ((W_WORKERS,), ("data",), 1),
    ((W_WORKERS,), ("data",), 3),
    ((W_WORKERS,), ("data",), 7),
    ((4, 2), ("pod", "data"), 1),
    ((4, 2), ("pod", "data"), 3),
])
@pytest.mark.parametrize("delay", [0, 1])
def test_overlap_exchange_collective_counts(key, mesh_shape, axes,
                                            n_chunks, delay):
    """The overlap transport lowers to EXACTLY the ring schedule's
    ``collective_permute`` count (``n_permutes``: chunk count x (W-1) per
    dp axis, ring of rings) with ZERO all_gathers for the compressed
    leaves — a flat gather sneaking back in would serialize the exchange
    — and ONE all_reduce (the dense-leaf pmean)."""
    from repro.comm.ring import n_permutes

    comp = Compressor(gamma=0.05, method="block_topk", block=512,
                      min_compress_size=64, value_bits=8)
    tree = _tree(key)
    leaves = jax.tree.leaves(tree)
    plan = build_bucket_plan([x.shape for x in leaves],
                             [x.ndim >= 2 for x in leaves], comp)
    txt = _lower_overlap(tree, comp, n_chunks, delay, mesh_shape, axes)
    want = n_permutes(mesh_shape, plan.total_words, n_chunks)
    assert txt.count(CP) == want, (txt.count(CP), want)
    assert txt.count(AG) == 0, txt.count(AG)
    assert txt.count(AR) == 1, txt.count(AR)


def test_exchange_all_dense_single_pmean(key):
    comp = Compressor(method="none")
    txt = _lower_exchange(_tree(key), comp, "bucketed")
    assert txt.count(AG) == 0
    assert txt.count(AR) == 1


def _lower_train_step(transport, downlink="dense"):
    from repro.configs import get_smoke_config
    from repro.configs.base import (OptimizerConfig, RunConfig,
                                    ShapeConfig)
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
        optimizer=OptimizerConfig(kind="csgd_asss", armijo=ArmijoConfig(),
                                  compressor=comp, transport=transport,
                                  downlink=downlink))
    # uncommitted: an array made under set_mesh is committed replicated,
    # which the step's batch-over-dp in_shardings reject
    batch = {"tokens": jnp.zeros((8, 32), jnp.int32)}
    with set_mesh(mesh):
        params = m.init(jax.random.PRNGKey(0))
        params = jax.device_put(params, param_shardings(params, mesh))
        st = init_opt_state(params, run, 4)
        st = jax.device_put(st, opt_state_shardings(st, params, mesh, run))
        step = build_train_step(m, run, mesh)(params, batch)
        txt = step.lower(params, st, batch).as_text()
    leaves = jax.tree.leaves(params)
    plan = build_bucket_plan([x.shape for x in leaves],
                             [x.ndim >= 2 for x in leaves], comp)
    return txt, plan


def test_train_step_all_gather_budget():
    """End to end: the lowered train step's all_gather count equals the
    bucket gather count (<= 2), where the per-leaf schedule pays one per
    compressed leaf."""
    txt, plan = _lower_train_step("bucketed")
    assert 1 <= txt.count(AG) == plan.n_gathers <= 2, txt.count(AG)

    ref, _ = _lower_train_step("perleaf")
    assert ref.count(AG) == len(plan.compressed_ids) > 2


def test_train_step_downlink_keeps_collective_budget():
    """End to end with ``downlink="compressed"``: the all_gather budget
    stays the bucket plan's gather count (<= 2) — the server-side
    recompression must never lower to an extra collective."""
    txt, plan = _lower_train_step("bucketed", downlink="compressed")
    assert 1 <= txt.count(AG) == plan.n_gathers <= 2, txt.count(AG)


# ---------------------------------------------------------------------------
# federated cohort tier (DESIGN.md §13): vmap must not multiply collectives
# ---------------------------------------------------------------------------

def _lower_cohort(key, comp, n_clients):
    from repro.fed.clients import cohort_compress_aggregate

    mesh = make_mesh((W_WORKERS,), ("data",))
    C = n_clients // W_WORKERS
    base = _tree(key)
    tree = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (C,) + x.shape), base)
    mem = jax.tree.map(jnp.zeros_like, tree)
    spec = jax.tree.map(lambda _: P(), tree)
    f = shard_map(
        lambda g, m, part: cohort_compress_aggregate(
            g, m, jnp.float32(0.1), comp, ("data",), part),
        mesh=mesh, in_specs=(spec, spec, P()),
        out_specs=(jax.tree.map(lambda _: P(), base), spec, P(), P()),
        axis_names={"data"}, check_vma=False)
    part = jnp.ones((n_clients,), jnp.float32)
    return jax.jit(f).lower(tree, mem, part).as_text()


@pytest.mark.parametrize("n_clients", [8, 64, 256])
def test_cohort_exchange_collective_counts(key, n_clients):
    """The vmap'd cohort exchange keeps the O(1) bucketed schedule:
    exactly ONE all_gather (every client's payload in one fixed-shape
    block) and exactly ONE all_reduce (dense leaves + the eff-bytes
    counter), INDEPENDENT of how many clients each worker simulates."""
    comp = Compressor(gamma=0.05, method="topk", min_compress_size=64,
                      value_bits=8, use_kernel=False)
    txt = _lower_cohort(key, comp, n_clients)
    assert txt.count(AG) == 1, txt.count(AG)
    assert txt.count(AR) == 1, txt.count(AR)


def _lower_fed_train_step(n_clients):
    from repro.configs import get_smoke_config
    from repro.configs.base import (FederatedConfig, OptimizerConfig,
                                    RunConfig, ShapeConfig)
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
                      min_compress_size=64, use_kernel=False)
    run = RunConfig(
        model=cfg, shape=ShapeConfig("t", 32, n_clients, "train"),
        optimizer=OptimizerConfig(
            kind="csgd_asss", armijo=ArmijoConfig(), compressor=comp,
            federated=FederatedConfig(n_clients=n_clients)))
    batch = {"tokens": jnp.zeros((n_clients, 1, 32), jnp.int32),
             "participation": jnp.ones((n_clients,), jnp.float32)}
    with set_mesh(mesh):
        params = m.init(jax.random.PRNGKey(0))
        params = jax.device_put(params, param_shardings(params, mesh))
        st = init_opt_state(params, run, 4)
        st = jax.device_put(st, opt_state_shardings(st, params, mesh, run))
        step = build_train_step(m, run, mesh)(params, batch)
        txt = step.lower(params, st, batch).as_text()
    leaves = jax.tree.leaves(params)
    plan = build_bucket_plan([x.shape for x in leaves],
                             [x.ndim >= 2 for x in leaves], comp)
    return txt, plan


def test_fed_train_step_collective_budget():
    """End to end: the federated train step's all_gather count equals the
    bucket plan's gather count — the SAME budget as the plain dp step —
    and stays constant as the cohort grows 8 -> 32 clients (vmap width
    never becomes collective count)."""
    txt8, plan = _lower_fed_train_step(8)
    txt32, _ = _lower_fed_train_step(32)
    assert 1 <= txt8.count(AG) == plan.n_gathers <= 2, txt8.count(AG)
    assert txt32.count(AG) == txt8.count(AG)
    assert txt32.count(AR) == txt8.count(AR)
