"""``shard_local_topk`` on a real (4, 2) device mesh.

The selection runs in a nested shard_map manual over 'model' inside the
train step's manual-dp region.  With identical per-worker batches, one
``shard_local_topk`` DCSGD-ASSS step equals the single-device CSGD-ASSS
step (the dense, paper-faithful reference), through the packed wire
exchange.
"""
import jax
import jax.numpy as jnp
import numpy as np

from jax import set_mesh
from repro.configs import get_smoke_config
from repro.configs.base import OptimizerConfig, RunConfig, ShapeConfig
from repro.core import ArmijoConfig, Compressor, CSGDConfig, csgd_asss
from repro.launch.train_step import (build_train_step, init_opt_state,
                                     opt_state_shardings)
from repro.models import build_model
from repro.sharding import param_shardings
from repro.launch.mesh import make_mesh
from jax.sharding import NamedSharding, PartitionSpec as P


def _dist_step(m, cfg, run, mesh, params0, one_batch, n_workers=4):
    with set_mesh(mesh):
        # the train step donates params/opt_state and device_put may alias
        # params0's buffers — give every call its own copy
        params0 = jax.tree.map(jnp.array, params0)
        params = jax.device_put(params0, param_shardings(params0, mesh))
        batch = {"tokens": jnp.tile(one_batch["tokens"], (n_workers, 1))}
        st = init_opt_state(params, run, n_workers)
        st = jax.device_put(st, opt_state_shardings(st, params, mesh, run))
        batch = jax.device_put(batch, jax.tree.map(
            lambda _: NamedSharding(mesh, P("data")), batch))
        step = build_train_step(m, run, mesh)(params, batch)
        return step(params, st, batch)


def test_shard_local_topk_matches_single_device(key):
    """Same data on every worker: shard_local_topk DCSGD == single-node
    CSGD-ASSS (block_topk selection; block-aligned shards keep the
    block-local operator identical across the nesting)."""
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = get_smoke_config("qwen1.5-4b")
    m = build_model(cfg)
    comp = Compressor(gamma=0.1, method="block_topk", block=256,
                      min_compress_size=64)
    arm = ArmijoConfig()
    run = RunConfig(
        model=cfg, shape=ShapeConfig("t", 32, 8, "train"),
        optimizer=OptimizerConfig(kind="csgd_asss", armijo=arm,
                                  compressor=comp, shard_local_topk=True))
    params0 = m.init(jax.random.PRNGKey(0))
    one = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                        cfg.vocab_size)}
    p_dist, st_dist, metrics = _dist_step(m, cfg, run, mesh, params0, one)

    opt = csgd_asss(CSGDConfig(armijo=arm, compressor=comp))
    p0 = m.init(jax.random.PRNGKey(0))
    s0 = opt.init(p0)
    p_ref, s_ref, aux = opt.step(lambda p: m.loss(p, one)[0], p0, s0)

    da = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))), p_dist, p_ref)
    worst = max(jax.tree.leaves(da))
    assert worst < 5e-3, worst
    assert abs(float(metrics["loss"]) - float(aux.loss)) < 1e-4
    assert float(metrics["wire_bytes"]) > 0


def test_shard_local_topk_equals_global_selection(key):
    """shard_local_topk=True and =False produce the SAME step while the
    model shards are block-aligned — parity between the two
    build_train_step variants."""
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = get_smoke_config("qwen1.5-4b")
    m = build_model(cfg)
    comp = Compressor(gamma=0.1, method="block_topk", block=256,
                      min_compress_size=64)

    def mkrun(flag):
        return RunConfig(
            model=cfg, shape=ShapeConfig("t", 32, 8, "train"),
            optimizer=OptimizerConfig(kind="csgd_asss",
                                      armijo=ArmijoConfig(),
                                      compressor=comp,
                                      shard_local_topk=flag))

    params0 = m.init(jax.random.PRNGKey(0))
    one = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                        cfg.vocab_size)}
    p_loc, _, m_loc = _dist_step(m, cfg, mkrun(True), mesh, params0, one)
    p_glob, _, m_glob = _dist_step(m, cfg, mkrun(False), mesh, params0, one)
    for a, b in zip(jax.tree.leaves(p_loc), jax.tree.leaves(p_glob)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)
    assert float(m_loc["wire_bytes"]) == float(m_glob["wire_bytes"])
