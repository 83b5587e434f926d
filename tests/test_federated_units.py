"""Single-device unit tests for the federated cohort tier (DESIGN.md §13).

The multi-worker parity/convergence suite lives in tests/federated/ under
the 8-virtual-device harness; everything here runs collective-free with
``dp_axes=None`` (W=1) so it rides tier-1.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FederatedConfig, OptimizerConfig
from repro.core.compression import Compressor
from repro.fed.aggregate import (scatter_with_support, support_weighted_mean,
                                 validate_aggregation, zero_averaged_mean)
from repro.fed.clients import (cohort_compress_aggregate, init_client_state,
                               local_participation, per_client_wire_bytes)
from repro.fed.sampling import ZeroParticipationError, participation_mask
from repro.launch.mesh import make_mesh


def _comp(**kw):
    base = dict(gamma=0.25, method="topk", min_compress_size=64,
                use_kernel=False)
    base.update(kw)
    return Compressor(**base)


# ---------------------------------------------------------------------------
# aggregation semantics
# ---------------------------------------------------------------------------

def test_support_counts_only_nonzero_senders():
    """Support is per-coordinate nonzero-sender count: decode-to-zero
    entries (ragged tails, padding clamps) and non-participants are
    invisible."""
    L, d = 1, 8
    vals = jnp.asarray([[[2.0, 0.0, 4.0]],     # client 0: coord 0, (3 is a
                        [[2.0, 6.0, 0.0]],     #   zero pad), coord 5
                        [[9.0, 9.0, 9.0]]])    # client 2: NOT participating
    idx = jnp.asarray([[[0, 3, 5]],
                       [[0, 3, 5]],
                       [[0, 3, 5]]], dtype=jnp.int32)
    w = jnp.asarray([1.0, 1.0, 0.0])
    total, support = scatter_with_support(vals, idx, w, L, d)
    np.testing.assert_array_equal(
        np.asarray(support[0]), [2, 0, 0, 1, 0, 1, 0, 0])
    np.testing.assert_array_equal(
        np.asarray(total[0]), [4, 0, 0, 6, 0, 4, 0, 0])
    sup = support_weighted_mean(total, support)
    np.testing.assert_array_equal(
        np.asarray(sup[0]), [2, 0, 0, 6, 0, 4, 0, 0])
    # the zero-averaging reference shrinks by the implicit zeros
    zav = zero_averaged_mean(total, jnp.float32(2.0))
    np.testing.assert_array_equal(
        np.asarray(zav[0]), [2, 0, 0, 3, 0, 2, 0, 0])


def test_support_mean_never_divides_by_zero():
    total = jnp.zeros((2, 16))
    support = jnp.zeros((2, 16))
    out = support_weighted_mean(total, support)
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_validate_aggregation():
    validate_aggregation("support")
    validate_aggregation("mean")
    with pytest.raises(ValueError, match="unknown aggregation"):
        validate_aggregation("median")


# ---------------------------------------------------------------------------
# cohort exchange (W=1, collective-free)
# ---------------------------------------------------------------------------

def _cohort_inputs(key, C=6, shapes=((3, 300), (2000,), (40,))):
    grads, mem = {}, {}
    for i, s in enumerate(shapes):
        k1, k2, key = jax.random.split(key, 3)
        grads[f"l{i}"] = jax.random.normal(k1, (C,) + s)
        mem[f"l{i}"] = jax.random.normal(k2, (C,) + s) * 0.1
    return grads, mem


def test_cohort_ef_identity_participants_frozen_nonparticipants(key):
    """The EF contract per client: for participants, decode(own payload) +
    m' == m + eta*g (within quantization-free f32 exactness on the topk
    path); non-participants' memory is bit-frozen."""
    C = 6
    grads, mem = _cohort_inputs(key, C)
    comp = _comp()
    part = jnp.asarray([1, 1, 0, 1, 0, 1], jnp.float32)
    eta = jnp.float32(0.3)
    updates, new_mem, wire, eff = cohort_compress_aggregate(
        grads, mem, eta, comp, None, part)
    for name in grads:
        g, m, m2 = grads[name], mem[name], new_mem[name]
        acc = np.asarray(m, np.float32) + 0.3 * np.asarray(g, np.float32)
        for c in range(C):
            if part[c] == 0:
                np.testing.assert_array_equal(np.asarray(m2[c]),
                                              np.asarray(m[c]))
            else:
                # sent = acc - m'  must hold coordinatewise (value_bits=32
                # topk: kept values ride the wire exactly)
                sent = acc[c] - np.asarray(m2[c])
                d = sent.size
                if comp.ships_dense(np.prod(g.shape[1:])) or \
                        g[c].size < comp.min_compress_size:
                    # dense lane: whole acc ships, memory zeroed
                    np.testing.assert_allclose(np.asarray(m2[c]), 0.0)
                else:
                    kept = np.count_nonzero(sent.reshape(-1))
                    assert 0 < kept <= sent.size
                    # unsent coordinates keep the full acc in memory
                    unsent = sent.reshape(-1) == 0.0
                    np.testing.assert_allclose(
                        np.asarray(m2[c]).reshape(-1)[unsent],
                        acc[c].reshape(-1)[unsent], atol=1e-6)


def test_cohort_wire_accounting(key):
    """wire == n_participants * per-client static bytes; eff <= wire and
    counts only participants."""
    from repro.comm.bucket import build_bucket_plan

    C = 4
    grads, mem = _cohort_inputs(key, C)
    comp = _comp(max_gamma=0.5)       # adaptive: ragged eff < static wire
    shapes = [g.shape[1:] for g in jax.tree.leaves(grads)]
    stacked = [len(s) >= 2 for s in shapes]
    plan = build_bucket_plan(shapes, stacked, comp)
    per_client = per_client_wire_bytes(plan)
    for n_on in (1, 3, 4):
        part = jnp.asarray([1.0] * n_on + [0.0] * (C - n_on))
        _, _, wire, eff = cohort_compress_aggregate(
            grads, mem, 0.1, comp, None, part,
            gamma_c=jnp.full((C,), 0.25))
        assert float(wire) == n_on * per_client
        assert 0.0 < float(eff) <= float(wire)


def test_cohort_heterogeneous_gamma(key):
    """Per-client gamma_c yields per-client k_t: lower-gamma clients ship
    fewer coordinates (visible in the per-client EF sparsity) while all
    payloads still ride the one fixed-shape exchange."""
    C = 4
    grads, mem = _cohort_inputs(key, C, shapes=((4096,),))
    mem = jax.tree.map(jnp.zeros_like, mem)
    comp = _comp(gamma=0.05, max_gamma=0.5)
    gamma_c = jnp.asarray([0.05, 0.1, 0.3, 0.5])
    part = jnp.ones((C,), jnp.float32)
    _, new_mem, _, _ = cohort_compress_aggregate(
        grads, mem, 1.0, comp, None, part, gamma_c=gamma_c)
    resid = np.asarray(new_mem["l0"])
    kept = [int(np.count_nonzero(np.asarray(grads["l0"][c]) - resid[c]))
            for c in range(C)]
    assert kept[0] < kept[1] < kept[2] < kept[3]
    for c, gt in enumerate(np.asarray(gamma_c)):
        assert abs(kept[c] - round(gt * 4096)) <= 2


def test_cohort_update_is_support_weighted(key):
    """The aggregated update equals the NumPy support-weighted mean of the
    per-client sent tensors."""
    C = 3
    grads, mem = _cohort_inputs(key, C, shapes=((1500,),))
    comp = _comp()
    part = jnp.asarray([1.0, 1.0, 1.0])
    eta = jnp.float32(0.5)
    updates, new_mem, _, _ = cohort_compress_aggregate(
        grads, mem, eta, comp, None, part)
    acc = (np.asarray(mem["l0"], np.float32)
           + 0.5 * np.asarray(grads["l0"], np.float32))
    sent = acc - np.asarray(new_mem["l0"], np.float32)   # (C, d)
    supp = np.count_nonzero(sent, axis=0).astype(np.float32)
    expect = sent.sum(0) / np.maximum(supp, 1.0)
    np.testing.assert_allclose(np.asarray(updates["l0"]), expect,
                               atol=1e-6)


def test_cohort_rejects_bad_mask_shape(key):
    grads, mem = _cohort_inputs(key, C=4)
    with pytest.raises(ValueError, match="participation"):
        cohort_compress_aggregate(grads, mem, 0.1, _comp(), None,
                                  jnp.ones((3,)))


def test_cohort_vmap_matches_loop(key):
    """The vmap'd cohort encode is bit-identical to running each client
    through the same selection alone (vmap is batching, not math)."""
    C = 3
    grads, mem = _cohort_inputs(key, C, shapes=((2048,), (50,)))
    comp = _comp()
    part_all = jnp.ones((C,), jnp.float32)
    up_all, nm_all, _, _ = cohort_compress_aggregate(
        grads, mem, 0.2, comp, None, part_all)
    for c in range(C):
        g1 = jax.tree.map(lambda x: x[c:c + 1], grads)
        m1 = jax.tree.map(lambda x: x[c:c + 1], mem)
        _, nm1, _, _ = cohort_compress_aggregate(
            g1, m1, 0.2, comp, None, jnp.ones((1,), jnp.float32))
        for k in grads:
            np.testing.assert_array_equal(np.asarray(nm_all[k][c]),
                                          np.asarray(nm1[k][0]))


# ---------------------------------------------------------------------------
# client state + config plumbing
# ---------------------------------------------------------------------------

def test_init_client_state_shapes():
    params = {"w": jnp.zeros((4, 32)), "b": jnp.zeros((7,))}
    opt = OptimizerConfig(kind="csgd_asss",
                          compressor=Compressor(gamma=0.1),
                          federated=FederatedConfig(n_clients=6))
    st = init_client_state(params, opt, 6)
    assert st.memory["w"].shape == (6, 4, 32)
    assert st.memory["b"].shape == (6, 7)
    assert st.gamma.shape == st.rounds.shape == st.alpha.shape == (6,)
    assert st.rounds.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(st.rounds), 0)
    ab = init_client_state(params, opt, 6, abstract=True)
    assert ab.memory["w"].shape == (6, 4, 32)


def test_local_participation_identity_without_dp():
    m = jnp.asarray([1.0, 0.0, 1.0])
    np.testing.assert_array_equal(np.asarray(local_participation(m, None, 3)),
                                  np.asarray(m))


def test_federated_config_validation():
    assert not FederatedConfig().enabled
    assert FederatedConfig(n_clients=8).enabled
    with pytest.raises(ValueError):
        FederatedConfig(n_clients=4, clients_per_round=5)
    with pytest.raises(ValueError):
        FederatedConfig(n_clients=4, sampling="roulette")
    with pytest.raises(ValueError):
        FederatedConfig(n_clients=4, aggregation="median")
    with pytest.raises(ValueError):
        FederatedConfig(n_clients=4, participation_rate=1.5)
    with pytest.raises(ValueError):
        OptimizerConfig(kind="csgd_asss",
                        compressor=Compressor(gamma=0.1),
                        transport="gossip",
                        federated=FederatedConfig(n_clients=4))


def test_build_train_step_rejects_bad_fed_combos():
    from repro.configs import get_smoke_config
    from repro.configs.base import RunConfig, ShapeConfig
    from repro.launch.train_step import build_train_step
    from repro.models import build_model

    cfg = get_smoke_config("paper-lm-100m")
    model = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])
    shape = ShapeConfig("t", 16, 4, "train")

    def run(**opt_kw):
        base = dict(kind="csgd_asss", compressor=Compressor(gamma=0.1),
                    federated=FederatedConfig(n_clients=4))
        base.update(opt_kw)
        return RunConfig(model=cfg, shape=shape,
                         optimizer=OptimizerConfig(**base))

    with pytest.raises(ValueError, match="compressing"):
        build_train_step(model, run(kind="sgd"), mesh)
    with pytest.raises(ValueError, match="local_steps"):
        build_train_step(model, run(local_steps=2), mesh)
    with pytest.raises(ValueError, match="shard_local_topk"):
        build_train_step(model, run(shard_local_topk=True), mesh)
    with pytest.raises(ValueError, match="schedule"):
        from repro.core.gamma import GammaControllerConfig
        build_train_step(model, run(
            compressor=Compressor(gamma=0.1, max_gamma=0.3),
            gamma_controller=GammaControllerConfig(
                schedule="ef-coupled")), mesh)


def test_sampling_fixed_no_replacement():
    m = participation_mask(32, 5, seed=1, mode="fixed", clients_per_round=8)
    assert m.shape == (32,) and int(m.sum()) == 8
    with pytest.raises(ValueError, match="out of range"):
        participation_mask(4, 0, mode="fixed", clients_per_round=9)
    with pytest.raises(ZeroParticipationError):
        participation_mask(8, 0, mode="bernoulli", rate=0.0)
