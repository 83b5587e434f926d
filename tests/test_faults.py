"""Hostile-wire robustness units (DESIGN.md §16).

Covers the three layers in isolation:

* the seeded injector — ``(seed, step, lane, row)`` determinism, the
  burst window, per-slot targeting, and each fault class's signature;
* the verdict/quarantine layer — what each fault class does to
  ``row_verdict`` and what survives ``quarantine_rows`` (plus the
  fixed-seed drive of the tests/wire_fuzz.py bodies, so the fuzz
  invariants run even without the hypothesis dev extra);
* the step-level breaker — ``HealthState`` arithmetic,
  ``check_divergence`` and the typed :class:`DivergenceError`.

End-to-end composition (faults-off bit-exactness per transport, the HLO
collective pin, the golden convergence-under-burst pair) lives in
tests/distributed/ and tests/test_golden_convergence.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import faults, wire as wire_fmt
from repro.comm.faults import FaultConfig, FaultCtx
from repro.core import Compressor
from repro.core.compression import block_extract_sparse
from repro.core.health import (DivergenceError, HealthState, advance_health,
                               all_finite, check_divergence)
from repro.launch.mesh import make_mesh

from wire_fuzz import (check_garbage_bucket_decode_safe,
                       check_garbage_rows_decode_safe,
                       check_honest_rows_verdict_clean)

D = 1280


def _encoded(seed=0, value_bits=32, adaptive=False, rows=4):
    """Honest (payload, spec) rows to corrupt."""
    comp = Compressor(gamma=0.05, max_gamma=0.05 if adaptive else 0.0,
                      method="block_topk", block=256, min_compress_size=1,
                      value_bits=value_bits)
    spec = wire_fmt.WireSpec.for_row(comp, D)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((rows, D)).astype(np.float32))
    vals, idx = block_extract_sparse(x, comp)
    counts = None
    if spec.ragged:
        counts = jnp.full((rows,), spec.full_count, jnp.int32)
    return wire_fmt.encode_rows(vals, idx, spec, counts=counts), spec


def _corrupt(payload, spec, cfg, step=0, lane=0, rows_per_worker=1):
    with faults.active_faults(cfg, jnp.int32(step)):
        return faults.maybe_corrupt(payload, spec, lane, rows_per_worker)


# ---------------------------------------------------------------------------
# FaultConfig validation + composition rules
# ---------------------------------------------------------------------------

def test_fault_config_validation():
    assert not FaultConfig().enabled
    assert FaultConfig(p_bitflip=0.1).enabled
    with pytest.raises(ValueError, match="p_count"):
        FaultConfig(p_count=1.5)
    with pytest.raises(ValueError, match="p_nonfinite"):
        FaultConfig(p_nonfinite=-0.1)
    with pytest.raises(ValueError, match="start_step"):
        FaultConfig(p_bitflip=0.1, start_step=-1)


def test_optimizer_config_rejects_bad_fault_compositions():
    from repro.configs.base import OptimizerConfig
    on = FaultConfig(p_bitflip=0.1)
    OptimizerConfig(faults=on)                      # baseline composes
    with pytest.raises(ValueError, match="wire to corrupt"):
        OptimizerConfig(kind="sgd", faults=on)
    with pytest.raises(ValueError, match="downlink"):
        OptimizerConfig(faults=on, downlink="compressed")
    with pytest.raises(ValueError, match="shard_local_topk"):
        OptimizerConfig(faults=on, shard_local_topk=True)
    with pytest.raises(ValueError, match="max_consecutive_skips"):
        OptimizerConfig(max_consecutive_skips=-1)


# ---------------------------------------------------------------------------
# the injector
# ---------------------------------------------------------------------------

def test_maybe_corrupt_is_identity_without_context():
    payload, spec = _encoded()
    out = faults.maybe_corrupt(payload, spec, 0, 1)
    assert out is payload                     # Python-level identity


def test_maybe_corrupt_identity_when_rates_zero():
    payload, spec = _encoded()
    out = _corrupt(payload, spec, FaultConfig())
    assert out is payload


def test_injector_deterministic_in_seed_step_lane():
    payload, spec = _encoded()
    cfg = FaultConfig(seed=3, p_bitflip=1.0)
    a = _corrupt(payload, spec, cfg, step=5, lane=2)
    b = _corrupt(payload, spec, cfg, step=5, lane=2)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the draw moves with every key component
    for kw in (dict(step=6, lane=2), dict(step=5, lane=3)):
        c = _corrupt(payload, spec, cfg, **kw)
        assert np.any(np.asarray(c) != np.asarray(a))
    d = _corrupt(payload, spec, FaultConfig(seed=4, p_bitflip=1.0),
                 step=5, lane=2)
    assert np.any(np.asarray(d) != np.asarray(a))


def test_burst_window_and_worker_targeting():
    payload, spec = _encoded(rows=4)
    cfg = FaultConfig(p_zero_row=1.0, start_step=10, n_steps=3)
    for step, hit in ((9, False), (10, True), (12, True), (13, False)):
        out = _corrupt(payload, spec, cfg, step=step)
        changed = np.any(np.asarray(out) != np.asarray(payload))
        assert changed == hit, step
    # rows_per_worker=2: slot 1 is rows 2..3 of the gathered stack
    tgt = FaultConfig(p_zero_row=1.0, worker=1)
    out = np.asarray(_corrupt(payload, spec, tgt, rows_per_worker=2))
    ref = np.asarray(payload)
    np.testing.assert_array_equal(out[:2], ref[:2])
    assert np.all(out[2:] == 0)


def test_bitflip_flips_exactly_one_bit_per_row():
    payload, spec = _encoded()
    out = _corrupt(payload, spec, FaultConfig(p_bitflip=1.0))
    diff = np.asarray(out) ^ np.asarray(payload)
    per_row = np.array([bin(int(w)).count("1")
                        for row in diff for w in row]).reshape(diff.shape)
    np.testing.assert_array_equal(per_row.sum(axis=1),
                                  np.ones(diff.shape[0]))


def test_zero_row_decodes_valid_and_contributes_nothing():
    """The dropped-worker fault: an all-zero row is NOT quarantined — it
    decodes cleanly to zero contribution (DESIGN.md §16 fault table)."""
    payload, spec = _encoded()
    out = _corrupt(payload, spec, FaultConfig(p_zero_row=1.0))
    assert np.all(np.asarray(out) == 0)
    vals, idx = wire_fmt.decode_rows(out, spec)
    assert np.all(np.asarray(vals) == 0.0)
    assert np.all(np.asarray(wire_fmt.row_verdict(out, spec, vals, idx)))


def test_count_fault_trips_verdict():
    payload, spec = _encoded(adaptive=True)
    assert spec.ragged
    out = _corrupt(payload, spec, FaultConfig(p_count=1.0))
    counts = np.asarray(out[:, 0]).astype(np.int64)
    assert np.all((counts == 0xFFFFFFFF)
                  | (counts == 2 * spec.full_count + 7))
    vals, idx = wire_fmt.decode_rows(out, spec)
    verdict = wire_fmt.row_verdict(out, spec, vals, idx)
    assert not np.any(np.asarray(verdict))
    qv, qi = wire_fmt.quarantine_rows(vals, idx, verdict)
    assert np.all(np.asarray(qv) == 0.0) and np.all(np.asarray(qi) == 0)


@pytest.mark.parametrize("value_bits", [4, 8, 16, 32])
def test_nonfinite_fault_trips_verdict_each_width(value_bits):
    payload, spec = _encoded(value_bits=value_bits)
    out = _corrupt(payload, spec, FaultConfig(p_nonfinite=1.0))
    vals, idx = wire_fmt.decode_rows(out, spec)
    verdict = wire_fmt.row_verdict(out, spec, vals, idx)
    assert not np.any(np.asarray(verdict))
    qv, _ = wire_fmt.quarantine_rows(vals, idx, verdict)
    assert np.all(np.isfinite(np.asarray(qv)))
    assert np.all(np.asarray(qv) == 0.0)


# ---------------------------------------------------------------------------
# guards context
# ---------------------------------------------------------------------------

def test_guards_active_default_and_overrides():
    assert faults.guards_active()             # defensive decode is default
    with faults.guards_disabled():
        assert not faults.guards_active()
    assert faults.guards_active()
    with faults.active_faults(FaultConfig(p_bitflip=0.5), 0):
        assert faults.guards_active()
        assert faults.injection_active()
    with faults.active_faults(
            FaultConfig(p_bitflip=0.5, quarantine=False), 0):
        assert not faults.guards_active()     # the no-guards ablation arm
    assert not faults.injection_active()


# ---------------------------------------------------------------------------
# the "faulty" wrapper transport
# ---------------------------------------------------------------------------

def test_faulty_wrapper_rejects_self_and_missing_inner_ctx():
    from repro.comm.transport import get_transport
    t = get_transport("faulty")
    assert t.stateful
    cfg = FaultConfig(p_bitflip=0.5)
    with pytest.raises(ValueError, match="wrap itself"):
        t.exchange(None, None, None, None, None, ("data",), None, 1,
                   ctx=FaultCtx(cfg=cfg, step=0, inner="faulty"))
    with pytest.raises(ValueError, match="inner_ctx"):
        t.exchange(None, None, None, None, None, ("data",), None, 1,
                   ctx=FaultCtx(cfg=cfg, step=0, inner="overlap"))


def _one_worker_exchange(transport, transport_ctx, comp, seed=0):
    """Jitted 1-worker worker_compress_aggregate under shard_map."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.core.dcsgd import worker_compress_aggregate

    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.standard_normal(D).astype(np.float32))
    m = jnp.asarray(rng.standard_normal(D).astype(np.float32)) * 0.5
    mesh = make_mesh((1,), ("data",))
    f = shard_map(
        lambda gg, mm: worker_compress_aggregate(
            gg, mm, jnp.float32(0.25), comp, ("data",),
            transport=transport, transport_ctx=transport_ctx),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(),
        axis_names={"data"}, check_vma=False)
    out = jax.jit(f)(g, m)
    return (g, m) + tuple(out)


@pytest.mark.parametrize("inner", ["bucketed", "perleaf"])
def test_faulty_wrapper_out_of_window_is_bit_exact(inner):
    """A campaign whose burst window excludes this step must reproduce the
    plain transport bit-for-bit (the masked injector adds no noise)."""
    comp = Compressor(gamma=0.05, method="block_topk", block=256,
                      min_compress_size=1)
    ctx = FaultCtx(cfg=FaultConfig(p_bitflip=1.0, p_nonfinite=1.0,
                                   start_step=100),
                   step=jnp.int32(0), inner=inner)
    got = _one_worker_exchange("faulty", ctx, comp)
    want = _one_worker_exchange(inner, None, comp)
    assert got[-1] == ()                     # stateless inner padded
    for a, b in zip(jax.tree.leaves(got[:-1]), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_faulty_wrapper_nonfinite_quarantines_own_row():
    """p_nonfinite=1.0 on a single worker: every payload row (all its
    own) is quarantined — the mean update is exactly zero and the leaf's
    EF residual freezes at the old memory (own-row freeze)."""
    comp = Compressor(gamma=0.05, method="block_topk", block=256,
                      min_compress_size=1)
    ctx = FaultCtx(cfg=FaultConfig(p_nonfinite=1.0),
                   step=jnp.int32(0), inner="bucketed")
    g, m, upd, m_new, wire_bytes, eff, tel, _ = \
        _one_worker_exchange("faulty", ctx, comp)
    assert np.all(np.asarray(upd) == 0.0)
    np.testing.assert_array_equal(np.asarray(m_new), np.asarray(m))
    assert float(tel.rows_quarantined) >= 1.0
    # control: the clean exchange moves both
    _, _, upd0, m0_new, *_ = _one_worker_exchange("bucketed", None, comp)
    assert np.any(np.asarray(upd0) != 0.0)
    assert np.any(np.asarray(m0_new) != np.asarray(m))


# ---------------------------------------------------------------------------
# health state + circuit breaker
# ---------------------------------------------------------------------------

def test_health_state_init_shapes():
    h = HealthState.init((4,))
    assert h.steps_skipped.shape == (4,)
    assert h.last_good_step.dtype == jnp.int32
    assert np.all(np.asarray(h.last_good_step) == -1)
    a = HealthState.init((4,), abstract=True)
    assert jax.tree.structure(a) == jax.tree.structure(h)
    for c, s in zip(jax.tree.leaves(h), jax.tree.leaves(a)):
        assert tuple(c.shape) == tuple(s.shape) and c.dtype == s.dtype


def test_advance_health_sequences():
    h = HealthState.init(())
    # good, good, skip, skip, good
    for step, ok, quar in ((0, True, 0.0), (1, True, 3.0), (2, False, 0.0),
                           (3, False, 2.0), (4, True, 0.0)):
        h = advance_health(h, jnp.bool_(ok), jnp.int32(step),
                           jnp.float32(quar))
    assert int(h.steps_skipped) == 2
    assert int(h.consecutive_skips) == 0      # reset by the final good step
    assert int(h.last_good_step) == 4
    assert float(h.rows_quarantined) == 5.0
    # an unbroken skip run accumulates
    for step in (5, 6, 7):
        h = advance_health(h, jnp.bool_(False), jnp.int32(step),
                           jnp.float32(0.0))
    assert int(h.consecutive_skips) == 3
    assert int(h.last_good_step) == 4


def test_all_finite():
    ok = {"a": jnp.ones((3,)), "b": jnp.zeros((2, 2))}
    assert bool(all_finite(ok))
    assert bool(all_finite(ok, jnp.float32(1.0)))
    assert not bool(all_finite(ok, {"c": jnp.array([1.0, jnp.nan])}))
    assert not bool(all_finite({"c": jnp.array([jnp.inf])}))


def test_check_divergence_raises_typed_error():
    m = {"step": 40, "consecutive_skips": 25, "last_good_step": 15}
    check_divergence(m, 0)                    # breaker disabled: no-op
    check_divergence(m, 26)                   # under threshold: no-op
    check_divergence({}, 25)                  # keys absent: no-op
    with pytest.raises(DivergenceError) as ei:
        check_divergence(m, 25)
    e = ei.value
    assert isinstance(e, RuntimeError)
    assert (e.step, e.last_good_step, e.consecutive, e.threshold) == \
        (40, 15, 25, 25)
    assert "last good step was 15" in str(e)


# ---------------------------------------------------------------------------
# fixed-seed drive of the fuzz bodies (tests/wire_fuzz.py) — the same
# invariants the hypothesis tier explores, runnable without the dev extra
# ---------------------------------------------------------------------------

_FUZZ_GRID = [(s, 193 + 331 * s, blk, vb, adaptive, method)
              for s, (blk, vb, adaptive, method) in enumerate(
                  [(64, 4, True, "block_topk"), (256, 8, False, "topk"),
                   (1024, 16, True, "block_topk"), (256, 32, False,
                                                    "block_topk"),
                   (64, 32, True, "topk"), (256, 4, False, "topk")])]


@pytest.mark.parametrize("seed,d,block,vb,adaptive,method", _FUZZ_GRID)
def test_garbage_rows_fixed_seeds(seed, d, block, vb, adaptive, method):
    check_garbage_rows_decode_safe(seed, d, block, vb, adaptive, method)
    check_honest_rows_verdict_clean(seed, d, block, vb, adaptive, method)


@pytest.mark.parametrize("seed", range(4))
def test_garbage_buckets_fixed_seeds(seed):
    check_garbage_bucket_decode_safe(seed, [4, 8, 16, 32][seed], seed % 2)
