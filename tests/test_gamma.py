"""Per-round gamma controller (core/gamma.py) + the adaptive-compression
golden convergence pairing (DESIGN.md §9).

The golden pairing runs the SAME seeded quadratic under (a) the paper's
fixed gamma = max_gamma and (b) the armijo-coupled adaptive schedule inside
the same budget, and asserts the adaptive run reaches the fixed run's loss
while logging strictly fewer cumulative ``effective_wire_bytes``.  Loss
comparison: within 5% plus an absolute allowance at the trajectory-noise
floor — near interpolation the per-run floor of this stochastic quadratic
jitters by tens of percent run-to-run, so the relative bound alone would be
a coin flip; the absolute term is calibrated to that floor (~2e-4) and
still fails hard if the controller or the ragged wire break convergence
(those failures are orders of magnitude, not percent).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ArmijoConfig, CompressionTelemetry, Compressor,
                        CSGDConfig, GammaControllerConfig, SearchTelemetry,
                        csgd_asss, gamma_init, gamma_update)
from repro.data.synthetic import interpolated_regression
from repro.launch.mesh import make_mesh

# ---------------------------------------------------------------------------
# controller unit tests
# ---------------------------------------------------------------------------


def test_config_validates_schedule():
    with pytest.raises(ValueError):
        GammaControllerConfig(schedule="bogus")


def test_resolve_defaults_from_compressor():
    comp = Compressor(gamma=0.02, max_gamma=0.08)
    g0, gmin, gmax = GammaControllerConfig().resolve(comp)
    assert g0 == 0.02
    assert gmax == 0.08                  # budget = geometry gamma
    assert gmin == pytest.approx(0.02 / 8)
    # explicit gamma_max never exceeds the wire budget
    _, _, gmax2 = GammaControllerConfig(gamma_max=0.5).resolve(comp)
    assert gmax2 == 0.08
    # non-adaptive compressor: budget is plain gamma
    assert GammaControllerConfig().resolve(Compressor(gamma=0.05))[2] == 0.05


def test_resolve_rejects_inverted_band():
    """An explicit gamma_min above the resolved gamma_max used to pass
    resolve() silently and pin every jnp.clip to gamma_max — the user's
    floor was unsatisfiable.  resolve() must raise instead."""
    comp = Compressor(gamma=0.02, max_gamma=0.08)
    with pytest.raises(ValueError, match="gamma_min"):
        GammaControllerConfig(gamma_min=0.5).resolve(comp)
    # the same inversion via an explicit gamma_max under the floor
    with pytest.raises(ValueError, match="gamma_min"):
        GammaControllerConfig(gamma_min=0.06, gamma_max=0.04).resolve(comp)
    # gamma_init goes through resolve, so the train-step init fails too
    with pytest.raises(ValueError, match="gamma_min"):
        gamma_init(GammaControllerConfig(gamma_min=0.5), comp)
    # boundary: gamma_min == gamma_max is a valid (degenerate) band
    g0, gmin, gmax = GammaControllerConfig(
        gamma_min=0.08, gamma_max=0.08).resolve(comp)
    assert g0 == gmin == gmax == 0.08


def test_fixed_schedule_is_constant():
    comp = Compressor(gamma=0.03, max_gamma=0.06)
    cfg = GammaControllerConfig(schedule="fixed")
    g = gamma_init(cfg, comp)
    for step in range(5):
        g = gamma_update(cfg, comp, g, jnp.int32(step))
    assert float(g) == pytest.approx(0.03)


def test_linear_schedule_ramps_to_budget():
    comp = Compressor(gamma=0.02, max_gamma=0.08)
    cfg = GammaControllerConfig(schedule="linear", ramp_steps=100)
    g0 = float(gamma_update(cfg, comp, jnp.float32(0.02), jnp.int32(0)))
    g50 = float(gamma_update(cfg, comp, jnp.float32(0.02), jnp.int32(50)))
    g100 = float(gamma_update(cfg, comp, jnp.float32(0.02), jnp.int32(100)))
    g999 = float(gamma_update(cfg, comp, jnp.float32(0.02), jnp.int32(999)))
    assert g0 == pytest.approx(0.02)
    assert g50 == pytest.approx(0.05)
    assert g100 == pytest.approx(0.08) == g999


def test_armijo_coupled_grow_shrink_and_clip():
    comp = Compressor(gamma=0.04, max_gamma=0.08)
    cfg = GammaControllerConfig(schedule="armijo-coupled", gamma_min=0.01,
                                grow=2.0, shrink=0.5, evals_hi=3.0,
                                evals_lo=2.0, alpha_collapse=0.5)

    def upd(g, alpha, alpha_prev, nev, ema):
        return float(gamma_update(
            cfg, comp, jnp.float32(g), jnp.int32(7),
            search=SearchTelemetry(
                alpha=jnp.float32(alpha), alpha_prev=jnp.float32(alpha_prev),
                n_evals=jnp.float32(nev), n_evals_ema=jnp.float32(ema))))

    # struggling search (eval EMA above threshold) -> grow
    assert upd(0.02, 0.1, 0.1, 4, 4.0) == pytest.approx(0.04)
    # alpha collapse vs the previous round -> grow
    assert upd(0.02, 0.04, 0.1, 2, 1.0) == pytest.approx(0.04)
    # instant accept with low EMA -> shrink
    assert upd(0.02, 0.1, 0.1, 1, 1.0) == pytest.approx(0.01)
    # neutral telemetry -> hold
    assert upd(0.02, 0.1, 0.1, 2, 2.5) == pytest.approx(0.02)
    # clipping into [gamma_min, budget]
    assert upd(0.06, 0.1, 0.1, 5, 5.0) == pytest.approx(0.08)
    assert upd(0.011, 0.1, 0.1, 1, 1.0) == pytest.approx(0.01)


def test_armijo_coupled_requires_telemetry():
    comp = Compressor(gamma=0.04, max_gamma=0.08)
    cfg = GammaControllerConfig(schedule="armijo-coupled")
    with pytest.raises(ValueError):
        gamma_update(cfg, comp, jnp.float32(0.04), jnp.int32(0))


def test_coupled_schedule_rejected_without_armijo():
    with pytest.raises(ValueError):
        CSGDConfig(armijo=None,
                   gamma_ctrl=GammaControllerConfig(
                       schedule="armijo-coupled"))


def _tel(backlog, cosine=1.0):
    return CompressionTelemetry(ef_backlog=jnp.float32(backlog),
                                cosine=jnp.float32(cosine),
                                decode_error=jnp.float32(0.0),
                                eff_gamma=jnp.float32(1.0))


def test_ef_coupled_hysteresis_band():
    """The ef-coupled state machine (DESIGN.md §10): grow above
    target+band, shrink below target-band (cosine healthy), hold inside
    the band, clip into [gamma_min, budget]."""
    comp = Compressor(gamma=0.04, max_gamma=0.08)
    cfg = GammaControllerConfig(schedule="ef-coupled", gamma_min=0.01,
                                grow=2.0, shrink=0.5,
                                ef_target=0.15, ef_band=0.05)

    def upd(g, backlog, cosine=1.0):
        return float(gamma_update(cfg, comp, jnp.float32(g), jnp.int32(3),
                                  compression=_tel(backlog, cosine)))

    assert upd(0.02, 0.30) == pytest.approx(0.04)      # over -> grow
    assert upd(0.02, 0.05) == pytest.approx(0.01)      # slack -> shrink
    assert upd(0.02, 0.15) == pytest.approx(0.02)      # in band -> hold
    # hysteresis edges: strictly-inside-band values hold
    assert upd(0.02, 0.199) == pytest.approx(0.02)
    assert upd(0.02, 0.101) == pytest.approx(0.02)
    # unhealthy cosine blocks the shrink even at low backlog
    assert upd(0.02, 0.05, cosine=-0.5) == pytest.approx(0.02)
    # diverging EF memory (non-finite backlog) always grows
    assert upd(0.02, float("nan")) == pytest.approx(0.04)
    assert upd(0.02, float("inf")) == pytest.approx(0.04)
    # clipping into [gamma_min, budget]
    assert upd(0.06, 0.40) == pytest.approx(0.08)
    assert upd(0.015, 0.01) == pytest.approx(0.01)


def test_ef_coupled_requires_telemetry_and_valid_band():
    comp = Compressor(gamma=0.04, max_gamma=0.08)
    with pytest.raises(ValueError):
        gamma_update(GammaControllerConfig(schedule="ef-coupled"), comp,
                     jnp.float32(0.04), jnp.int32(0))
    with pytest.raises(ValueError, match="hysteresis"):
        GammaControllerConfig(schedule="ef-coupled", ef_target=0.1,
                              ef_band=0.2)


# ---------------------------------------------------------------------------
# golden adaptive convergence (fixed seeds; ISSUE 3 acceptance pairing)
# ---------------------------------------------------------------------------

SEED = 0
D = 256
N = 512
STEPS = 900
BATCH = 32
GMAX = 0.04


def _run(cfg, steps=STEPS, tail=400):
    A, b, _ = interpolated_regression(N, D, feature_std=1.0, seed=SEED)

    def bl(w, idx):
        r = A[idx] @ w - b[idx]
        return jnp.mean(r ** 2)

    @jax.jit
    def full_loss(w):
        return jnp.mean((A @ w - b) ** 2)

    opt = csgd_asss(cfg)
    w = jnp.zeros(D)
    st = opt.init(w)

    @jax.jit
    def step(w, s, idx):
        return opt.step(lambda ww: bl(ww, idx), w, s)

    rng = np.random.default_rng(SEED)
    wbar = np.zeros(D)
    navg = 0
    gammas = []
    for t in range(steps):
        idx = jnp.asarray(rng.integers(0, N, BATCH))
        w, st, aux = step(w, st, idx)
        gammas.append(float(aux.gamma))
        if t >= steps - tail:           # Polyak tail average
            wbar += np.asarray(w)
            navg += 1
    # the run total rides in the state/aux now (ISSUE 4 satellite): one
    # number instead of re-summing the per-step metric
    cum_eff = float(aux.cum_eff_bytes)
    return float(full_loss(jnp.asarray(wbar / navg))), cum_eff, gammas


def test_armijo_coupled_matches_fixed_loss_with_fewer_bytes():
    """The acceptance pairing: armijo-coupled gamma inside the max_gamma
    budget reaches the fixed-gamma=max_gamma loss (5% + noise-floor
    allowance, see module docstring) while logging strictly fewer
    cumulative effective_wire_bytes."""
    fixed = CSGDConfig(
        armijo=ArmijoConfig(sigma=0.1, a_scale=0.3),
        compressor=Compressor(gamma=GMAX, min_compress_size=1))
    loss_f, eff_f, gam_f = _run(fixed)

    coupled = CSGDConfig(
        armijo=ArmijoConfig(sigma=0.1, a_scale=0.3),
        compressor=Compressor(gamma=GMAX, max_gamma=GMAX,
                              min_compress_size=1),
        gamma_ctrl=GammaControllerConfig(schedule="armijo-coupled",
                                         gamma_min=0.03))
    loss_c, eff_c, gam_c = _run(coupled)

    # both converge to the interpolation floor at all
    assert np.isfinite(loss_f) and loss_f < 1e-3, loss_f
    assert np.isfinite(loss_c) and loss_c < 1e-3, loss_c
    # coupled reaches the fixed-run loss: within 5% + the noise floor
    assert loss_c <= 1.05 * loss_f + 5e-4, (loss_c, loss_f)
    # ... while shipping strictly fewer effective bytes inside the SAME
    # static budget (fixed run: effective == budget every round)
    assert eff_c < eff_f, (eff_c, eff_f)
    # and the controller actually moved within [gamma_min, max_gamma]
    assert min(gam_c) >= 0.03 - 1e-6 and max(gam_c) <= GMAX + 1e-6
    assert min(gam_c) < GMAX - 1e-6
    assert all(abs(g - GMAX) < 1e-6 for g in gam_f)


def test_ef_coupled_matches_fixed_loss_with_fewer_bytes():
    """EF-coupled pairing at the SAME healthy starting gamma: couples to
    the compressor's own backlog signal, reaches the fixed-gamma loss
    (same 5% + noise-floor bound as the armijo pairing) while shipping
    strictly fewer cumulative effective bytes — and, unlike armijo-coupled,
    its shrink decisions are grounded in a signal that actually moves with
    gamma (the observability pair in test_golden_convergence.py pins the
    discriminating direction)."""
    fixed = CSGDConfig(
        armijo=ArmijoConfig(sigma=0.1, a_scale=0.3),
        compressor=Compressor(gamma=GMAX, min_compress_size=1))
    loss_f, eff_f, gam_f = _run(fixed)

    coupled = CSGDConfig(
        armijo=ArmijoConfig(sigma=0.1, a_scale=0.3),
        compressor=Compressor(gamma=GMAX, max_gamma=GMAX,
                              min_compress_size=1),
        gamma_ctrl=GammaControllerConfig(schedule="ef-coupled",
                                         gamma_min=0.01))
    loss_c, eff_c, gam_c = _run(coupled)

    assert np.isfinite(loss_f) and loss_f < 1e-3, loss_f
    assert np.isfinite(loss_c) and loss_c < 1e-3, loss_c
    assert loss_c <= 1.05 * loss_f + 5e-4, (loss_c, loss_f)
    assert eff_c < eff_f, (eff_c, eff_f)
    # the controller moved: it spent rounds strictly inside the budget
    assert min(gam_c) < GMAX - 1e-6
    assert max(gam_c) <= GMAX + 1e-6


def test_linear_schedule_strictly_fewer_bytes_same_budget():
    """Coarse-to-fine linear ramp: converges inside the budget with
    strictly fewer effective bytes (cheap sanity pairing for the second
    schedule; bounds loose)."""
    lin = CSGDConfig(
        armijo=ArmijoConfig(sigma=0.1, a_scale=0.3),
        compressor=Compressor(gamma=0.02, max_gamma=GMAX,
                              min_compress_size=1),
        gamma_ctrl=GammaControllerConfig(schedule="linear", ramp_steps=300))
    loss_l, eff_l, gam_l = _run(lin, steps=600, tail=150)
    assert np.isfinite(loss_l) and loss_l < 1e-2, loss_l
    assert gam_l[0] == pytest.approx(0.02)
    assert gam_l[-1] == pytest.approx(GMAX)
    # budget bytes for 600 steps at max gamma would be 600 * (k_max * 8):
    # the ramp must come in strictly under
    k_max = Compressor(gamma=GMAX, min_compress_size=1).k_for(D)
    budget_rows = 600 * _ragged_row_bytes(k_max)
    assert eff_l < budget_rows


def _ragged_row_bytes(k_max):
    """One (1, D)-leaf ragged row at full count: header + 16-bit idx +
    32-bit values (the quadratic's single leaf fits 16-bit indexing)."""
    iw = -(-k_max * 16 // 32)
    return 4 * (1 + iw + k_max)


def test_build_train_step_rejects_coupled_schedule_without_armijo():
    """Launch-path counterpart of the CSGDConfig validation: optimizer
    kinds that never run the Armijo search cannot drive the
    armijo-coupled schedule — fail at build time, not at trace time."""
    import jax
    from repro.configs.base import (OptimizerConfig, RunConfig, ShapeConfig,
                                    smoke_variant)
    from repro.configs import get_config
    from repro.launch.train_step import build_train_step
    from repro.models import build_model

    cfg = smoke_variant(get_config("qwen1.5-4b"))
    mesh = make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])
    run = RunConfig(
        model=cfg, shape=ShapeConfig("t", 64, 4, "train"),
        optimizer=OptimizerConfig(
            kind="nonadaptive",
            gamma_controller=GammaControllerConfig(
                schedule="armijo-coupled")))
    with pytest.raises(ValueError, match="armijo-coupled"):
        build_train_step(build_model(cfg), run, mesh)
