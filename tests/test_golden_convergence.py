"""Golden reproduction of the paper's simulation claim (Fig. 4 regime):
on the quadratic objective, CSGD-ASSS *with* step-size scaling converges
with bounded iterates, while the unscaled variant (a = 1) blows up.

Fixed seeds throughout — this is a golden test: the trajectories are
deterministic and the bounds are loose enough to survive numerics churn
but tight enough that a regression in the scaling logic, the compression
operator, or the EF memory flips the verdict.

The OBSERVABILITY pair at the bottom pins the DESIGN.md §9 caveat as a
regression test: injected over-compression (gamma forced below this
problem's divergence threshold) is invisible to the armijo-coupled
controller — the line search runs on the uncompressed gradient, so it
stalls at gamma_min — while the ef-coupled controller senses the EF
backlog and recovers gamma, restoring convergence (ISSUE 4 acceptance).
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (ArmijoConfig, Compressor, CSGDConfig,
                        GammaControllerConfig, csgd_asss)
from repro.data.synthetic import interpolated_regression
from repro.launch.mesh import make_mesh

SEED = 0
D = 256
N = 512
STEPS = 400
BATCH = 32


def _quadratic_problem():
    """min_w (1/2n)||Aw - b||^2 with interpolation (b in range(A)) — the
    convex quadratic of the paper's simulations."""
    A, b, _ = interpolated_regression(N, D, feature_std=1.0, seed=SEED)

    def batch_loss(w, idx):
        r = A[idx] @ w - b[idx]
        return jnp.mean(r ** 2)

    return batch_loss


def _trajectory(use_scaling: bool, gamma: float, a_scale: float,
                steps: int = STEPS):
    bl = _quadratic_problem()
    cfg = CSGDConfig(
        armijo=ArmijoConfig(sigma=0.1, a_scale=a_scale),
        compressor=Compressor(gamma=gamma, min_compress_size=1),
        use_scaling=use_scaling)
    opt = csgd_asss(cfg)
    w = jnp.zeros(D)
    st = opt.init(w)

    @jax.jit
    def step(w, s, idx):
        return opt.step(lambda ww: bl(ww, idx), w, s)

    rng = np.random.default_rng(SEED)
    sup_norm, loss = 0.0, None
    for t in range(steps):
        idx = jnp.asarray(rng.integers(0, N, BATCH))
        w, st, aux = step(w, st, idx)
        loss = float(aux.loss)
        wn = float(jnp.linalg.norm(w))
        sup_norm = max(sup_norm, wn if np.isfinite(wn) else np.inf)
        if not np.isfinite(loss) or loss > 1e10:
            break
    return loss, sup_norm


def test_scaling_converges_with_bounded_iterates():
    """CSGD-ASSS (a = 3*sigma scaling): loss drops below 0.1 and every
    iterate stays inside a fixed ball — Theorem 1's bounded-trajectory
    behavior on the interpolating quadratic."""
    loss, sup_norm = _trajectory(use_scaling=True, gamma=0.04, a_scale=0.3)
    assert np.isfinite(loss) and loss < 0.1, loss
    assert sup_norm < 50.0, sup_norm


def test_no_scaling_diverges_unbounded_iterates():
    """The same problem and seeds without scaling (a = 1), at the paper's
    Fig. 4 compression level (gamma = 1%): iterates leave any bounded set.
    (The same-gamma controlled pairing is the discriminator test below.)"""
    loss, sup_norm = _trajectory(use_scaling=False, gamma=0.01, a_scale=1.0,
                                 steps=150)
    diverged = (not np.isfinite(loss)) or loss > 100.0 or sup_norm > 1e3
    assert diverged, (loss, sup_norm)


def test_scaling_necessity_is_the_discriminator():
    """Golden pairing: identical gamma, identical seeds — ONLY the scaling
    flag differs, and it alone separates convergence from divergence."""
    gamma = 0.02
    loss_s, sup_s = _trajectory(use_scaling=True, gamma=gamma, a_scale=0.3,
                                steps=250)
    loss_u, sup_u = _trajectory(use_scaling=False, gamma=gamma, a_scale=1.0,
                                steps=250)
    assert np.isfinite(loss_s) and loss_s < 5.0 and sup_s < 50.0, \
        (loss_s, sup_s)
    assert (not np.isfinite(loss_u)) or loss_u > 10.0 * max(loss_s, 1e-6) \
        or sup_u > 20.0 * sup_s, (loss_u, sup_u)


# ---------------------------------------------------------------------------
# controller observability pair (ISSUE 4): injected over-compression
# ---------------------------------------------------------------------------

GMAX = 0.04        # healthy budget (k = 10 of d = 256)
GLOW = 0.004       # injected level: k = 1, below the divergence threshold
                   # for a_scale = 0.3 (gammas <= 0.01 stall at loss >= 1e2
                   # on this seeded problem; 0.04 reaches ~3e-4)
CTRL_STEPS = 900
CTRL_TAIL = 400


def _controller_trajectory(schedule: str):
    """900 steps from an over-compressed start: gamma0 = gamma_min = GLOW
    inside a GMAX ragged budget; the controller must climb out on its own
    signal.  Returns (Polyak-tail loss, per-step gammas, cum eff bytes)."""
    bl = _quadratic_problem()

    @jax.jit
    def full_loss(w):
        A, b, _ = interpolated_regression(N, D, feature_std=1.0, seed=SEED)
        return jnp.mean((A @ w - b) ** 2)

    if schedule == "fixed-max":
        compressor = Compressor(gamma=GMAX, min_compress_size=1)
        ctrl = GammaControllerConfig()
    else:
        compressor = Compressor(gamma=GLOW, max_gamma=GMAX,
                                min_compress_size=1)
        ctrl = GammaControllerConfig(schedule=schedule, gamma_min=GLOW)
    cfg = CSGDConfig(armijo=ArmijoConfig(sigma=0.1, a_scale=0.3),
                     compressor=compressor, gamma_ctrl=ctrl)
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
    for t in range(CTRL_STEPS):
        idx = jnp.asarray(rng.integers(0, N, BATCH))
        w, st, aux = step(w, st, idx)
        gammas.append(float(aux.gamma))
        if t >= CTRL_STEPS - CTRL_TAIL:
            wbar += np.asarray(w)
            navg += 1
    return (float(full_loss(jnp.asarray(wbar / navg))), gammas,
            float(aux.cum_eff_bytes))


def _burst_trajectory(fault_cfg=None, breaker=True, steps=600,
                      tail=200):
    """CSGD-ASSS on the golden quadratic through the REAL wire path —
    ``worker_compress_aggregate`` on a 1-worker mesh, optionally under
    the "faulty" §16 wrapper — with the train step's breaker gating
    (``all_finite`` gate + bit-frozen carried state on a failed check).

    Returns (Polyak-tail full loss, final HealthState, final w).
    """
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from repro.comm.faults import FaultCtx
    from repro.core import armijo_search, next_alpha_max
    from repro.core.dcsgd import worker_compress_aggregate
    from repro.core.health import HealthState, advance_health, all_finite

    bl = _quadratic_problem()
    comp = Compressor(gamma=GMAX, min_compress_size=1)
    acfg = ArmijoConfig(sigma=0.1, a_scale=0.3)
    mesh = make_mesh((1,), ("data",))
    faulty = fault_cfg is not None and fault_cfg.enabled

    def worker(w, m, amax, health, step, idx):
        def loss_fn(ww):
            return bl(ww, idx)

        g = jax.grad(loss_fn)(w)
        res = armijo_search(loss_fn, w, g, amax, acfg)
        t_name, t_ctx = "bucketed", None
        if faulty:
            t_name = "faulty"
            t_ctx = FaultCtx(cfg=fault_cfg, step=step, inner="bucketed")
        out = worker_compress_aggregate(g, m, res.eta, comp, ("data",),
                                        transport=t_name,
                                        transport_ctx=t_ctx)
        upd, m_new, tel = out[0], out[1], out[4]
        step_ok = jnp.isfinite(res.f0) & all_finite(upd)
        cand = (w - upd, m_new, next_alpha_max(res.alpha, acfg))
        if breaker:
            cand = jax.tree.map(lambda a, b: jnp.where(step_ok, a, b),
                                cand, (w, m, amax))
        health = advance_health(health, step_ok, step,
                                tel.rows_quarantined)
        return (*cand, health)

    fn = jax.jit(shard_map(worker, mesh=mesh,
                           in_specs=(P(),) * 6, out_specs=P(),
                           axis_names={"data"}, check_vma=False))

    @jax.jit
    def full_loss(w):
        A, b, _ = interpolated_regression(N, D, feature_std=1.0, seed=SEED)
        return jnp.mean((A @ w - b) ** 2)

    w = jnp.zeros(D)
    m = jnp.zeros(D)
    amax = jnp.float32(acfg.alpha0)
    health = HealthState.init(())
    rng = np.random.default_rng(SEED)
    wbar, navg = np.zeros(D), 0
    for t in range(steps):
        idx = jnp.asarray(rng.integers(0, N, BATCH))
        w, m, amax, health, = fn(w, m, amax, health, jnp.int32(t), idx)
        if t >= steps - tail:
            wbar += np.asarray(w)
            navg += 1
    return float(full_loss(jnp.asarray(wbar / navg))), health, w


BURST = dict(seed=7, p_nonfinite=1.0, start_step=100, n_steps=10)


def test_hostile_burst_quarantine_recovers_within_five_percent():
    """THE §16 acceptance pair, golden-seeded: a 10-step all-NaN wire
    burst mid-run.

    * quarantine on (default): every poisoned row is caught at decode,
      zero (or at most burst-length) steps skip, and the run converges
      to within 5% + noise floor of the fault-free trajectory;
    * breaker-only (quarantine disabled): each poisoned round trips the
      all-finite gate instead — skips bounded by the burst length, state
      freezes through it, and convergence still lands within the band;
    * neither (the unguarded ablation): the same burst is pinned
      divergent/stalled.
    """
    from repro.comm.faults import FaultConfig

    loss_clean, h_clean, _ = _burst_trajectory()
    assert np.isfinite(loss_clean) and loss_clean < 1e-2, loss_clean
    assert int(h_clean.steps_skipped) == 0
    assert float(h_clean.rows_quarantined) == 0.0

    # quarantine arm
    loss_q, h_q, w_q = _burst_trajectory(FaultConfig(**BURST))
    assert np.all(np.isfinite(np.asarray(w_q)))
    assert float(h_q.rows_quarantined) >= 10.0          # the whole burst
    assert int(h_q.steps_skipped) <= 10                 # <= burst length
    assert np.isfinite(loss_q)
    assert loss_q <= 1.05 * loss_clean + 5e-4, (loss_q, loss_clean)

    # breaker-only arm: the gate catches what the verdicts no longer do
    loss_b, h_b, w_b = _burst_trajectory(
        FaultConfig(quarantine=False, **BURST))
    assert np.all(np.isfinite(np.asarray(w_b)))
    assert 1 <= int(h_b.steps_skipped) <= 10
    assert int(h_b.last_good_step) > 110                # resumed after
    assert np.isfinite(loss_b)
    assert loss_b <= 1.05 * loss_clean + 5e-4, (loss_b, loss_clean)


def test_hostile_burst_unguarded_is_pinned_divergent():
    """Ablation pin: the identical burst with quarantine AND breaker off
    poisons the parameters — NaN sticks and the run never recovers."""
    from repro.comm.faults import FaultConfig

    loss_u, _, w_u = _burst_trajectory(
        FaultConfig(quarantine=False, **BURST), breaker=False, steps=200,
        tail=50)
    diverged = (not np.isfinite(loss_u)) \
        or not np.all(np.isfinite(np.asarray(w_u)))
    assert diverged, loss_u


def test_ef_coupled_recovers_injected_over_compression():
    """THE observability pair (DESIGN.md §9 caveat -> §10 fix, pinned):

    * ``armijo-coupled`` cannot see the injected over-compression — its
      telemetry comes from a line search on the *uncompressed* gradient —
      so it stays pinned at gamma_min and the run stalls orders of
      magnitude above the healthy floor;
    * ``ef-coupled`` reads the EF backlog ``||m'||/||g||``, grows gamma
      back into the budget, and restores convergence to within 5% (plus
      the trajectory-noise floor, see tests/test_gamma.py) of the
      fixed-gamma=GMAX baseline.
    """
    loss_fixed, _, _ = _controller_trajectory("fixed-max")
    loss_ef, gam_ef, _ = _controller_trajectory("ef-coupled")
    loss_arm, gam_arm, _ = _controller_trajectory("armijo-coupled")

    # healthy baseline converged
    assert np.isfinite(loss_fixed) and loss_fixed < 1e-3, loss_fixed
    # ef-coupled restored convergence: within 5% + the noise floor
    assert np.isfinite(loss_ef), loss_ef
    assert loss_ef <= 1.05 * loss_fixed + 5e-4, (loss_ef, loss_fixed)
    # ... by actually recovering gamma out of the injected hole
    assert max(gam_ef) >= 0.5 * GMAX, max(gam_ef)
    assert gam_ef[0] <= GLOW + 1e-6
    # armijo-coupled provably did not: gamma never escaped the
    # over-compressed regime (the divergence threshold is ~0.01) ...
    assert max(gam_arm) <= 0.01, max(gam_arm)
    assert gam_arm[-1] <= GLOW + 1e-6, gam_arm[-1]
    # ... and the run stalled far above both the baseline and ef-coupled
    assert (not np.isfinite(loss_arm)) or \
        loss_arm > 100.0 * max(loss_fixed, loss_ef), \
        (loss_arm, loss_fixed, loss_ef)
