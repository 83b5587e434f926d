"""Distributed train/serve step builders — DCSGD-ASSS as a first-class
feature of the runtime (DESIGN.md §4).

``build_train_step``: jit(shard_map(worker_fn)) where the shard_map is
*manual* over the data-parallel axes (('pod','data') or ('data',)) and
*auto* over 'model' (XLA partitions the tensor-parallel math from the
parameter shardings + in-model hints).  Each dp worker:

  grads  <- value_and_grad over its microbatches           (model-axis TP)
  alpha  <- Armijo search on its first microbatch          (Algorithm 3 l.4)
  update <- compress + all-gather sparse over dp axes      (Algorithm 3 l.5-7)

Per-worker optimizer state (EF memory m^(k), alpha^(k)) is stored with a
leading worker axis sharded over the dp mesh axes — per-chip EF memory is
P/|model| as analyzed in DESIGN.md §6.

``build_prefill_step`` / ``build_decode_step``: pure-pjit serving steps with
batch-over-dp, seq-sharded KV caches (flash-decode combine emerges from the
partitioner; see models/attention.py).
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm.downlink import (DownlinkCtx, DownlinkState,
                                 init_downlink_state)
from repro.comm.faults import FaultCtx, active_faults
from repro.comm.gossip import GossipCtx, GossipState
from repro.comm.overlap import OverlapCtx, OverlapState, init_overlap_state
from repro.comm.topology import build_topology
from repro.configs.base import RunConfig, ShapeConfig
from repro.core.armijo import armijo_search, next_alpha_max, tree_sqnorm
from repro.core.dcsgd import dense_aggregate, worker_compress_aggregate
from repro.core.gamma import gamma_init, gamma_update
from repro.core.health import HealthState, advance_health, all_finite
from repro.core.telemetry import CompressionTelemetry, SearchTelemetry
from repro.fed.clients import (ClientState, cohort_compress_aggregate,
                               init_client_state, local_participation)
from repro.models.registry import Model
from repro.sharding import cache_pspecs, dp_axes_of, param_pspecs

PyTree = Any


class GossipOptState(NamedTuple):
    """Per-worker serverless-mode state (DESIGN.md §12).

    Under ``transport="gossip"`` there is no global mean, so workers'
    models genuinely diverge between rounds: each worker's parameters
    live here with a leading (W,) axis (the replicated ``params`` input
    stays frozen as the common initialization), next to the AdaGossip
    consensus state carried exactly like ``CompressionTelemetry``.
    """

    params: PyTree           # per-worker models: leaves (W, *param_shape)
    state: GossipState       # (W,) adaptive-consensus (v, lr)


class DistOptState(NamedTuple):
    step: jax.Array          # () int32
    alpha_prev: jax.Array    # (W,) per-worker carried step size
    memory: PyTree           # per-worker EF: leaves (W, *param_shape)
    n_evals_ema: jax.Array   # (W,)
    gamma: jax.Array         # (W,) per-worker per-round compression level
    telemetry: CompressionTelemetry  # (W,) per-worker compression health
    cum_eff_bytes: jax.Array         # () cumulative worker-mean eff bytes
    gossip: Any = ()         # GossipOptState under transport="gossip"
    fed: Any = ()            # ClientState when federated.n_clients > 0
                             # (leaves (n_clients, ...) over the dp axes)
    overlap: Any = ()        # OverlapState under transport="overlap"
                             # (leaves (W, ...): carried payload buffers)
    downlink: Any = ()       # DownlinkState under downlink="compressed"
                             # (leaves (W, ...): replicated server EF/gamma)
    velocity: Any = ()       # Nesterov buffers under kind="acgd"
                             # (per-worker leaves (W, *param_shape) f32)
    health: Any = ()         # HealthState: (W,) step-skip / quarantine
                             # counters (DESIGN.md §16) — always present
                             # for new states; () only in legacy pytrees


def _n_workers(mesh) -> int:
    return math.prod(mesh.shape[a] for a in dp_axes_of(mesh))


def init_opt_state(params: PyTree, run_cfg: RunConfig, n_workers: int,
                   abstract: bool = False,
                   stacked_mask: PyTree | None = None) -> DistOptState:
    """``stacked_mask``: the per-leaf stacked flags the worker will pass to
    ``worker_compress_aggregate`` — REQUIRED to match for
    ``transport="overlap"`` (the carried payload buffer's geometry derives
    from it; ``build_train_step`` passes ``model.stacked_mask``).  The
    default reproduces dcsgd's ``leaf.ndim >= 2`` fallback."""
    opt = run_cfg.optimizer
    ef_dt = jnp.dtype(opt.ef_dtype)

    def mem_leaf(p):
        shape = (n_workers,) + tuple(p.shape)
        if abstract:
            return jax.ShapeDtypeStruct(shape, ef_dt)
        return jnp.zeros(shape, ef_dt)

    def gossip_params_leaf(p):
        shape = (n_workers,) + tuple(p.shape)
        if abstract:
            return jax.ShapeDtypeStruct(shape, p.dtype)
        # every worker starts at the common initialization
        return jnp.broadcast_to(p[None], shape).astype(p.dtype)

    fed_on = opt.federated.enabled
    needs_mem = opt.kind in ("csgd_asss", "nonadaptive", "acgd") \
        and not fed_on
    needs_gossip = needs_mem and opt.transport == "gossip"
    needs_overlap = needs_mem and opt.transport == "overlap"
    needs_downlink = needs_mem and opt.downlink == "compressed"
    needs_vel = opt.kind == "acgd" and not fed_on
    mk = (lambda s, d: jax.ShapeDtypeStruct(s, d)) if abstract else \
        (lambda s, d: jnp.zeros(s, d))

    def broadcast_w(tree):
        """(W,)-leading replication of an unbatched carried-state pytree
        (the gossip_params_leaf convention)."""
        return jax.tree.map(
            lambda x: (jax.ShapeDtypeStruct((n_workers,) + x.shape, x.dtype)
                       if abstract else
                       jnp.broadcast_to(x[None], (n_workers,) + x.shape)),
            tree)

    def flat_geometry():
        flat_p, treedef = jax.tree.flatten(params)
        flags = ([leaf.ndim >= 2 for leaf in flat_p]
                 if stacked_mask is None
                 else treedef.flatten_up_to(stacked_mask))
        return [p.shape for p in flat_p], flags

    overlap = ()
    if needs_overlap:
        shapes, flags = flat_geometry()
        overlap = broadcast_w(init_overlap_state(
            shapes, flags, opt.compressor, abstract=abstract))
    downlink = ()
    if needs_downlink:
        shapes, flags = flat_geometry()
        downlink = broadcast_w(init_downlink_state(
            shapes, flags, opt.compressor,
            opt.downlink_gamma.resolve(opt.compressor)[0],
            abstract=abstract))
    return DistOptState(
        step=mk((), jnp.int32),
        alpha_prev=(mk((n_workers,), jnp.float32) if abstract else
                    jnp.full((n_workers,), opt.armijo.alpha0, jnp.float32)),
        memory=jax.tree.map(mem_leaf, params) if needs_mem else (),
        n_evals_ema=mk((n_workers,), jnp.float32),
        gamma=(mk((n_workers,), jnp.float32) if abstract else
               jnp.full((n_workers,),
                        gamma_init(opt.gamma_controller, opt.compressor),
                        jnp.float32)),
        telemetry=CompressionTelemetry.init((n_workers,), abstract=abstract),
        cum_eff_bytes=mk((), jnp.float32),
        gossip=(GossipOptState(
            params=jax.tree.map(gossip_params_leaf, params),
            state=GossipState.init((n_workers,), abstract=abstract))
            if needs_gossip else ()),
        fed=(init_client_state(params, opt, opt.federated.n_clients,
                               abstract=abstract) if fed_on else ()),
        overlap=overlap,
        downlink=downlink,
        velocity=(jax.tree.map(
            lambda p: (jax.ShapeDtypeStruct((n_workers,) + tuple(p.shape),
                                            jnp.float32) if abstract else
                       jnp.zeros((n_workers,) + tuple(p.shape),
                                 jnp.float32)),
            params) if needs_vel else ()),
        health=HealthState.init((n_workers,), abstract=abstract),
    )


def opt_state_shardings(opt_state: DistOptState, params: PyTree, mesh,
                        run_cfg: RunConfig) -> DistOptState:
    """Shardings: leading dim over dp axes; remaining dims follow the param
    pspec (so m^(k) is model-sharded exactly like its parameter)."""
    dp = dp_axes_of(mesh)
    dp_spec = dp if len(dp) > 1 else dp[0]
    pspecs = param_pspecs(params)
    mem_kind = ("pinned_host" if run_cfg.optimizer.ef_host_offload
                else None)

    def mem_sh(ps):
        return NamedSharding(mesh, P(dp_spec, *ps), memory_kind=mem_kind)

    rep = NamedSharding(mesh, P())
    vec = NamedSharding(mesh, P(dp_spec))
    return DistOptState(
        step=rep,
        alpha_prev=vec,
        memory=(jax.tree.map(mem_sh, pspecs)
                if opt_state.memory != () else ()),
        n_evals_ema=vec,
        gamma=vec,
        telemetry=jax.tree.map(lambda _: vec, opt_state.telemetry),
        cum_eff_bytes=rep,
        gossip=(GossipOptState(
            params=jax.tree.map(
                lambda ps: NamedSharding(mesh, P(dp_spec, *ps)), pspecs),
            state=GossipState(v=vec, lr=vec))
            if opt_state.gossip != () else ()),
        fed=(ClientState(
            memory=jax.tree.map(mem_sh, pspecs),
            gamma=vec, rounds=vec, alpha=vec)
            if opt_state.fed != () else ()),
        overlap=(jax.tree.map(lambda _: vec, opt_state.overlap)
                 if opt_state.overlap != () else ()),
        downlink=(jax.tree.map(lambda _: vec, opt_state.downlink)
                  if opt_state.downlink != () else ()),
        velocity=(jax.tree.map(
            lambda ps: NamedSharding(mesh, P(dp_spec, *ps)), pspecs)
            if opt_state.velocity != () else ()),
        health=jax.tree.map(lambda _: vec, opt_state.health),
    )


# ===========================================================================
# train step
# ===========================================================================

def build_train_step(model: Model, run_cfg: RunConfig, mesh):
    """Returns (train_step, in_shardings, batch_sharding).

    train_step(params, opt_state, batch) -> (params, opt_state, metrics).
    """
    opt = run_cfg.optimizer
    if opt.gamma_controller.schedule == "armijo-coupled" and \
            opt.kind not in ("csgd_asss", "sls"):
        raise ValueError(
            f"gamma schedule 'armijo-coupled' needs an Armijo-searching "
            f"optimizer (csgd_asss | sls), got kind={opt.kind!r} — use "
            f"'fixed' or 'linear'")
    if opt.gamma_controller.schedule == "ef-coupled" and \
            opt.kind not in ("csgd_asss", "nonadaptive", "acgd"):
        raise ValueError(
            f"gamma schedule 'ef-coupled' needs a compressing optimizer "
            f"(csgd_asss | nonadaptive | acgd) — only those produce the "
            f"CompressionTelemetry it couples to, got kind={opt.kind!r}")
    dp = dp_axes_of(mesh)
    dp_spec = dp if len(dp) > 1 else dp[0]
    W = _n_workers(mesh)
    micro = run_cfg.microbatches

    compressing = opt.kind in ("csgd_asss", "nonadaptive", "acgd")
    acgd_mode = opt.kind == "acgd"
    # hostile-wire robustness (DESIGN.md §16).  faults×downlink,
    # faults×shard_local_topk and faults×dense are rejected by
    # OptimizerConfig.__post_init__ before we ever get here.
    faults_on = opt.faults.enabled
    breaker_on = opt.max_consecutive_skips > 0

    def wrap_faults(t_name, t_ctx, step):
        """Route the exchange through the 'faulty' wrapper transport when
        a fault campaign is configured — the wrapper corrupts the gathered
        payload rows, then runs the inner transport unchanged."""
        if not faults_on:
            return t_name, t_ctx
        return "faulty", FaultCtx(cfg=opt.faults, step=step,
                                  inner=t_name, inner_ctx=t_ctx)
    if acgd_mode and opt.local_steps > 1:
        raise ValueError(
            "kind='acgd' does not compose with local_steps > 1 — the "
            "Nesterov velocity advances once per exchange round, not per "
            "local Armijo step (use kind='csgd_asss' for local steps)")

    downlink_mode = opt.downlink == "compressed"
    if downlink_mode:
        # (gossip/overlap/federated composition is already rejected by
        # OptimizerConfig.__post_init__ — no replicated global aggregate)
        if not compressing:
            raise ValueError(
                f"downlink='compressed' re-compresses the compressed "
                f"exchange's aggregate (DESIGN.md §15); kind={opt.kind!r} "
                f"ships a dense pmean with no server to simulate — use "
                f"csgd_asss | nonadaptive | acgd")
        if opt.shard_local_topk:
            raise ValueError(
                "downlink='compressed' does not compose with "
                "shard_local_topk — the server plan is the whole-gradient "
                "bucket geometry, not a model shard's")
        if opt.local_steps > 1:
            raise ValueError(
                "downlink='compressed' does not compose with "
                "local_steps > 1 yet — the local-steps exchange applies "
                "the dense mean delta directly")

    gossip_mode = opt.transport == "gossip"
    topo = None
    if gossip_mode:
        if opt.kind not in ("csgd_asss", "nonadaptive"):
            raise ValueError(
                f"transport 'gossip' needs a compressing optimizer "
                f"(csgd_asss | nonadaptive), got kind={opt.kind!r}")
        if len(dp) != 1:
            raise ValueError(
                f"transport 'gossip' needs a single data-parallel mesh "
                f"axis (lax.ppermute is single-axis), got {dp!r} — use a "
                f"('data', 'model') mesh, not multi_pod")
        if opt.local_steps > 1:
            raise ValueError(
                "transport 'gossip' does not compose with local_steps > 1")
        if opt.shard_local_topk:
            raise ValueError(
                "transport 'gossip' does not compose with shard_local_topk")
        topo = build_topology(opt.gossip.topology, W)

    overlap_mode = opt.transport == "overlap"
    if overlap_mode:
        if opt.kind not in ("csgd_asss", "nonadaptive"):
            raise ValueError(
                f"transport 'overlap' needs a compressing optimizer "
                f"(csgd_asss | nonadaptive), got kind={opt.kind!r}")
        if opt.shard_local_topk:
            raise ValueError(
                "transport 'overlap' does not compose with "
                "shard_local_topk (the carried payload geometry is the "
                "whole-gradient bucket plan, not a model-shard's)")

    # local_steps consumes exactly one microbatch per local step — a
    # build-time contract, not a traced assert (asserts vanish under
    # `python -O` and would otherwise fail late inside tracing)
    if opt.local_steps > 1 and opt.kind in ("csgd_asss", "nonadaptive") \
            and micro != opt.local_steps:
        raise ValueError(
            f"local_steps={opt.local_steps} requires microbatches == "
            f"local_steps (got microbatches={micro}): each local Armijo "
            f"step consumes exactly one microbatch of the global batch")

    fed = opt.federated
    fed_mode = fed.enabled
    if fed_mode:
        # (transport="gossip" is already rejected by OptimizerConfig)
        if opt.kind not in ("csgd_asss", "nonadaptive"):
            raise ValueError(
                f"federated cohort simulation needs a compressing "
                f"optimizer (csgd_asss | nonadaptive), got "
                f"kind={opt.kind!r}")
        if opt.local_steps > 1:
            raise ValueError(
                "federated cohort simulation does not compose with "
                "local_steps > 1")
        if opt.shard_local_topk:
            raise ValueError(
                "federated cohort simulation does not compose with "
                "shard_local_topk")
        if micro > 1:
            raise ValueError(
                "federated cohort simulation does not compose with "
                "microbatches > 1 (each client IS a batch row group)")
        if fed.n_clients % W:
            raise ValueError(
                f"n_clients={fed.n_clients} must divide evenly over the "
                f"{W} dp workers (each worker vmaps n_clients/W clients)")
        if opt.gamma_controller.schedule not in ("fixed", "linear"):
            raise ValueError(
                f"per-client gamma controllers support the 'fixed' and "
                f"'linear' schedules (each client sees only its own "
                f"participation counter, not the coupled telemetry), got "
                f"{opt.gamma_controller.schedule!r}")

    def local_loss(params, batch):
        loss, _ = model.loss(params, batch)
        return loss

    def _local_steps_worker(params, opt_state, batch, mem, alpha_prev, ema,
                            gamma_prev, tel_prev):
        """H local Armijo-SGD steps, then ONE EF-compressed exchange of the
        accumulated model delta (paper §V future work; Qsparse-local [8])."""
        H = run_cfg.optimizer.local_steps
        # micro == H is enforced at build time (build_train_step above)
        mbs = jax.tree.map(
            lambda x: x.reshape(H, x.shape[0] // H, *x.shape[1:]), batch)

        def one(carry, mb):
            p_loc, amax, ev = carry
            with jax.named_scope("csgd_grad"):
                loss, g = jax.value_and_grad(local_loss)(p_loc, mb)
                gsq = tree_sqnorm(g)
            with jax.named_scope("csgd_armijo"):
                res = armijo_search(lambda p: local_loss(p, mb), p_loc, g,
                                    amax, opt.armijo, f0=loss,
                                    grad_sqnorm=gsq)
            eta = opt.armijo.a_scale * res.alpha
            with jax.named_scope("csgd_apply"):
                p_loc = jax.tree.map(
                    lambda p, gg: (p.astype(jnp.float32) - eta
                                   * gg.astype(jnp.float32)).astype(p.dtype),
                    p_loc, g)
            return (p_loc, next_alpha_max(res.alpha, opt.armijo),
                    ev + res.n_evals.astype(jnp.float32)), (loss, res.alpha)

        amax0 = next_alpha_max(alpha_prev, opt.armijo)
        (p_end, amax_f, evals), (losses, alphas) = jax.lax.scan(
            one, (params, amax0, jnp.float32(0.0)), mbs)

        # per-round gamma from the H-step aggregate search telemetry (or
        # last round's compression telemetry for the ef-coupled schedule)
        gamma_t = gamma_update(
            opt.gamma_controller, opt.compressor, gamma_prev,
            opt_state.step,
            search=SearchTelemetry(alpha=alphas[-1], alpha_prev=alpha_prev,
                                   n_evals=evals / H, n_evals_ema=ema),
            compression=tel_prev)

        # accumulated local update (already eta-scaled) -> EF + exchange
        delta = jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            params, p_end)
        smask = model.stacked_mask(params)
        if overlap_mode:
            # THE overlap seam (DESIGN.md §14): the exchange ships the
            # carried previous-segment payload, so its ring runs
            # concurrently with this segment's H local Armijo-SGD steps
            ctx = OverlapCtx(
                cfg=opt.overlap,
                state=jax.tree.map(lambda x: x[0], opt_state.overlap))
            t_name, t_ctx = wrap_faults(opt.transport, ctx, opt_state.step)
            updates, new_mem, wire, eff_wire, tel, ov_state = \
                worker_compress_aggregate(
                    delta, mem, jnp.float32(1.0), opt.compressor, dp,
                    stacked_mask=smask, gamma_t=gamma_t,
                    transport=t_name, transport_ctx=t_ctx)
            new_overlap = jax.tree.map(lambda x: x[None], ov_state)
        elif faults_on:
            t_name, t_ctx = wrap_faults(opt.transport, None, opt_state.step)
            updates, new_mem, wire, eff_wire, tel, _ = \
                worker_compress_aggregate(
                    delta, mem, jnp.float32(1.0), opt.compressor, dp,
                    stacked_mask=smask, gamma_t=gamma_t,
                    transport=t_name, transport_ctx=t_ctx)
            new_overlap = opt_state.overlap
        else:
            updates, new_mem, wire, eff_wire, tel = \
                worker_compress_aggregate(
                    delta, mem, jnp.float32(1.0), opt.compressor, dp,
                    stacked_mask=smask, gamma_t=gamma_t,
                    transport=opt.transport)
            new_overlap = opt_state.overlap
        with jax.named_scope("csgd_apply"):
            new_params = jax.tree.map(
                lambda p, u: (p.astype(jnp.float32) - u).astype(p.dtype),
                params, updates)
        cum_eff = opt_state.cum_eff_bytes + jax.lax.pmean(eff_wire, dp)
        metrics = {
            "loss": jax.lax.pmean(jnp.mean(losses), dp),
            "grad_sqnorm": jnp.float32(0.0),
            "alpha": jax.lax.pmean(alphas[-1], dp),
            "n_evals": jax.lax.pmean(evals / H, dp),
            "wire_bytes": jax.lax.pmean(wire, dp),
            "effective_wire_bytes": jax.lax.pmean(eff_wire, dp),
            "cum_effective_wire_bytes": cum_eff,
            "gamma": jax.lax.pmean(gamma_t, dp),
            "ef_backlog": jax.lax.pmean(tel.ef_backlog, dp),
            "ef_cosine": jax.lax.pmean(tel.cosine, dp),
        }
        if overlap_mode:
            metrics["staleness"] = jax.lax.pmean(
                jnp.float32(opt.overlap.delay)
                * opt_state.overlap.seeded[0], dp)

        # ---- step-level circuit breaker (DESIGN.md §16) -----------------
        with jax.named_scope("csgd_apply"):
            health = jax.tree.map(lambda x: x[0], opt_state.health)
            step_ok = jnp.isfinite(metrics["loss"]) & all_finite(updates)
            if breaker_on:
                new_params = jax.tree.map(
                    lambda a, b: jnp.where(step_ok, a, b), new_params,
                    params)
            new_health = advance_health(health, step_ok, opt_state.step,
                                        tel.rows_quarantined)
        metrics["steps_skipped"] = \
            new_health.steps_skipped.astype(jnp.float32)
        metrics["consecutive_skips"] = \
            new_health.consecutive_skips.astype(jnp.float32)
        metrics["last_good_step"] = \
            new_health.last_good_step.astype(jnp.float32)
        metrics["rows_quarantined"] = new_health.rows_quarantined

        new_state = DistOptState(
            step=opt_state.step + 1,
            alpha_prev=(amax_f / opt.armijo.omega)[None],
            memory=jax.tree.map(lambda x: x[None], new_mem),
            n_evals_ema=(0.9 * ema + 0.1 * evals / H)[None],
            gamma=gamma_t[None],
            telemetry=jax.tree.map(lambda x: x[None], tel),
            cum_eff_bytes=cum_eff,
            overlap=new_overlap,
            health=jax.tree.map(lambda x: x[None], new_health),
        )
        if breaker_on:
            frozen = new_state._replace(
                alpha_prev=opt_state.alpha_prev,
                memory=opt_state.memory,
                n_evals_ema=opt_state.n_evals_ema,
                gamma=opt_state.gamma,
                telemetry=opt_state.telemetry,
                overlap=opt_state.overlap)
            with jax.named_scope("csgd_apply"):
                new_state = jax.tree.map(
                    lambda a, b: jnp.where(step_ok, a, b), new_state, frozen)
        return new_params, new_state, metrics

    def _federated_worker(params, opt_state, batch):
        """One cohort round (DESIGN.md §13): this worker vmaps its C =
        n_clients/W clients — per-client grad, Armijo step, and gamma —
        then ONE cohort exchange aggregates the participants'
        compressed payloads support-weighted.  Non-participating
        clients' carried state (EF memory, gamma, rounds, alpha) is
        bit-frozen; their compute this round is simulation overhead the
        mask discards, exactly like a sampled-out real client."""
        C = fed.n_clients // W
        fedst = opt_state.fed                     # local leaves (C, ...)
        mask = batch["participation"]             # (n_clients,) replicated
        cbatch = {k: v for k, v in batch.items() if k != "participation"}
        pl = local_participation(mask, dp, C)     # (C,)
        n_part = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)

        def wmean(x_c):
            """Participation-weighted global mean of a per-client (C,)."""
            return jax.lax.psum(jnp.sum(pl * x_c), dp) / n_part

        # ---- per-client gradients (ONE vmap over the local cohort) ------
        with jax.named_scope("csgd_grad"):
            losses, grads_c = jax.vmap(
                lambda mb: jax.value_and_grad(local_loss)(params, mb))(cbatch)
            gsq_c = jax.vmap(tree_sqnorm)(grads_c)
        metrics = {"loss": wmean(losses), "grad_sqnorm": wmean(gsq_c),
                   "participants": jnp.sum(mask.astype(jnp.float32))}

        # ---- per-client gamma controllers -------------------------------
        if fed.per_client_gamma:
            # each client's linear ramp advances on its OWN participation
            # counter — heterogeneous k_t across the cohort by design
            gamma_t_c = jax.vmap(
                lambda g, r: gamma_update(opt.gamma_controller,
                                          opt.compressor, g, r))(
                fedst.gamma, fedst.rounds)
        else:
            gamma_t_c = jnp.broadcast_to(
                gamma_update(opt.gamma_controller, opt.compressor,
                             fedst.gamma[0], opt_state.step), (C,))
        gamma_used = jnp.where(pl > 0, gamma_t_c, fedst.gamma)
        metrics["gamma"] = wmean(gamma_used)

        # ---- per-client step sizes --------------------------------------
        if opt.kind == "csgd_asss":
            amax_c = next_alpha_max(fedst.alpha, opt.armijo)
            with jax.named_scope("csgd_armijo"):
                res = jax.vmap(
                    lambda mb, g, f0, gsq, amax: armijo_search(
                        lambda p: local_loss(p, mb), params, g, amax,
                        opt.armijo, f0=f0, grad_sqnorm=gsq))(
                    cbatch, grads_c, losses, gsq_c, amax_c)
            alpha_c = res.alpha
            evals_c = res.n_evals.astype(jnp.float32)
            eta_c = jax.vmap(
                lambda g, a: opt.armijo.scale_for(g) * a)(
                gamma_used, alpha_c)
        else:
            alpha_c = jnp.full((C,), opt.eta, jnp.float32)
            evals_c = jnp.zeros((C,), jnp.float32)
            eta_c = jnp.full((C,), opt.eta, jnp.float32)
        metrics["alpha"] = wmean(alpha_c)
        metrics["n_evals"] = wmean(evals_c)

        # ---- the cohort exchange: ONE gather + ONE psum -----------------
        smask = model.stacked_mask(params)
        if faults_on:
            with active_faults(opt.faults, opt_state.step):
                updates, new_mem, wire, eff_wire, quar = \
                    cohort_compress_aggregate(
                        grads_c, fedst.memory, eta_c, opt.compressor, dp,
                        mask, gamma_used, stacked_mask=smask,
                        aggregation=fed.aggregation,
                        return_quarantined=True)
        else:
            updates, new_mem, wire, eff_wire, quar = \
                cohort_compress_aggregate(
                    grads_c, fedst.memory, eta_c, opt.compressor, dp, mask,
                    gamma_used, stacked_mask=smask,
                    aggregation=fed.aggregation, return_quarantined=True)
        with jax.named_scope("csgd_apply"):
            new_params = jax.tree.map(
                lambda p, u: (p.astype(jnp.float32) - u).astype(p.dtype),
                params, updates)

            # ---- step-level circuit breaker (DESIGN.md §16) -------------
            health = jax.tree.map(lambda x: x[0], opt_state.health)
            step_ok = jnp.isfinite(metrics["loss"]) & all_finite(updates)
            if breaker_on:
                new_params = jax.tree.map(
                    lambda a, b: jnp.where(step_ok, a, b), new_params,
                    params)
            new_health = advance_health(health, step_ok, opt_state.step,
                                        quar)
        metrics["steps_skipped"] = \
            new_health.steps_skipped.astype(jnp.float32)
        metrics["consecutive_skips"] = \
            new_health.consecutive_skips.astype(jnp.float32)
        metrics["last_good_step"] = \
            new_health.last_good_step.astype(jnp.float32)
        metrics["rows_quarantined"] = new_health.rows_quarantined

        # wire/eff are cohort-global already (mask-weighted + psum'd)
        cum_eff = opt_state.cum_eff_bytes + eff_wire
        metrics["wire_bytes"] = wire
        metrics["effective_wire_bytes"] = eff_wire
        metrics["cum_effective_wire_bytes"] = cum_eff
        metrics["ef_backlog"] = jnp.float32(0.0)   # no cohort telemetry
        metrics["ef_cosine"] = jnp.float32(1.0)    # (DESIGN.md §13)

        new_state = DistOptState(
            step=opt_state.step + 1,
            alpha_prev=opt_state.alpha_prev,
            memory=(),
            n_evals_ema=opt_state.n_evals_ema,
            gamma=opt_state.gamma,
            telemetry=opt_state.telemetry,
            cum_eff_bytes=cum_eff,
            gossip=opt_state.gossip,
            overlap=opt_state.overlap,
            fed=ClientState(
                memory=new_mem,
                gamma=jnp.where(pl > 0, gamma_t_c, fedst.gamma),
                rounds=fedst.rounds + (pl > 0).astype(jnp.int32),
                alpha=jnp.where(pl > 0, alpha_c, fedst.alpha)),
            health=jax.tree.map(lambda x: x[None], new_health),
        )
        if breaker_on:
            frozen = new_state._replace(fed=opt_state.fed)
            with jax.named_scope("csgd_apply"):
                new_state = jax.tree.map(
                    lambda a, b: jnp.where(step_ok, a, b), new_state, frozen)
        return new_params, new_state, metrics

    def worker_fn(params, opt_state, batch):
        if fed_mode:
            return _federated_worker(params, opt_state, batch)
        # squeeze the per-worker leading axis of the optimizer state
        mem = jax.tree.map(lambda x: x[0], opt_state.memory) \
            if opt_state.memory != () else ()
        alpha_prev = opt_state.alpha_prev[0]
        ema = opt_state.n_evals_ema[0]
        gamma_prev = opt_state.gamma[0]
        tel_prev = jax.tree.map(lambda x: x[0], opt_state.telemetry)

        # serverless mode: the replicated ``params`` input is only the
        # common initialization — this worker optimizes ITS model copy
        # from DistOptState.gossip (workers genuinely diverge; the
        # topology's mixing contracts the disagreement each round)
        base_params = params
        if gossip_mode:
            params = jax.tree.map(lambda x: x[0], opt_state.gossip.params)

        # ---- local iterations (Qsparse-local-style, beyond-paper) -------
        if run_cfg.optimizer.local_steps > 1 and \
                opt.kind in ("csgd_asss", "nonadaptive"):
            return _local_steps_worker(params, opt_state, batch, mem,
                                       alpha_prev, ema, gamma_prev,
                                       tel_prev)

        # ---- gradient over microbatches (accumulated) -------------------
        with jax.named_scope("csgd_grad"):
            if micro > 1:
                mbs = jax.tree.map(
                    lambda x: x.reshape(micro, x.shape[0] // micro,
                                        *x.shape[1:]), batch)
                probe = jax.tree.map(lambda x: x[0], mbs)

                def acc(carry, mb):
                    lo, g = jax.value_and_grad(local_loss)(params, mb)
                    cl, cg = carry
                    return (cl + lo, jax.tree.map(jnp.add, cg, g)), None

                zero_g = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (loss_sum, grads), _ = jax.lax.scan(
                    acc, (jnp.float32(0.0), zero_g), mbs)
                loss = loss_sum / micro
                grads = jax.tree.map(lambda g: g / micro, grads)
            else:
                probe = batch
                loss, grads = jax.value_and_grad(local_loss)(params, batch)
            gsq = tree_sqnorm(grads)
        metrics = {"loss": jax.lax.pmean(loss, dp),
                   "grad_sqnorm": jax.lax.pmean(gsq, dp)}

        # ---- step size --------------------------------------------------
        if opt.kind in ("csgd_asss", "sls"):
            amax = next_alpha_max(alpha_prev, opt.armijo)
            with jax.named_scope("csgd_armijo"):
                res = armijo_search(lambda p: local_loss(p, probe), params,
                                    grads, amax, opt.armijo,
                                    grad_sqnorm=gsq)
            new_alpha = res.alpha
            new_ema = 0.9 * ema + 0.1 * res.n_evals.astype(jnp.float32)
            metrics["alpha"] = jax.lax.pmean(res.alpha, dp)
            metrics["n_evals"] = jax.lax.pmean(
                res.n_evals.astype(jnp.float32), dp)
        else:
            res = None
            new_alpha = alpha_prev
            new_ema = ema
            metrics["alpha"] = jnp.float32(opt.eta)
            metrics["n_evals"] = jnp.float32(0.0)

        # ---- per-round compression level (gamma controller round) -------
        search_tel = SearchTelemetry(
            alpha=res.alpha, alpha_prev=alpha_prev, n_evals=res.n_evals,
            n_evals_ema=ema) if res is not None else None
        gamma_t = gamma_update(opt.gamma_controller, opt.compressor,
                               gamma_prev, opt_state.step,
                               search=search_tel, compression=tel_prev)
        metrics["gamma"] = jax.lax.pmean(gamma_t, dp)

        if res is not None:
            # a = scale_for(gamma_t): paper's a_scale, re-clamped to
            # zeta(gamma_t) each round under armijo.theory_safe
            eta = opt.armijo.scale_for(gamma_t) * res.alpha
        else:
            eta = jnp.float32(opt.eta)

        # ---- aggregate (compressed or dense) ----------------------------
        if compressing:
            smask = model.stacked_mask(params)
            if acgd_mode:
                # Nesterov round (arXiv 2002.11364 composed with EF —
                # core/acgd.py): the exchange ships the lookahead descent
                # direction mu*v' + g instead of the raw gradient
                vel = jax.tree.map(
                    lambda v, g: opt.momentum * v + g.astype(jnp.float32),
                    jax.tree.map(lambda x: x[0], opt_state.velocity),
                    grads)
                send = jax.tree.map(
                    lambda v, g: opt.momentum * v + g.astype(jnp.float32),
                    vel, grads)
                new_velocity = jax.tree.map(lambda x: x[None], vel)
            else:
                send = grads
                new_velocity = opt_state.velocity
            dl_res = None
            if opt.shard_local_topk:
                # per-(layer, model-shard) top_k: nested manual-'model'
                # region so selection runs on the local gradient shard and
                # the only collective stays the small dp packed all-gather.
                pspecs = param_pspecs(params)
                # telemetry_axes: the model shards are ONE worker, so the
                # telemetry sums psum over 'model' before the ratios form
                # (the P() out_spec asserts them replicated; wire/eff are
                # shape-derived and replicated without it)
                inner = jax.shard_map(
                    lambda g, m2, e, gt: worker_compress_aggregate(
                        g, m2, e, opt.compressor, dp, stacked_mask=smask,
                        gamma_t=gt, telemetry_axes=("model",),
                        transport=opt.transport),
                    mesh=None,  # nested: resolve from the trace context
                    in_specs=(pspecs, pspecs, P(), P()),
                    out_specs=(pspecs, pspecs, P(), P(), P()),
                    axis_names={"model"}, check_vma=False)
                updates, new_mem, wire, eff_wire, tel = inner(send, mem,
                                                              eta, gamma_t)
            elif gossip_mode:
                ctx = GossipCtx(
                    topology=topo, cfg=opt.gossip,
                    state=jax.tree.map(lambda x: x[0],
                                       opt_state.gossip.state))
                t_name, t_ctx = wrap_faults(opt.transport, ctx,
                                            opt_state.step)
                updates, new_mem, wire, eff_wire, tel, gos_state = \
                    worker_compress_aggregate(
                        send, mem, eta, opt.compressor, dp,
                        stacked_mask=smask, gamma_t=gamma_t,
                        transport=t_name, transport_ctx=t_ctx)
            elif overlap_mode:
                ctx = OverlapCtx(
                    cfg=opt.overlap,
                    state=jax.tree.map(lambda x: x[0], opt_state.overlap))
                t_name, t_ctx = wrap_faults(opt.transport, ctx,
                                            opt_state.step)
                updates, new_mem, wire, eff_wire, tel, ov_state = \
                    worker_compress_aggregate(
                        send, mem, eta, opt.compressor, dp,
                        stacked_mask=smask, gamma_t=gamma_t,
                        transport=t_name, transport_ctx=t_ctx)
            elif downlink_mode:
                # server round (DESIGN.md §15): advance the downlink gamma
                # schedule, then re-compress the replicated aggregate
                # through the server-side EF — same collectives, the dense
                # return direction becomes packed payload rows
                dl_prev = jax.tree.map(lambda x: x[0], opt_state.downlink)
                dl_gamma = gamma_update(opt.downlink_gamma, opt.compressor,
                                        dl_prev.gamma, opt_state.step)
                ctx = DownlinkCtx(state=DownlinkState(
                    memory=dl_prev.memory, gamma=dl_gamma))
                updates, new_mem, wire, eff_wire, tel, dl_res = \
                    worker_compress_aggregate(
                        send, mem, eta, opt.compressor, dp,
                        stacked_mask=smask, gamma_t=gamma_t,
                        transport=opt.transport, downlink_ctx=ctx)
            elif faults_on:
                # stateless inner (perleaf | bucketed) wrapped by the
                # stateful 'faulty' transport: the SIXTH element is the
                # wrapper's carried state, always () for a stateless inner
                t_name, t_ctx = wrap_faults(opt.transport, None,
                                            opt_state.step)
                updates, new_mem, wire, eff_wire, tel, _ = \
                    worker_compress_aggregate(
                        send, mem, eta, opt.compressor, dp,
                        stacked_mask=smask, gamma_t=gamma_t,
                        transport=t_name, transport_ctx=t_ctx)
            else:
                updates, new_mem, wire, eff_wire, tel = \
                    worker_compress_aggregate(
                        send, mem, eta, opt.compressor, dp,
                        stacked_mask=smask, gamma_t=gamma_t,
                        transport=opt.transport)
            # the EF memory written back in the state's per-worker layout
            with jax.named_scope("csgd_ef"):
                new_mem = jax.tree.map(lambda x: x[None], new_mem)
        else:
            updates, wire = dense_aggregate(grads, eta, dp)
            eff_wire = wire
            new_mem = opt_state.memory
            new_velocity = opt_state.velocity
            dl_res = None
            tel = tel_prev              # no compression: health unchanged
        cum_eff = opt_state.cum_eff_bytes + jax.lax.pmean(eff_wire, dp)
        metrics["wire_bytes"] = jax.lax.pmean(wire, dp)
        metrics["effective_wire_bytes"] = jax.lax.pmean(eff_wire, dp)
        if dl_res is not None:
            # replicated by construction (every worker simulates the same
            # server); pmean keeps the metric convention uniform.  The
            # uplink counters above stay uplink-only — these keys carry
            # the return direction, and cum_eff prices both.
            metrics["downlink_wire_bytes"] = jax.lax.pmean(
                dl_res.wire_bytes, dp)
            metrics["downlink_effective_wire_bytes"] = jax.lax.pmean(
                dl_res.eff_wire_bytes, dp)
            cum_eff = cum_eff + jax.lax.pmean(dl_res.eff_wire_bytes, dp)
            new_downlink = jax.tree.map(lambda x: x[None], dl_res.state)
        else:
            new_downlink = opt_state.downlink
        metrics["cum_effective_wire_bytes"] = cum_eff
        metrics["ef_backlog"] = jax.lax.pmean(tel.ef_backlog, dp)
        metrics["ef_cosine"] = jax.lax.pmean(tel.cosine, dp)

        with jax.named_scope("csgd_apply"):
            new_params = jax.tree.map(
                lambda p, u: (p.astype(jnp.float32) - u).astype(p.dtype),
                params, updates)

            # ---- step-level circuit breaker (DESIGN.md §16) -------------
            health = jax.tree.map(lambda x: x[0], opt_state.health)
            step_ok = jnp.isfinite(metrics["loss"])
            if not gossip_mode:
                # the decoded aggregate is replicated (every worker decodes
                # the same gathered payload), so the update check adds no
                # collective; under gossip updates are per-worker by design
                # and the breaker couples through the pmean'd loss alone —
                # a NaN anywhere poisons the mean within one round
                step_ok &= all_finite(updates)
            if breaker_on:
                new_params = jax.tree.map(
                    lambda a, b: jnp.where(step_ok, a, b), new_params,
                    params)
            quar_round = tel.rows_quarantined if compressing \
                else jnp.float32(0.0)
            new_health = advance_health(health, step_ok, opt_state.step,
                                        quar_round)
        metrics["steps_skipped"] = \
            new_health.steps_skipped.astype(jnp.float32)
        metrics["consecutive_skips"] = \
            new_health.consecutive_skips.astype(jnp.float32)
        metrics["last_good_step"] = \
            new_health.last_good_step.astype(jnp.float32)
        quar_metric = new_health.rows_quarantined
        if gossip_mode:
            # per-worker under gossip (each worker verdicts its own
            # neighbor gather) — pmean'd for the replicated metric slot
            quar_metric = jax.lax.pmean(quar_metric, dp)
        metrics["rows_quarantined"] = quar_metric

        if gossip_mode:
            # the per-worker model advances in DistOptState.gossip; the
            # replicated params output stays the frozen initialization
            # (its out_spec asserts replication — diverged values there
            # would be undefined behavior)
            new_gossip = GossipOptState(
                params=jax.tree.map(lambda x: x[None], new_params),
                state=jax.tree.map(lambda x: x[None], gos_state))
            new_params = base_params
        else:
            new_gossip = opt_state.gossip
        if overlap_mode:
            new_overlap = jax.tree.map(lambda x: x[None], ov_state)
            # 1.0 once the carried payload is a real previous step (delay=1
            # applies a one-step-stale aggregate); 0.0 on the warmup step
            # and always under delay=0 (DESIGN.md §14)
            metrics["staleness"] = jax.lax.pmean(
                jnp.float32(opt.overlap.delay)
                * opt_state.overlap.seeded[0], dp)
        else:
            new_overlap = opt_state.overlap
        new_state = DistOptState(
            step=opt_state.step + 1,
            alpha_prev=new_alpha[None],
            memory=new_mem,
            n_evals_ema=new_ema[None],
            gamma=gamma_t[None],
            telemetry=jax.tree.map(lambda x: x[None], tel),
            cum_eff_bytes=cum_eff,
            gossip=new_gossip,
            overlap=new_overlap,
            downlink=new_downlink,
            velocity=new_velocity,
            health=jax.tree.map(lambda x: x[None], new_health),
        )
        if breaker_on:
            # skip-step: step/cum_eff/health advance; every carried
            # optimizer quantity freezes bit-exactly (jnp.where with a
            # replicated scalar predicate — zero collectives, and the
            # taken branch is bit-identical to the unconditional write)
            frozen = new_state._replace(
                alpha_prev=opt_state.alpha_prev,
                memory=opt_state.memory,
                n_evals_ema=opt_state.n_evals_ema,
                gamma=opt_state.gamma,
                telemetry=opt_state.telemetry,
                gossip=opt_state.gossip,
                overlap=opt_state.overlap,
                downlink=opt_state.downlink,
                velocity=opt_state.velocity)
            with jax.named_scope("csgd_apply"):
                new_state = jax.tree.map(
                    lambda a, b: jnp.where(step_ok, a, b), new_state, frozen)
        return new_params, new_state, metrics

    # ---- specs ------------------------------------------------------------
    lead = P(dp_spec)
    rep = P()

    def batch_spec_of(batch_tree):
        # the cohort participation mask is a global (n_clients,) row every
        # worker reads (each slices its own C clients) — replicated, not
        # batch-sharded like the data leaves
        return {k: (rep if k == "participation" else P(dp_spec))
                for k in batch_tree} if isinstance(batch_tree, dict) else \
            jax.tree.map(lambda _: P(dp_spec), batch_tree)

    def make(params_like, batch_like):
        tel_spec = jax.tree.map(lambda _: lead,
                                CompressionTelemetry.init(abstract=True))
        state_in = DistOptState(
            step=rep, alpha_prev=lead,
            memory=(jax.tree.map(lambda _: lead, params_like)
                    if compressing and not fed_mode else ()),
            n_evals_ema=lead, gamma=lead,
            telemetry=tel_spec, cum_eff_bytes=rep,
            gossip=(GossipOptState(
                params=jax.tree.map(lambda _: lead, params_like),
                state=GossipState(v=lead, lr=lead))
                if gossip_mode else ()),
            fed=(ClientState(
                memory=jax.tree.map(lambda _: lead, params_like),
                gamma=lead, rounds=lead, alpha=lead)
                if fed_mode else ()),
            overlap=(OverlapState(
                payload=lead, dense=lead, eff_wire=lead, seeded=lead)
                if overlap_mode else ()),
            downlink=(DownlinkState(memory=lead, gamma=lead)
                      if downlink_mode and not fed_mode else ()),
            velocity=(jax.tree.map(lambda _: lead, params_like)
                      if acgd_mode and not fed_mode else ()),
            health=HealthState(steps_skipped=lead, consecutive_skips=lead,
                               last_good_step=lead, rows_quarantined=lead))
        metric_keys = ("loss", "grad_sqnorm", "alpha", "n_evals",
                       "wire_bytes", "effective_wire_bytes",
                       "cum_effective_wire_bytes", "ef_backlog",
                       "ef_cosine", "gamma",
                       "steps_skipped", "consecutive_skips",
                       "last_good_step", "rows_quarantined") + \
            (("participants",) if fed_mode else ()) + \
            (("staleness",) if overlap_mode else ()) + \
            (("downlink_wire_bytes", "downlink_effective_wire_bytes")
             if downlink_mode and not fed_mode else ())
        metrics_spec = {k: rep for k in metric_keys}
        # Manual over dp, auto over 'model' (XLA partitions the TP math).
        sm = jax.shard_map(
            worker_fn, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: rep, params_like),
                      state_in, batch_spec_of(batch_like)),
            out_specs=(jax.tree.map(lambda _: rep, params_like),
                       state_in, metrics_spec),
            axis_names=set(dp), check_vma=False)
        # outer jit: model-axis shardings
        psh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           param_pspecs(params_like))
        opt_sh = opt_state_shardings(
            init_opt_state(params_like, run_cfg, W, abstract=True,
                           stacked_mask=model.stacked_mask(params_like)),
            params_like, mesh, run_cfg)
        bsh = jax.tree.map(
            lambda s: NamedSharding(mesh, s), batch_spec_of(batch_like),
            is_leaf=lambda x: isinstance(x, P))
        msh = {k: NamedSharding(mesh, P()) for k in metric_keys}
        # donation of pinned_host-backed state trips an XLA SPMD RET_CHECK
        # (side-effecting copy-to-host without sharding); skip it there.
        donate = () if opt.ef_host_offload else (0, 1)
        return jax.jit(sm,
                       in_shardings=(psh, opt_sh, bsh),
                       out_shardings=(psh, opt_sh, msh),
                       donate_argnums=donate)

    return make


# ===========================================================================
# serve steps
# ===========================================================================

def build_prefill_step(model: Model, run_cfg: RunConfig, mesh,
                       shape: ShapeConfig, params_2d: bool = False):
    """Batched prefill under auto pjit: batch over dp, TP from hints.

    ``params_2d``: weights additionally sharded over the data axis (serving
    memory optimization — see sharding.param_pspecs)."""
    dp = dp_axes_of(mesh)
    dp_spec = dp if len(dp) > 1 else dp[0]

    def prefill_step(params, batch):
        logits, cache = model.prefill(params, batch)
        return logits, cache

    def make(params_like, batch_like):
        pspecs = param_pspecs(params_like, two_d=params_2d)
        psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
        bsh = jax.tree.map(lambda _: NamedSharding(mesh, P(dp_spec)),
                           batch_like)
        return jax.jit(prefill_step, in_shardings=(psh, bsh))
    return make


def decode_seq_axes(mesh, shape: ShapeConfig) -> tuple[str, ...]:
    """Cache-seq sharding axes: 'model' normally; every axis for batch=1."""
    if shape.global_batch == 1:
        return tuple(mesh.axis_names)
    return ("model",)


def build_decode_step(model: Model, run_cfg: RunConfig, mesh,
                      shape: ShapeConfig, params_2d: bool = False):
    """One-token serve_step: new token against a seq_len KV cache."""
    dp = dp_axes_of(mesh)
    seq_axes = decode_seq_axes(mesh, shape)

    def serve_step(params, token, cache, cur_len):
        logits, cache = model.decode_step(params, token, cache, cur_len)
        return logits, cache

    def make(params_like, token_like, cache_like):
        pspecs = param_pspecs(params_like, two_d=params_2d)
        psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
        dp_spec = dp if len(dp) > 1 else dp[0]
        tok_sh = NamedSharding(
            mesh, P(dp_spec) if shape.global_batch > 1 else P())
        cspecs = cache_pspecs(cache_like,
                              dp if shape.global_batch > 1 else (), seq_axes)
        csh = jax.tree.map(lambda s: NamedSharding(mesh, s), cspecs,
                           is_leaf=lambda x: isinstance(x, P))
        return jax.jit(serve_step,
                       in_shardings=(psh, tok_sh, csh, NamedSharding(mesh, P())),
                       out_shardings=None)
    return make
