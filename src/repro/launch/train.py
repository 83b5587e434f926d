"""Training driver: DCSGD-ASSS on a device mesh, with checkpointing.

CPU-scale entry point (the production mesh path is exercised by dryrun.py):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.train --arch yi-34b --smoke \
        --steps 50 --mesh 4x2 --opt csgd_asss --gamma 0.05

Runs real steps on the (forced-host) mesh, logs loss/alpha/wire-bytes, and
writes checkpoints.  ``--arch paper-lm-100m`` is the ~100M end-to-end run.

:func:`main` takes an argv list and returns the run's record, so a caller
that must keep the chip in one process (``chip_smoke.py``) drives it
in-process.  Compiled programs persist in JAX's compilation cache:
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.
``--profile-dir DIR --profile-steps A:B`` writes a ``jax.profiler`` trace
of steps A to B - 1 with the loop's host spans (``launch/spans.py``).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import statistics
import time

import jax
import jax.numpy as jnp

from jax import set_mesh
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import checkpoint as ckpt
from repro.comm.faults import FaultConfig
from repro.comm.gossip import GossipConfig
from repro.comm.overlap import OverlapConfig
from repro.comm.topology import TOPOLOGIES
from repro.comm.transport import transport_names
from repro.configs import get_config, get_smoke_config
from repro.configs.base import (FederatedConfig, OptimizerConfig, RunConfig,
                                ShapeConfig)
from repro.core.armijo import ArmijoConfig
from repro.core.compression import Compressor
from repro.core.gamma import GammaControllerConfig
from repro.core.health import check_divergence
from repro.data.synthetic import TokenPipeline
from repro.fed.sampling import participation_mask
from repro.launch.mesh import parse_mesh
from repro.launch.spans import DEFAULT_STEPS, LoopProfile, parse_steps
from repro.launch.train_step import (build_train_step, init_opt_state,
                                     opt_state_shardings)
from repro.models import build_model
from repro.sharding import dp_axes_of, param_shardings


CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def init_compile_cache() -> None:
    """Persistent compilation cache: JAX reads ``JAX_COMPILATION_CACHE_DIR``
    itself; without it, one fixed git-ignored directory in the checkout
    (a fixed path, since the path is part of the cache key)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(CHECKOUT / ".jax_cache"))


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-lm-100m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke variant of --arch")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", default="2x1")
    ap.add_argument("--opt", default="csgd_asss",
                    choices=["csgd_asss", "nonadaptive", "acgd", "sgd",
                             "dense", "sls"])
    ap.add_argument("--gamma", type=float, default=0.01)
    ap.add_argument("--compress-method", default="topk",
                    choices=["topk", "block_topk", "none"],
                    help="block_topk = fused Pallas kernel path")
    # ---- adaptive per-round compression (DESIGN.md §9) ----
    ap.add_argument("--max-gamma", type=float, default=0.0,
                    help="> 0: static ragged-wire budget; gamma becomes "
                         "the per-round initial level")
    ap.add_argument("--gamma-schedule", default="fixed",
                    choices=["fixed", "linear", "armijo-coupled",
                             "ef-coupled"],
                    help="per-round gamma controller (core/gamma.py); "
                         "ef-coupled couples to the EF backlog telemetry "
                         "(DESIGN.md §10)")
    ap.add_argument("--gamma-min", type=float, default=0.0,
                    help="controller floor (0 = gamma/8)")
    ap.add_argument("--gamma-ramp-steps", type=int, default=1000,
                    help="linear schedule: steps from gamma to max-gamma")
    # defaults come from the dataclass so the CLI can never drift from
    # the calibrated controller defaults (core/gamma.py)
    ap.add_argument("--ef-target", type=float,
                    default=GammaControllerConfig.ef_target,
                    help="ef-coupled: backlog ratio ||m'||/||g|| the "
                         "hysteresis band centers on")
    ap.add_argument("--ef-band", type=float,
                    default=GammaControllerConfig.ef_band,
                    help="ef-coupled: band half-width (grow above "
                         "target+band, shrink below target-band)")
    ap.add_argument("--theory-safe", action="store_true",
                    help="clamp the step scale to zeta(gamma_t) = "
                         "sigma*gamma/(2-gamma) each round")
    ap.add_argument("--no-kernel", action="store_true",
                    help="block_topk via pure jnp (kernel escape hatch)")
    ap.add_argument("--eta", type=float, default=0.1)
    ap.add_argument("--momentum", type=float, default=0.9,
                    help="acgd: Nesterov mu (arXiv 2002.11364); heavy-ball "
                         "momentum for single-node CSGD lives in "
                         "repro.core.csgd")
    ap.add_argument("--local-steps", type=int, default=1)
    ap.add_argument("--value-bits", type=int, default=32,
                    choices=[32, 16, 8, 4],
                    help="wire value width (DESIGN.md §8 packed format)")
    ap.add_argument("--ef-dtype", default="float32")
    # choices come from the transport registry (repro/comm/transport.py)
    # so the CLI can never drift from the actual registered schedules
    ap.add_argument("--transport", default="bucketed",
                    choices=list(transport_names()),
                    help="compressed-exchange schedule (DESIGN.md §11/§12): "
                         "bucketed = ONE flat packed all_gather + batched "
                         "launches; perleaf = one collective per leaf "
                         "(bit-exact reference); gossip = serverless "
                         "neighbor-ppermute consensus exchange; overlap = "
                         "chunked-ring, double-buffered exchange "
                         "(DESIGN.md §14)")
    # ---- overlapped exchange (transport=overlap, DESIGN.md §14) ----
    ap.add_argument("--overlap-chunks", type=int,
                    default=OverlapConfig.n_chunks,
                    help="ring chunk count: the payload crosses each link "
                         "as n_chunks independent collective_permute hops "
                         "per ring step")
    ap.add_argument("--overlap-delay", type=int,
                    default=OverlapConfig.delay, choices=[0, 1],
                    help="1 = double-buffered: ship the PREVIOUS step's "
                         "payload so the collective overlaps this step's "
                         "compute; 0 = synchronous (bit-exact vs bucketed)")
    # ---- gossip / consensus (transport=gossip, DESIGN.md §12) ----
    ap.add_argument("--topology", default=GossipConfig.topology,
                    choices=sorted(TOPOLOGIES),
                    help="gossip mixing graph over the dp workers")
    ap.add_argument("--consensus-lr", type=float,
                    default=GossipConfig.consensus_lr,
                    help="numerator of the AdaGossip adaptive consensus "
                         "step (capped at --consensus-lr-max)")
    ap.add_argument("--consensus-beta", type=float,
                    default=GossipConfig.beta,
                    help="EMA decay of the gossip-error second moment")
    ap.add_argument("--consensus-lr-max", type=float,
                    default=GossipConfig.lr_max,
                    help="consensus step cap (the fixed-step baseline)")
    ap.add_argument("--shard-local-topk", action="store_true")
    # ---- compressed downlink (DESIGN.md §15) ----
    ap.add_argument("--downlink", default="dense",
                    choices=["dense", "compressed"],
                    help="return direction of the aggregate: 'dense' ships "
                         "the full f32 mean (bit-exact reference); "
                         "'compressed' re-compresses it through the same "
                         "wire format with server-side error feedback — "
                         "no extra collective")
    ap.add_argument("--downlink-gamma", type=float, default=0.0,
                    help="downlink compression level (0 = the uplink "
                         "compressor's gamma)")
    ap.add_argument("--downlink-gamma-schedule", default="fixed",
                    choices=["fixed", "linear"],
                    help="open-loop downlink gamma schedule (the simulated "
                         "server has no telemetry to couple to)")
    # ---- federated cohort simulation (DESIGN.md §13) ----
    ap.add_argument("--n-clients", type=int, default=0,
                    help="> 0: federated cohort simulation — vmap "
                         "n-clients/W simulated clients per dp worker, "
                         "each with its own non-IID shard, EF memory and "
                         "gamma controller")
    ap.add_argument("--clients-per-round", type=int, default=0,
                    help="fixed-size sampling: participants per round "
                         "(0 = all clients)")
    ap.add_argument("--client-sampling", default="fixed",
                    choices=["fixed", "bernoulli"],
                    help="per-round participation sampler (fed/sampling.py)")
    ap.add_argument("--participation-rate", type=float, default=1.0,
                    help="bernoulli sampling: per-client participation "
                         "probability")
    ap.add_argument("--straggler-rate", type=float, default=0.0,
                    help="probability a sampled client drops out "
                         "(straggler model, applied after sampling)")
    ap.add_argument("--aggregation", default="support",
                    choices=["support", "mean"],
                    help="cohort aggregation: 'support' divides each "
                         "coordinate by its nonzero-support count; 'mean' "
                         "is the zero-averaging dense-pmean reference")
    ap.add_argument("--dirichlet-alpha", type=float, default=0.0,
                    help="> 0: non-IID client shards via per-client "
                         "Dirichlet(alpha) unigram tilt (data/synthetic.py)")
    ap.add_argument("--fed-seed", type=int, default=0,
                    help="seed for participation sampling + client shards")
    # ---- hostile-wire robustness (DESIGN.md §16) ----
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed of the (seed, step, worker)-deterministic "
                         "fault-injection stream")
    ap.add_argument("--fault-bitflip", type=float, default=0.0,
                    help="per-row probability of flipping one random wire "
                         "bit in the gathered payload")
    ap.add_argument("--fault-count", type=float, default=0.0,
                    help="per-row probability of a truncated/overflowed "
                         "ragged count header")
    ap.add_argument("--fault-nonfinite", type=float, default=0.0,
                    help="per-row probability of a NaN/Inf scale or value "
                         "field")
    ap.add_argument("--fault-zero-row", type=float, default=0.0,
                    help="per-row probability of zeroing the whole row "
                         "(dropped-worker model: decodes as a VALID empty "
                         "contribution)")
    ap.add_argument("--fault-worker", type=int, default=-1,
                    help="gathered row-slot to target (-1 = all workers)")
    ap.add_argument("--fault-start-step", type=int, default=0,
                    help="first step of the fault burst")
    ap.add_argument("--fault-steps", type=int, default=-1,
                    help="burst length in steps (-1 = open-ended)")
    ap.add_argument("--no-quarantine", action="store_true",
                    help="disable the defensive decode verdicts (corrupt "
                         "rows flow into the mean; the step-level breaker "
                         "is the only remaining defense)")
    ap.add_argument("--max-consecutive-skips", type=int,
                    default=OptimizerConfig.max_consecutive_skips,
                    help="step-level circuit breaker: this many consecutive "
                         "non-finite (skipped) rounds raise "
                         "DivergenceError naming the last good step "
                         "(0 disables the gate)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--out", default=None, help="JSON metrics log")
    ap.add_argument("--profile-dir", default=None,
                    help="write a jax.profiler trace of --profile-steps "
                         "here, with the loop's host spans (train.step, "
                         "train.make_batch, train.put_batch, train.wait, "
                         "train.divergence_read, train.log, "
                         "train.checkpoint)")
    ap.add_argument("--profile-steps", type=parse_steps,
                    default=DEFAULT_STEPS, metavar="A:B",
                    help="the traced steps, A to B - 1 (default 1:3)")
    return ap.parse_args(argv)


def run_config(args: argparse.Namespace) -> RunConfig:
    """The RunConfig the CLI flags describe."""
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    shape = ShapeConfig("cli", args.seq_len, args.global_batch, "train")
    return RunConfig(
        model=cfg, shape=shape,
        optimizer=OptimizerConfig(
            kind=args.opt, armijo=ArmijoConfig(theory_safe=args.theory_safe),
            compressor=Compressor(gamma=args.gamma,
                                  method=args.compress_method,
                                  value_bits=args.value_bits,
                                  use_kernel=not args.no_kernel,
                                  max_gamma=args.max_gamma),
            gamma_controller=GammaControllerConfig(
                schedule=args.gamma_schedule,
                gamma_min=args.gamma_min,
                ramp_steps=args.gamma_ramp_steps,
                ef_target=args.ef_target,
                ef_band=args.ef_band),
            eta=args.eta, momentum=args.momentum, ef_dtype=args.ef_dtype,
            shard_local_topk=args.shard_local_topk,
            local_steps=args.local_steps,
            transport=args.transport,
            gossip=GossipConfig(topology=args.topology,
                                consensus_lr=args.consensus_lr,
                                beta=args.consensus_beta,
                                lr_max=args.consensus_lr_max),
            overlap=OverlapConfig(n_chunks=args.overlap_chunks,
                                  delay=args.overlap_delay),
            federated=FederatedConfig(
                n_clients=args.n_clients,
                clients_per_round=args.clients_per_round,
                sampling=args.client_sampling,
                participation_rate=args.participation_rate,
                straggler_rate=args.straggler_rate,
                aggregation=args.aggregation,
                dirichlet_alpha=args.dirichlet_alpha,
                seed=args.fed_seed),
            downlink=args.downlink,
            downlink_gamma=GammaControllerConfig(
                schedule=args.downlink_gamma_schedule,
                gamma0=args.downlink_gamma),
            faults=FaultConfig(seed=args.fault_seed,
                               p_bitflip=args.fault_bitflip,
                               p_count=args.fault_count,
                               p_nonfinite=args.fault_nonfinite,
                               p_zero_row=args.fault_zero_row,
                               worker=args.fault_worker,
                               start_step=args.fault_start_step,
                               n_steps=args.fault_steps,
                               quarantine=not args.no_quarantine),
            max_consecutive_skips=args.max_consecutive_skips),
        microbatches=args.microbatches)


def log_row(metrics: dict, step: int, t_start: float,
            args: argparse.Namespace) -> dict:
    """The step's metrics read to the host, printed as one line."""
    m = {k: float(v) for k, v in metrics.items()}
    m["step"] = step
    m["wall_s"] = round(time.time() - t_start, 1)
    down = (f"down={m['downlink_effective_wire_bytes']:.3e}B "
            if "downlink_effective_wire_bytes" in m else "")
    print(f"step {step:5d} loss={m['loss']:.4f} "
          f"alpha={m['alpha']:.4g} evals={m['n_evals']:.2f} "
          f"up={m['wire_bytes']:.3e}B "
          f"eff={m.get('effective_wire_bytes', 0.0):.3e}B "
          f"{down}"
          f"cum={m.get('cum_effective_wire_bytes', 0.0):.3e}B "
          f"gamma={m.get('gamma', args.gamma):.4g} "
          f"backlog={m.get('ef_backlog', 0.0):.3g} "
          f"cos={m.get('ef_cosine', 1.0):.3f}"
          + (f" skips={m['steps_skipped']:.0f}"
             f" quar={m['rows_quarantined']:.0f}"
             if m.get("steps_skipped", 0.0)
             or m.get("rows_quarantined", 0.0) else ""),
          flush=True)
    return m


def main(argv: list[str] | None = None) -> dict:
    """Train; returns ``{"log", "compile_s", "step_s"}`` — the logged
    metric rows, the train-step compile seconds and every step's wall
    seconds (dispatch to ``block_until_ready``)."""
    init_compile_cache()
    args = parse_args(argv)
    run = run_config(args)
    cfg = run.model
    model = build_model(cfg)
    mesh = parse_mesh(args.mesh)
    dp = dp_axes_of(mesh)
    W = math.prod(mesh.shape[a] for a in dp)

    with set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        params = jax.device_put(params, param_shardings(params, mesh))
        opt_state = init_opt_state(params, run, W,
                                   stacked_mask=model.stacked_mask(params))
        opt_state = jax.device_put(
            opt_state, opt_state_shardings(opt_state, params, mesh, run))

        start = 0
        if args.resume and args.ckpt_dir and ckpt.latest_step(args.ckpt_dir):
            (params, opt_state), meta = ckpt.restore(
                args.ckpt_dir, (params, opt_state))
            start = meta.get("step", 0)
            print(f"resumed from step {start}")

        fed = run.optimizer.federated
        bspec = NamedSharding(mesh, P(dp if len(dp) > 1 else dp[0]))
        rep_sh = NamedSharding(mesh, P())
        if fed.enabled:
            if args.global_batch % fed.n_clients:
                raise SystemExit(
                    f"--global-batch {args.global_batch} must divide "
                    f"evenly across --n-clients {fed.n_clients}")
            # one shard-aware pipeline per client: client c IS shard c of
            # the (seed, step, shard)-deterministic stream, Dirichlet-
            # tilted per client when --dirichlet-alpha > 0
            cpipes = [TokenPipeline(
                vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                global_batch=args.global_batch, seed=fed.seed,
                n_shards=fed.n_clients, shard=c,
                dirichlet_alpha=fed.dirichlet_alpha)
                for c in range(fed.n_clients)]

            def make_batch(step):
                rows = [p.batch_with_aux(step, cfg) for p in cpipes]
                b = {k: jnp.stack([r[k] for r in rows]) for k in rows[0]}
                b["participation"] = participation_mask(
                    fed.n_clients, step, seed=fed.seed, mode=fed.sampling,
                    clients_per_round=fed.clients_per_round,
                    rate=fed.participation_rate,
                    straggler_rate=fed.straggler_rate)
                return b
        else:
            pipe = TokenPipeline(vocab_size=cfg.vocab_size,
                                 seq_len=args.seq_len,
                                 global_batch=args.global_batch)

            def make_batch(step):
                return pipe.batch_with_aux(step, cfg)

        def put_batch(b):
            return {k: jax.device_put(
                v, rep_sh if k == "participation" else bspec)
                for k, v in b.items()}

        step_fn = None
        log = []
        compile_s = 0.0
        step_s = []
        t_start = time.time()
        with LoopProfile(args.profile_dir, args.profile_steps) as prof:
            for step in range(start, args.steps):
                with prof.step(step):
                    with prof.span("train.make_batch"):
                        host_batch = make_batch(step)
                    with prof.span("train.put_batch"):
                        batch = put_batch(host_batch)
                    if step_fn is None:
                        step_fn = build_train_step(model, run, mesh)(
                            params, batch)
                        t0 = time.perf_counter()
                        step_fn = step_fn.lower(params, opt_state,
                                                batch).compile()
                        compile_s = time.perf_counter() - t0
                        print(f"compiled train_step in {compile_s:.1f}s")
                    t0 = time.perf_counter()
                    params, opt_state, metrics = step_fn(params, opt_state,
                                                         batch)
                    with prof.span("train.wait"):
                        jax.block_until_ready(metrics)
                    step_s.append(time.perf_counter() - t0)
                    if run.optimizer.max_consecutive_skips > 0:
                        # host-side breaker: DivergenceError is a typed
                        # Python exception, impossible to raise from jit
                        with prof.span("train.divergence_read"):
                            check_divergence(
                                {"step": step,
                                 "consecutive_skips":
                                     metrics["consecutive_skips"],
                                 "last_good_step": metrics["last_good_step"]},
                                run.optimizer.max_consecutive_skips)
                    if step % args.log_every == 0 or step == args.steps - 1:
                        with prof.span("train.log"):
                            log.append(log_row(metrics, step, t_start, args))
                    if args.ckpt_dir and step and step % args.ckpt_every == 0:
                        with prof.span("train.checkpoint"):
                            ckpt.save(args.ckpt_dir, step, (params, opt_state),
                                      metadata={"step": step})
        if args.ckpt_dir:
            ckpt.save(args.ckpt_dir, args.steps, (params, opt_state),
                      metadata={"step": args.steps})
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(log, f, indent=1)
        if len(step_s) > 1:
            print(f"median step {statistics.median(step_s[1:]):.4f}s "
                  f"over {len(step_s) - 1} steps after the first")
    return {"log": log, "compile_s": compile_s, "step_s": step_s}


if __name__ == "__main__":
    main()
