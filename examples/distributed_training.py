"""DCSGD-ASSS (paper Algorithm 3) on a simulated 8-chip mesh.

Each data-parallel worker line-searches on ITS OWN batch, compresses its
gradient with error feedback, and only the sparse (values, indices) pairs
cross the wire — watch the wire-bytes column vs the dense baseline.  Each
step also logs the worker-mean compression telemetry (DESIGN.md §10): the
EF backlog ratio ``||m'||/||g||`` and the decode/gradient cosine — the
signal the ``ef-coupled`` gamma controller closes the loop on.

    PYTHONPATH=src python examples/distributed_training.py
(the script re-execs itself with XLA_FLAGS for 8 host devices)

The same machinery from the training CLI (repro/launch/train.py)::

    PYTHONPATH=src python -m repro.launch.train --arch yi-34b --smoke \\
        --mesh 4x2 --gamma 0.005 --max-gamma 0.05 \\
        --gamma-schedule ef-coupled --ef-target 0.15 --ef-band 0.08

``--gamma-schedule ef-coupled`` adapts the per-round compression level
from the EF backlog (grow when backlog leaves the hysteresis band above
``--ef-target + --ef-band`` — over-compressed; shrink below ``--ef-target
- --ef-band`` while the decode cosine is healthy); ``--max-gamma`` sizes
the static ragged wire budget the controller moves inside.  Unlike
``armijo-coupled`` it senses over-compression directly, so a too-small
``--gamma`` start recovers instead of stalling at ``--gamma-min``
(tests/test_golden_convergence.py pins that pairing).

The exchange itself is **bucketed** (DESIGN.md §11, the default): every
compressed leaf's packed payload rides ONE flat ``all_gather`` per step
(down from one collective per leaf), the pack/unpack and fused-EF
kernels launch once per bucket instead of once per leaf, and every dense
small leaf folds into a single ``pmean`` — same bytes on the wire, same
updates bit for bit.  ``--transport perleaf`` restores the per-leaf
reference schedule for A/B timing or debugging::

    PYTHONPATH=src python -m repro.launch.train --arch yi-34b --smoke \\
        --mesh 4x2 --compress-method block_topk --transport perleaf

**Serverless** (DESIGN.md §12): ``--transport gossip`` drops the server
role entirely — the SAME packed payload moves by ``degree`` neighbor
``ppermute``\\ s on a fixed mixing graph, each worker consensus-averages
itself + neighbors with an AdaGossip-style adaptive consensus step, and
per-worker models converge through the topology's spectral gap::

    python examples/distributed_training.py --transport gossip \\
        --topology ring --consensus-lr 1.0

Byte accounting is PER LINK so transports stay comparable: a gossip
worker's uplink carries ``degree x`` the per-link payload (ring: 2x),
where the gather-based transports pay ``(W-1) x`` — the printed
``wire_bytes/link`` is the same per-payload figure for all of them.

**Overlapped** (DESIGN.md §14): ``--transport overlap`` streams the SAME
packed buffer around a chunked ``ppermute`` ring instead of one flat
``all_gather`` and, at ``--overlap-delay 1`` (the default), ships the
PREVIOUS step's payload so the collective runs concurrently with this
step's compute — the applied mean is one step stale (watch the
``staleness`` column flip 0 -> 1 after the warm-up step) while EF and
telemetry stay current.  ``--overlap-delay 0`` is the bit-exact bucketed
drop-in; ``--overlap-chunks`` sets the ring section count::

    python examples/distributed_training.py --transport overlap \\
        --overlap-chunks 4 --overlap-delay 1

**Compressed downlink** (DESIGN.md §15): ``--downlink compressed``
closes the return direction — the replicated aggregate the bucketed
gather decodes is re-compressed through the SAME wire format with a
server-side error-feedback memory before workers apply it, so BOTH
directions ship packed payload rows.  No extra collective: the server is
physically simulated (every worker runs the identical compress/EF), only
the accounting changes.  Watch the per-direction columns — ``up`` stays
the uplink payload, ``down`` drops from dense f32 bytes to the payload
budget at ``--downlink-gamma``::

    python examples/distributed_training.py --downlink compressed \\
        --downlink-gamma 0.05

**Federated cohort simulation** (DESIGN.md §13): ``--n-clients N`` vmaps
``N / W`` simulated clients onto each dp worker — per-client EF memory,
per-client gamma, non-IID Dirichlet-tilted shards, partial participation
— while the whole cohort still moves on ONE all_gather + ONE psum per
round.  The demo runs the same non-IID cohort twice to show WHY
support-weighted aggregation is the default: ``support`` divides each
coordinate by the clients that actually sent it, ``mean`` averages in
the zeros absent coordinates leave behind (watch the loss gap and the
``participants`` column)::

    python examples/distributed_training.py --n-clients 32 \\
        --clients-per-round 24

The training CLI exposes the full surface::

    PYTHONPATH=src python -m repro.launch.train --arch yi-34b --smoke \\
        --mesh 4x2 --n-clients 64 --clients-per-round 48 \\
        --dirichlet-alpha 0.3 --aggregation support --straggler-rate 0.1

**Hostile-wire robustness** (DESIGN.md §16): ``--fault-demo`` runs the
same compressed exchange with a seeded fault campaign corrupting worker
0's gathered payload rows (bit flips, poisoned ragged counts, NaN/Inf
scale fields) for a 5-step burst.  The defensive decode layer verdicts
every row, quarantines the invalid ones (zeroed, with the mean's
denominator adjusted), and freezes the victim's EF residual for the
round — watch the ``quar`` column light up during the burst while the
loss keeps descending.  The step-level circuit breaker backs the
verdicts up: any non-finite round skips the parameter write bit-exactly
(``skips`` column) and ``--max-consecutive-skips`` consecutive skips
raise ``DivergenceError`` naming the last good step.  The training CLI
carries the full surface::

    PYTHONPATH=src python -m repro.launch.train --arch yi-34b --smoke \\
        --mesh 4x2 --fault-nonfinite 0.5 --fault-worker 0 \\
        --fault-start-step 10 --fault-steps 5 --fault-seed 7

``--no-quarantine`` disables the verdict layer (corrupt rows flow into
the mean — the breaker alone keeps parameters finite) and
``--max-consecutive-skips 0`` disables the breaker; with both off a
burst is pinned divergent by tests/test_golden_convergence.py.
"""
import argparse
import os
import sys

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.execv(sys.executable, [sys.executable] + sys.argv)

import jax
import jax.numpy as jnp

from jax import set_mesh
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm.faults import FaultConfig
from repro.comm.gossip import GossipConfig
from repro.comm.overlap import OverlapConfig
from repro.comm.topology import TOPOLOGIES, build_topology
from repro.comm.transport import transport_names
from repro.configs import get_smoke_config
from repro.configs.base import (FederatedConfig, OptimizerConfig,
                                RunConfig, ShapeConfig)
from repro.fed.sampling import participation_mask
from repro.core import ArmijoConfig, Compressor, GammaControllerConfig
from repro.data.synthetic import TokenPipeline
from repro.launch.train_step import (build_train_step, init_opt_state,
                                     opt_state_shardings)
from repro.models import build_model
from repro.sharding import param_shardings
from repro.launch.mesh import make_mesh


def run(kind: str, steps=15, gamma=0.02, transport="bucketed",
        gossip=GossipConfig(), overlap=OverlapConfig(),
        downlink="dense", downlink_gamma=0.0, faults=FaultConfig()):
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = get_smoke_config("yi-34b")
    model = build_model(cfg)
    run_cfg = RunConfig(
        model=cfg, shape=ShapeConfig("ex", 64, 8, "train"),
        optimizer=OptimizerConfig(kind=kind, armijo=ArmijoConfig(),
                                  compressor=Compressor(gamma=gamma,
                                                        min_compress_size=64),
                                  eta=0.05, transport=transport,
                                  gossip=gossip, overlap=overlap,
                                  downlink=downlink,
                                  downlink_gamma=GammaControllerConfig(
                                      gamma0=downlink_gamma),
                                  faults=faults))
    # links per worker uplink: the gossip worker sends its payload to each
    # of `degree` neighbors; gather/pmean transports send to the W-1 others
    if kind in ("csgd_asss", "nonadaptive") and transport == "gossip":
        n_links = build_topology(gossip.topology, 4).degree
    else:
        n_links = 4 - 1
    pipe = TokenPipeline(vocab_size=cfg.vocab_size, seq_len=64,
                         global_batch=8)
    with set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        params = jax.device_put(params, param_shardings(params, mesh))
        st = init_opt_state(params, run_cfg, 4,
                            stacked_mask=model.stacked_mask(params))
        st = jax.device_put(st, opt_state_shardings(st, params, mesh,
                                                    run_cfg))
        step_fn = None
        for i in range(steps):
            batch = pipe.batch(i)
            batch = jax.device_put(batch, jax.tree.map(
                lambda _: NamedSharding(mesh, P("data")), batch))
            if step_fn is None:
                step_fn = build_train_step(model, run_cfg, mesh)(params, batch)
            params, st, m = step_fn(params, st, batch)
            if i % 5 == 0 or i == steps - 1:
                wire = float(m["wire_bytes"])
                stale = (f" staleness={float(m['staleness']):.0f}"
                         if "staleness" in m else "")
                down = (f" down/link={float(m['downlink_wire_bytes']):.3e}"
                        if "downlink_wire_bytes" in m else "")
                hostile = (f" quar={float(m['rows_quarantined']):.0f}"
                           f" skips={float(m['steps_skipped']):.0f}"
                           if faults.enabled else "")
                print(f"  [{kind:9s}] step {i:3d} loss={float(m['loss']):.4f}"
                      f" alpha={float(m['alpha']):.4f}"
                      f" up/link={wire:.3e}"
                      f" uplink={n_links * wire:.3e}{down}"
                      f" backlog={float(m['ef_backlog']):.3f}"
                      f" cos={float(m['ef_cosine']):.3f}{stale}{hostile}")
    return float(m["wire_bytes"])


def run_federated(n_clients: int, clients_per_round: int,
                  aggregation: str, steps=15, gamma=0.05):
    """Non-IID cohort (DESIGN.md §13): W=4 dp workers vmap n_clients/4
    simulated clients each; one all_gather + one psum per round."""
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = get_smoke_config("yi-34b")
    model = build_model(cfg)
    run_cfg = RunConfig(
        model=cfg, shape=ShapeConfig("ex", 64, n_clients, "train"),
        optimizer=OptimizerConfig(
            kind="csgd_asss", armijo=ArmijoConfig(),
            compressor=Compressor(gamma=gamma, min_compress_size=64),
            eta=0.05,
            federated=FederatedConfig(
                n_clients=n_clients, clients_per_round=clients_per_round,
                aggregation=aggregation, dirichlet_alpha=0.3)))
    fed = run_cfg.optimizer.federated
    # client c IS shard c of the deterministic stream, Dirichlet-tilted
    cpipes = [TokenPipeline(vocab_size=cfg.vocab_size, seq_len=64,
                            global_batch=n_clients, seed=fed.seed,
                            n_shards=n_clients, shard=c,
                            dirichlet_alpha=fed.dirichlet_alpha)
              for c in range(n_clients)]
    with set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        params = jax.device_put(params, param_shardings(params, mesh))
        st = init_opt_state(params, run_cfg, 4)
        st = jax.device_put(st, opt_state_shardings(st, params, mesh,
                                                    run_cfg))
        step_fn = None
        for i in range(steps):
            rows = [p.batch_with_aux(i, cfg) for p in cpipes]
            batch = {k: jnp.stack([r[k] for r in rows]) for k in rows[0]}
            batch["participation"] = participation_mask(
                n_clients, i, seed=fed.seed, mode=fed.sampling,
                clients_per_round=clients_per_round)
            batch = {k: jax.device_put(v, NamedSharding(
                mesh, P() if k == "participation" else P("data")))
                for k, v in batch.items()}
            if step_fn is None:
                step_fn = build_train_step(model, run_cfg, mesh)(params,
                                                                 batch)
            params, st, m = step_fn(params, st, batch)
            if i % 5 == 0 or i == steps - 1:
                print(f"  [{aggregation:7s}] round {i:3d} "
                      f"loss={float(m['loss']):.4f} "
                      f"participants={float(m['participants']):.0f} "
                      f"gamma={float(m['gamma']):.4f} "
                      f"wire_bytes={float(m['wire_bytes']):.3e} "
                      f"eff={float(m['effective_wire_bytes']):.3e}")
    return float(m["loss"])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--transport", default="bucketed",
                    choices=list(transport_names()),
                    help="compressed-exchange schedule for the DCSGD run")
    ap.add_argument("--topology", default="ring",
                    choices=sorted(TOPOLOGIES),
                    help="gossip mixing graph (transport=gossip)")
    ap.add_argument("--consensus-lr", type=float, default=1.0,
                    help="AdaGossip consensus step numerator")
    ap.add_argument("--overlap-chunks", type=int,
                    default=OverlapConfig.n_chunks,
                    help="ring sections per gather axis "
                         "(transport=overlap, DESIGN.md §14)")
    ap.add_argument("--overlap-delay", type=int,
                    default=OverlapConfig.delay, choices=[0, 1],
                    help="1: ship the previous step's payload (overlapped,"
                         " one-step-stale aggregate); 0: bit-exact "
                         "bucketed drop-in")
    ap.add_argument("--downlink", default="dense",
                    choices=["dense", "compressed"],
                    help="aggregate return direction (DESIGN.md §15): "
                         "compressed = server-side EF re-compression "
                         "through the same wire format, no extra "
                         "collective")
    ap.add_argument("--downlink-gamma", type=float, default=0.0,
                    help="downlink compression level (0 = uplink gamma)")
    ap.add_argument("--n-clients", type=int, default=0,
                    help="> 0: federated cohort demo (DESIGN.md §13) — "
                         "support vs mean aggregation on non-IID shards")
    ap.add_argument("--clients-per-round", type=int, default=0,
                    help="participating clients per round (0: all)")
    ap.add_argument("--fault-demo", action="store_true",
                    help="hostile-wire demo (DESIGN.md §16): inject a "
                         "seeded 5-step fault burst into worker 0's "
                         "gathered rows and watch the quarantine/breaker "
                         "columns")
    ap.add_argument("--steps", type=int, default=15)
    args = ap.parse_args()

    if args.n_clients:
        k = args.clients_per_round or args.n_clients
        print(f"== federated cohort: {args.n_clients} non-IID clients, "
              f"{k}/round, support-weighted aggregation ==")
        loss_s = run_federated(args.n_clients, args.clients_per_round,
                               "support", steps=args.steps)
        print("== same cohort, dense zero-averaged mean ==")
        loss_m = run_federated(args.n_clients, args.clients_per_round,
                               "mean", steps=args.steps)
        print(f"\nfinal loss: support={loss_s:.4f} mean={loss_m:.4f} "
              f"(mean averages absent coordinates' zeros)")
        return
    if args.fault_demo:
        burst = FaultConfig(seed=7, p_bitflip=0.2, p_count=0.2,
                            p_nonfinite=0.4, worker=0,
                            start_step=5, n_steps=5)
        print("== DCSGD-ASSS under a 5-step hostile-wire burst on worker "
              "0 (steps 5-9; quarantine + breaker armed) ==")
        run("csgd_asss", steps=args.steps, transport=args.transport,
            faults=burst)
        return
    gossip = GossipConfig(topology=args.topology,
                          consensus_lr=args.consensus_lr)
    overlap = OverlapConfig(n_chunks=args.overlap_chunks,
                            delay=args.overlap_delay)

    mode = "compressed, per-worker Armijo"
    if args.transport == "gossip":
        mode += f", serverless {args.topology} gossip"
    elif args.transport == "overlap":
        mode += (f", chunked-ring overlap ({args.overlap_chunks} chunks, "
                 f"delay {args.overlap_delay})")
    if args.downlink == "compressed":
        mode += ", compressed downlink (server-side EF)"
    print(f"== DCSGD-ASSS ({mode}) ==")
    wire_c = run("csgd_asss", steps=args.steps, transport=args.transport,
                 gossip=gossip, overlap=overlap, downlink=args.downlink,
                 downlink_gamma=args.downlink_gamma)
    print("== dense SGD baseline (uncompressed all-reduce) ==")
    wire_d = run("dense", steps=args.steps)
    print(f"\ncommunication saving: {wire_d / wire_c:.1f}x "
          f"({wire_c:.2e} vs {wire_d:.2e} bytes/link/step)")


if __name__ == "__main__":
    main()
