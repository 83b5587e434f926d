import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes and extract the raw per-chip cost terms.

MUST be run as its own process (the XLA flag above must precede any jax
import — which is why it is the very first statement of the module).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] \
        --out results/dryrun.json

Outputs one JSON record per combination: compile ok, per-device HLO FLOPs /
bytes (cost_analysis), memory stats, and per-collective wire bytes parsed
from the partitioned HLO.
"""
import argparse
import dataclasses
import json
import re
import time
import traceback

import jax

from jax import set_mesh
import jax.numpy as jnp

from repro.comm.faults import FaultConfig
from repro.comm.gossip import GossipConfig
from repro.comm.overlap import OverlapConfig
from repro.comm.topology import TOPOLOGIES
from repro.comm.transport import transport_names
from repro.configs import ARCH_NAMES, SHAPES, get_config
from repro.configs.base import (FederatedConfig, OptimizerConfig, RunConfig,
                                ShapeConfig)
from repro.core.armijo import ArmijoConfig
from repro.core.compression import Compressor
from repro.core.gamma import GammaControllerConfig
from repro.launch.mesh import make_production_mesh
from repro.launch.train_step import (build_decode_step, build_prefill_step,
                                     build_train_step, init_opt_state)
from repro.models import build_model

# ---------------------------------------------------------------------------
# HLO analysis — computation-structured and TRIP-COUNT AWARE.
#
# XLA's compiled cost_analysis() counts while-loop bodies ONCE (verified
# empirically: a 10-layer scan reports 1 layer of FLOPs), so naive parsing
# undercounts anything inside the layer scan by ~n_layers.  We therefore
# walk the HLO computation graph: per computation we account matmul FLOPs
# (from dot shapes), buffer traffic (2x non-fused instruction result bytes —
# fusion internals never hit HBM) and collective wire bytes; `while` ops
# multiply their body's totals by the trip count recovered from the loop
# condition's s32 constant.  Scan trip counts are exact; the Armijo search
# loop is data-dependent, so the dry-run pins its iteration cap to the
# *expected* evaluation count (~2 per the paper §IV-B and our measured
# 1.7-1.9) — see make_run_config.
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1, "s32": 4, "u32": 4,
                "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(pred|bf16|f8e4m3fn|f8e5m2|[sufc]\d+)\[([\d,]*)\]")
_GROUP_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUP_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_COMP_HDR_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_INSTR_RE = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*((?:\([^()]*\))|\S+)\s+([a-z][\w\-]*)\(")
_WHILE_RE = re.compile(r"condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_DOT_DIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_DOT_ARGS_RE = re.compile(r"dot\(%?([\w.\-]+),")
_CONST_RE = re.compile(r"=\s*s32\[\]\s*constant\((\d+)\)")

_SKIP_BYTES_OPS = {"parameter", "get-tuple-element", "tuple", "constant",
                   "bitcast", "copy", "after-all", "partition-id",
                   "replica-id", "iota", "broadcast"}


def _shape_dims(type_str: str) -> list[tuple[str, list[int]]]:
    out = []
    for dt, dims in _SHAPE_RE.findall(type_str):
        out.append((dt, [int(d) for d in dims.split(",") if d]))
    return out


def _tensor_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _shape_dims(type_str):
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def _coll_wire(kind: str, nbytes: int, n: int) -> float:
    frac = (n - 1) / max(n, 1)
    if kind == "all-reduce":
        return 2 * nbytes * frac
    if kind == "collective-permute":
        return float(nbytes)
    if kind == "reduce-scatter":
        return float(nbytes * (n - 1))   # result is 1/n of the input
    return nbytes * frac                 # all-gather, all-to-all


def parse_hlo(hlo_text: str, *, ring_schedule: bool = False) -> dict:
    """Trip-count-aware per-chip totals: matmul FLOPs, buffer-traffic bytes,
    collective wire bytes (per kind) — all from the partitioned HLO.

    ``ring_schedule``: the permute ops form a send-right ring (the overlap
    transport, DESIGN.md §14) rather than a neighbor-fanout graph (gossip):
    every one of the ``n_chunks * (W-1)`` hops traverses the SAME physical
    i -> i+1 link (per hop: payload/W of the gathered total, over W-1
    steps), so the per-link figure keeps the FULL permute total instead of
    dividing by the permute count."""
    # ---- split into computations -----------------------------------------
    comps: dict[str, list[str]] = {}
    entry = None
    cur = None
    for line in hlo_text.splitlines():
        if not line.startswith(" ") and line.rstrip().endswith("{"):
            m = _COMP_HDR_RE.match(line.rstrip())
            if m:
                cur = m.group(2)
                comps[cur] = []
                if m.group(1):
                    entry = cur
                continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line)

    # ---- instruction result types (for dot operand shapes) ---------------
    types: dict[str, str] = {}
    for lines in comps.values():
        for line in lines:
            m = _INSTR_RE.match(line)
            if m:
                types[m.group(1)] = m.group(2)

    # ---- per-computation local stats --------------------------------------
    local = {}
    for name, lines in comps.items():
        flops = 0.0
        bytes_ = 0.0
        wire: dict[str, float] = {}
        ccount: dict[str, int] = {}
        whiles: list[tuple[str, str]] = []
        for line in lines:
            m = _INSTR_RE.match(line)
            if not m:
                continue
            res_name, res_type, op = m.group(1), m.group(2), m.group(3)
            if op == "while":
                w = _WHILE_RE.search(line)
                if w:
                    whiles.append((w.group(1), w.group(2)))
            cm = _COLL_RE.search(line)
            if cm:
                kind = cm.group(2)
                nb = _tensor_bytes(cm.group(1))
                g = _GROUP_RE.search(line)
                if g:
                    n = len(g.group(1).split(","))
                else:
                    gi = _GROUP_IOTA_RE.search(line)
                    n = int(gi.group(2)) if gi else 2
                wire[kind] = wire.get(kind, 0.0) + _coll_wire(kind, nb, n)
                ccount[kind] = ccount.get(kind, 0) + 1
            if op == "dot":
                dims = _shape_dims(res_type)
                res_n = 1
                for _, ds in dims:
                    for d in ds:
                        res_n *= d
                contract = 1
                a = _DOT_ARGS_RE.search(line)
                c = _DOT_DIMS_RE.search(line)
                if a and c and a.group(1) in types:
                    lhs_dims = _shape_dims(types[a.group(1)])
                    if lhs_dims:
                        ds = lhs_dims[0][1]
                        for ci in (int(x) for x in c.group(1).split(",") if x):
                            if ci < len(ds):
                                contract *= ds[ci]
                flops += 2.0 * res_n * contract
            if op not in _SKIP_BYTES_OPS:
                bytes_ += 2.0 * _tensor_bytes(res_type)
        local[name] = dict(flops=flops, bytes=bytes_, wire=wire,
                           counts=ccount, whiles=whiles)

    # ---- trip counts from loop conditions ---------------------------------
    def trip(cond_name: str) -> int:
        lines = comps.get(cond_name, [])
        best = 1
        for line in lines:
            m = _CONST_RE.search(line)
            if m:
                best = max(best, int(m.group(1)))
        return best

    # ---- recursive totals --------------------------------------------------
    memo: dict[str, dict] = {}

    def total(name: str) -> dict:
        if name in memo:
            return memo[name]
        base = local.get(name, dict(flops=0, bytes=0, wire={}, counts={},
                                    whiles=[]))
        agg = dict(flops=float(base["flops"]), bytes=float(base["bytes"]),
                   wire=dict(base["wire"]), counts=dict(base["counts"]))
        memo[name] = agg   # break cycles defensively
        for cond, body in base["whiles"]:
            t = trip(cond)
            sub = total(body)
            agg["flops"] += t * sub["flops"]
            agg["bytes"] += t * sub["bytes"]
            for k, v in sub["wire"].items():
                agg["wire"][k] = agg["wire"].get(k, 0.0) + t * v
            for k, v in sub["counts"].items():
                agg["counts"][k] = agg["counts"].get(k, 0) + t * v
        return agg

    if entry is None:
        entry = max(comps, key=lambda n: len(comps[n])) if comps else ""
    agg = total(entry)
    out = dict(agg["wire"])
    out["total_wire_bytes"] = sum(agg["wire"].values())
    out["counts"] = agg["counts"]
    # per-LINK bytes: collective-permute totals count every neighbor
    # direction (the gossip transport issues ``degree`` of them per
    # exchange), so the per-step figure comparable across transports
    # divides the permute total by the permute count — one link's
    # payload — while the star-shaped collectives pass through unchanged.
    # The ring schedule is the exception: its permutes all share one
    # physical link, so the full total IS the per-link figure.
    perm = out.get("collective-permute", 0.0)
    n_perm = agg["counts"].get("collective-permute", 0)
    per_link_perm = perm if ring_schedule else \
        (perm / n_perm if n_perm else 0.0)
    out["wire_bytes_per_link"] = (out["total_wire_bytes"] - perm) \
        + per_link_perm
    return {
        "collectives": out,
        "hlo_matmul_flops": agg["flops"],
        "hlo_traffic_bytes": agg["bytes"],
    }


# ---------------------------------------------------------------------------
# per-combination lowering
# ---------------------------------------------------------------------------

def make_run_config(cfg, shape, opt_kind="csgd_asss", gamma=0.01,
                    microbatches=None, ef_host_offload=False,
                    ef_dtype="float32", shard_local_topk=False,
                    local_steps=1, transport="bucketed", topology="ring",
                    n_clients=0, aggregation="support",
                    overlap_chunks=1, overlap_delay=1,
                    downlink="dense", downlink_gamma=0.0, faults=None):
    if microbatches is None:
        microbatches = 4 if shape.kind == "train" else 1
    if n_clients:
        microbatches = 1   # each client IS a batch row group
    # max_backtracks=2 pins the Armijo while loop's HLO trip-count constant
    # to the paper's expected ~2 condition evaluations per step (we measure
    # 1.7-1.9 on real runs), so the trip-count-aware cost analysis charges
    # the search its EXPECTED cost.  Execution semantics on TPU are unchanged
    # apart from the iteration cap (dynamic early exit still applies).
    return RunConfig(
        model=cfg, shape=shape,
        optimizer=OptimizerConfig(
            kind=opt_kind, armijo=ArmijoConfig(max_backtracks=2),
            compressor=Compressor(gamma=gamma),
            ef_host_offload=ef_host_offload, ef_dtype=ef_dtype,
            shard_local_topk=shard_local_topk, local_steps=local_steps,
            transport=transport,
            gossip=GossipConfig(topology=topology),
            overlap=OverlapConfig(n_chunks=overlap_chunks,
                                  delay=overlap_delay),
            federated=FederatedConfig(n_clients=n_clients,
                                      aggregation=aggregation),
            downlink=downlink,
            downlink_gamma=GammaControllerConfig(gamma0=downlink_gamma),
            faults=faults if faults is not None else FaultConfig()),
        microbatches=microbatches)


def federate_input_specs(batch_like, n_clients: int):
    """Reshape abstract batch specs to the cohort layout: every data leaf
    (B, ...) -> (n_clients, B/n_clients, ...) + the participation row."""
    out = {}
    for k, v in batch_like.items():
        assert v.shape[0] % n_clients == 0, \
            f"batch dim {v.shape[0]} must divide across {n_clients} clients"
        out[k] = jax.ShapeDtypeStruct(
            (n_clients, v.shape[0] // n_clients) + tuple(v.shape[1:]),
            v.dtype)
    out["participation"] = jax.ShapeDtypeStruct((n_clients,), jnp.float32)
    return out


def adapt_for_shape(cfg, shape: ShapeConfig):
    """long_500k on pure full-attention archs -> sliding-window variant
    (DESIGN.md §5); returns (cfg, variant_note)."""
    if shape.name == "long_500k" and not cfg.subquadratic:
        if not cfg.swa_for_long_context:
            return None, "skipped (full attention, no SWA variant)"
        return dataclasses.replace(
            cfg, sliding_window=cfg.long_context_window), \
            f"sliding_window={cfg.long_context_window}"
    if shape.name == "long_500k" and cfg.family in ("hybrid", "encdec"):
        # hybrid/encdec attention sub-blocks also get the window at 500k
        return dataclasses.replace(
            cfg, sliding_window=cfg.long_context_window), \
            f"attn blocks windowed @{cfg.long_context_window}"
    return cfg, ""


def lower_one(arch: str, shape_name: str, *, multi_pod: bool = False,
              opt_kind: str = "csgd_asss", gamma: float = 0.01,
              microbatches: int | None = None, ef_host_offload: bool = False,
              ef_dtype: str = "float32", shard_local_topk: bool = False,
              seq_parallel: bool = False, params_2d: bool = False,
              moe_ep: bool = False, capacity_factor: float = None,
              kv_int8: bool = False, local_steps: int = 1,
              transport: str = "bucketed", topology: str = "ring",
              n_clients: int = 0, aggregation: str = "support",
              overlap_chunks: int = 1, overlap_delay: int = 1,
              downlink: str = "dense", downlink_gamma: float = 0.0,
              faults=None, keep_hlo: bool = False) -> dict:
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "opt": opt_kind if shape_name == "train_4k" else "-",
           "gamma": gamma,
           "flags": {"shard_local_topk": shard_local_topk,
                     "params_2d": params_2d,
                     "moe_ep": moe_ep,
                     "ef_dtype": ef_dtype,
                     "ef_host_offload": ef_host_offload,
                     "seq_parallel": seq_parallel,
                     "microbatches": microbatches,
                     "transport": transport,
                     "topology": topology,
                     "overlap_chunks": overlap_chunks,
                     "overlap_delay": overlap_delay,
                     "downlink": downlink}}
    shape = SHAPES[shape_name]
    cfg0 = get_config(arch)
    cfg, note = adapt_for_shape(cfg0, shape)
    rec["variant"] = note
    if cfg is None:
        rec["status"] = "skipped"
        return rec

    if seq_parallel:
        cfg = dataclasses.replace(cfg, seq_parallel=True)
    if moe_ep:
        cfg = dataclasses.replace(cfg, moe_expert_parallel=True)
    if capacity_factor is not None:
        cfg = dataclasses.replace(cfg, capacity_factor=capacity_factor)
    if kv_int8:
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    run = make_run_config(cfg, shape, opt_kind, gamma, microbatches,
                          ef_host_offload, ef_dtype, shard_local_topk,
                          local_steps, transport, topology,
                          n_clients, aggregation,
                          overlap_chunks, overlap_delay,
                          downlink, downlink_gamma, faults)
    n_chips = mesh.size

    with set_mesh(mesh):
        key_like = jax.ShapeDtypeStruct((2,), jnp.uint32)
        params_like = jax.eval_shape(model.init, key_like)
        rec["n_params"] = int(sum(x.size for x in jax.tree.leaves(params_like)))

        if shape.kind == "train":
            from repro.sharding import dp_axes_of
            import math as _m
            W = _m.prod(mesh.shape[a] for a in dp_axes_of(mesh))
            batch_like = model.input_specs(shape)
            if n_clients:
                batch_like = federate_input_specs(batch_like, n_clients)
            opt_like = init_opt_state(
                params_like, run, W, abstract=True,
                stacked_mask=model.stacked_mask(params_like))
            step = build_train_step(model, run, mesh)(params_like, batch_like)
            lowered = step.lower(params_like, opt_like, batch_like)
            if opt_kind in ("csgd_asss", "nonadaptive", "acgd"):
                # per-direction split (DESIGN.md §15): the collectives
                # parsed from HLO below carry only the UPLINK — the
                # downlink is physically simulated (replicated compute,
                # no collective), so its per-link bytes are accounted
                # from the same static plan the server uses
                from repro.comm.downlink import (dense_downlink_bytes,
                                                 downlink_plan,
                                                 downlink_wire_bytes)
                flat_p, treedef = jax.tree.flatten(params_like)
                flags = treedef.flatten_up_to(
                    model.stacked_mask(params_like))
                plan = downlink_plan([p.shape for p in flat_p], flags,
                                     run.optimizer.compressor)
                dense_b = dense_downlink_bytes([p.shape for p in flat_p])
                rec["downlink"] = {
                    "mode": downlink,
                    "bytes_per_link": (downlink_wire_bytes(plan)
                                       if downlink == "compressed"
                                       else dense_b),
                    "dense_bytes_per_link": dense_b,
                }
        elif shape.kind == "prefill":
            batch_like = model.input_specs(shape)
            step = build_prefill_step(model, run, mesh, shape,
                                      params_2d=params_2d)(
                params_like, batch_like)
            lowered = step.lower(params_like, batch_like)
        else:  # decode
            B, S = shape.global_batch, shape.seq_len
            if cfg.family == "encdec":
                cache_like = jax.eval_shape(
                    lambda: model.init_cache(B, S, s_enc=S // 2))
            else:
                cache_like = jax.eval_shape(lambda: model.init_cache(B, S))
            token_like = jax.ShapeDtypeStruct((B, 1), jnp.int32)
            step = build_decode_step(model, run, mesh, shape,
                                     params_2d=params_2d)(
                params_like, token_like, cache_like)
            lowered = step.lower(params_like, token_like, cache_like,
                                 jnp.int32(S - 1))
        rec["lower_s"] = round(time.time() - t0, 2)

        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

        ca = compiled.cost_analysis()
        # raw XLA numbers (per-device, while-bodies counted ONCE — kept as
        # diagnostics; the trip-count-aware numbers below are authoritative)
        rec["xla_flops_body_once"] = float(ca.get("flops", 0.0))
        rec["xla_bytes_body_once"] = float(ca.get("bytes accessed", 0.0))
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "code_bytes": int(ma.generated_code_size_in_bytes),
            "host_argument_bytes": int(ma.host_argument_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
        hlo = compiled.as_text()
        parsed = parse_hlo(hlo, ring_schedule=(transport == "overlap"))
        rec["collectives"] = parsed["collectives"]
        rec["flops_per_chip"] = parsed["hlo_matmul_flops"]
        rec["bytes_per_chip"] = parsed["hlo_traffic_bytes"]
        if keep_hlo:
            rec["hlo_len"] = len(hlo)
        rec["n_chips"] = n_chips
        rec["status"] = "ok"
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--opt", default="csgd_asss",
                    choices=["csgd_asss", "nonadaptive", "acgd", "sgd",
                             "dense", "sls"])
    ap.add_argument("--gamma", type=float, default=0.01)
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--ef-host-offload", action="store_true")
    ap.add_argument("--ef-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--shard-local-topk", action="store_true")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--params-2d", action="store_true",
                    help="serving: shard weights over data axis too")
    ap.add_argument("--moe-ep", action="store_true",
                    help="explicit expert-parallel MoE shard_map")
    ap.add_argument("--capacity-factor", type=float, default=None)
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8 self-attention KV cache")
    ap.add_argument("--local-steps", type=int, default=1)
    ap.add_argument("--transport", default="bucketed",
                    choices=list(transport_names()),
                    help="compressed-exchange schedule (DESIGN.md §11/§12)")
    ap.add_argument("--topology", default="ring",
                    choices=sorted(TOPOLOGIES),
                    help="gossip mixing graph (transport=gossip)")
    ap.add_argument("--overlap-chunks", type=int,
                    default=OverlapConfig.n_chunks,
                    help="transport=overlap: ring chunk count (DESIGN.md "
                         "§14); the per-link accounting charges the FULL "
                         "permute total — every hop shares one link")
    ap.add_argument("--overlap-delay", type=int,
                    default=OverlapConfig.delay, choices=[0, 1],
                    help="transport=overlap: 1 = ship the previous step's "
                         "payload (double-buffered), 0 = synchronous")
    ap.add_argument("--n-clients", type=int, default=0,
                    help="> 0: lower the federated cohort train step "
                         "(n-clients/W vmapped clients per dp worker)")
    ap.add_argument("--aggregation", default="support",
                    choices=["support", "mean"],
                    help="cohort aggregation (federated mode)")
    ap.add_argument("--downlink", default="dense",
                    choices=["dense", "compressed"],
                    help="aggregate return direction (DESIGN.md §15): "
                         "compressed = server-side EF re-compression, "
                         "accounted per link in the record's 'downlink' "
                         "block (no collective — it is simulated)")
    ap.add_argument("--downlink-gamma", type=float, default=0.0,
                    help="downlink compression level (0 = uplink gamma)")
    # ---- hostile-wire robustness (DESIGN.md §16) ----
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--fault-bitflip", type=float, default=0.0,
                    help="per-row wire bit-flip probability — lowers the "
                         "train step through the 'faulty' transport wrapper "
                         "so the injected-HLO collective schedule can be "
                         "audited")
    ap.add_argument("--fault-count", type=float, default=0.0,
                    help="per-row corrupt ragged-count probability")
    ap.add_argument("--fault-nonfinite", type=float, default=0.0,
                    help="per-row NaN/Inf scale-or-value probability")
    ap.add_argument("--fault-zero-row", type=float, default=0.0,
                    help="per-row whole-row zeroing probability")
    ap.add_argument("--fault-worker", type=int, default=-1,
                    help="gathered row-slot to target (-1 = all)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    combos = []
    if args.all:
        for arch in ARCH_NAMES:
            for shape in SHAPES:
                combos.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        combos = [(args.arch, args.shape)]

    records = []
    for arch, shape in combos:
        try:
            rec = lower_one(arch, shape, multi_pod=args.multi_pod,
                            opt_kind=args.opt, gamma=args.gamma,
                            microbatches=args.microbatches,
                            ef_host_offload=args.ef_host_offload,
                            ef_dtype=args.ef_dtype,
                            shard_local_topk=args.shard_local_topk,
                            seq_parallel=args.seq_parallel,
                            params_2d=args.params_2d,
                            moe_ep=args.moe_ep,
                            capacity_factor=args.capacity_factor,
                            kv_int8=args.kv_int8,
                            local_steps=args.local_steps,
                            transport=args.transport,
                            topology=args.topology,
                            n_clients=args.n_clients,
                            aggregation=args.aggregation,
                            overlap_chunks=args.overlap_chunks,
                            overlap_delay=args.overlap_delay,
                            downlink=args.downlink,
                            downlink_gamma=args.downlink_gamma,
                            faults=FaultConfig(
                                seed=args.fault_seed,
                                p_bitflip=args.fault_bitflip,
                                p_count=args.fault_count,
                                p_nonfinite=args.fault_nonfinite,
                                p_zero_row=args.fault_zero_row,
                                worker=args.fault_worker))
        except Exception as e:  # record failures — they are bugs to fix
            rec = {"arch": arch, "shape": shape, "status": "FAIL",
                   "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
        status = rec["status"]
        colls = rec.get("collectives", {})
        dl = rec.get("downlink", {})
        down = (f"down/link={dl['bytes_per_link']:.3e} "
                if dl else "")
        print(f"[{status:7s}] {arch:24s} {shape:12s} "
              f"flops/chip={rec.get('flops_per_chip', 0):.3e} "
              f"wire={colls.get('total_wire_bytes', 0):.3e} "
              f"up/link={colls.get('wire_bytes_per_link', 0):.3e} "
              f"{down}"
              f"compile={rec.get('compile_s', 0)}s", flush=True)
        records.append(rec)

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {len(records)} records -> {args.out}")


if __name__ == "__main__":
    main()
