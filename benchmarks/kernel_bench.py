"""Kernel micro-benchmarks: jnp reference path timings on CPU, plus
ref-vs-fused comparisons for the EF-compression two-pass hot loop.

NOTE: off-TPU the Pallas kernels run in interpret mode; for the model-side
ops (attention/wkv — per-tile Python stepping) interpret timings are not
meaningful for TPU projection, so those time the jnp reference path only
(numerics are verified in tests/test_kernels.py).  The EF kernels evaluate
one vectorized tile per grid step, so their interpret timings are reported
side-by-side with the ref path — on TPU the fused path is the default
(kernels/dispatch.py) and saves one full accumulator round-trip through
HBM (2 reads + 2 writes vs 3+ reads of a naive composition).

Besides the CSV rows on stdout, a machine-readable ``BENCH_kernels.json``
is written at the repo root — one record per (op, backend, shape) with the
median per-call milliseconds — so the perf trajectory is diffable across
PRs.  ``--smoke`` shrinks shapes/iterations to a seconds-scale run (the CI
invocation); ``--out`` overrides the JSON path.
"""
import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.launch.mesh import make_mesh
from .common import emit

# Representative per-layer gradient shapes from the production configs
# (qwen1.5-4b attention qkv, its MLP hidden, granite-moe expert slab).
EF_LAYER_SHAPES = [
    ("attn_qkv_2.5kx2.5k", (2560, 2560)),
    ("mlp_2.5kx6.9k", (2560, 6912)),
    ("moe_expert_8x1kx2k", (8, 1024 * 2048)),
]
EF_LAYER_SHAPES_SMOKE = [
    ("attn_qkv_256x256", (256, 256)),
    ("mlp_256x688", (256, 688)),
]

_RECORDS: list[dict] = []


def timeit(f, *args, n=20):
    """(median, min) per-call microseconds over n timed calls (1 warm-up).

    Both statistics of the SAME window travel together into ``record`` —
    bench_diff compares min_ms across runs because load bursts on shared
    runners inflate a whole median window but rarely every single call.
    """
    jax.block_until_ready(f(*args))
    times = []
    for _ in range(n):
        t0 = time.time()
        jax.block_until_ready(f(*args))
        times.append(time.time() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6, times[0] * 1e6


def record(op: str, backend: str, shape, us, note: str = "",
           min_us: float | None = None):
    """One BENCH_kernels.json record + the repo's CSV contract line.
    ``us`` is a (median, min) pair from :func:`timeit`, or a bare median
    with an explicit ``min_us`` (see timeit for why bench_diff keys off
    the window minimum)."""
    if isinstance(us, tuple):
        us, min_us = us
    if min_us is None:
        raise ValueError(f"record({op!r}): need a (median, min) timeit "
                         f"pair or an explicit min_us")
    _RECORDS.append({"op": op, "backend": backend,
                     "shape": list(shape) if not isinstance(shape, str)
                     else shape,
                     "median_ms": round(us / 1e3, 6),
                     "min_ms": round(min_us / 1e3, 6)})
    emit(f"kernel_{op}_{backend}", us, note or op)


def paired_ratio(f_num, f_den, args, n_pairs=12, repeats=3):
    """Robust wall-time ratio f_num/f_den: per-pair ratios of ADJACENT
    single calls (machine drift hits both sides of a pair equally), median
    per repeat, min over repeats (noise only inflates).  This is how the
    telemetry-fused EF op's "same streaming pass" claim is certified — two
    independently-timed medians are far too noisy on shared CI runners."""
    for f in (f_den, f_num):
        jax.block_until_ready(f(*args))
    meds = []
    for _ in range(repeats):
        ratios = []
        for _ in range(n_pairs):
            t0 = time.perf_counter()
            jax.block_until_ready(f_den(*args))
            td = time.perf_counter() - t0
            t0 = time.perf_counter()
            jax.block_until_ready(f_num(*args))
            ratios.append((time.perf_counter() - t0) / max(td, 1e-9))
        ratios.sort()
        meds.append(ratios[len(ratios) // 2])
    return min(meds)


def main(smoke: bool = False, out_path: str | None = None) -> dict:
    key = jax.random.PRNGKey(0)
    out = {}
    # smoke shapes are tiny, so more reps cost little and the medians are
    # stable enough for the bench-diff CI gate (benchmarks/bench_diff.py)
    n_heavy = 7 if smoke else 10
    n_light = 15 if smoke else 20

    ef_n = (1 << 14) if smoke else (1 << 20)
    m = jax.random.normal(key, (ef_n,))
    g = jax.random.normal(jax.random.fold_in(key, 1), (ef_n,))
    f_ef = jax.jit(lambda m, g: ops.ef_threshold_update(m, g, 0.1, 0.3))
    us = timeit(f_ef, m, g, n=n_light)
    record("ef_update", "default", (ef_n,), us,
           "fused EF accumulate+sparsify")
    out["ef"] = us[0]

    B, H, S, D = (1, 2, 128, 64) if smoke else (1, 8, 1024, 128)
    q = jax.random.normal(key, (B, H, S, D)) * 0.1
    k = jax.random.normal(jax.random.fold_in(key, 2), (B, H, S, D)) * 0.1
    v = jax.random.normal(jax.random.fold_in(key, 3), (B, H, S, D))
    f_at = jax.jit(lambda q, k, v: ops.attention(q, k, v))
    us = timeit(f_at, q, k, v, n=n_heavy)
    record("attention", "ref", (B, H, S, D), us,
           f"causal MHA {H}hx{S}x{D}")
    out["attn"] = us[0]

    R_rn = 256 if smoke else 4096
    x = jax.random.normal(key, (R_rn, 2048))
    w = jnp.ones((2048,))
    f_rn = jax.jit(lambda x, w: ops.rms_norm(x, w))
    us = timeit(f_rn, x, w, n=n_light)
    record("rmsnorm", "ref", (R_rn, 2048), us, "fused rmsnorm")
    out["rmsnorm"] = us[0]

    # ---- wire pack/unpack: ref vs pallas on a production payload shape ----
    # qwen1.5-4b MLP leaf at gamma=1%, value_bits=8: 2560 layer rows of
    # k=70 entries each -> 16-bit block-local indices + 8-bit values.
    R, kk = (256, 70) if smoke else (2560, 70)
    fields16 = jax.random.randint(key, (R, kk), 0, 1 << 16) \
        .astype(jnp.uint32)
    for bits in (8, 16):
        nwords = -(-kk * bits // 32)
        words = jax.random.randint(jax.random.fold_in(key, bits),
                                   (R, nwords), 0, 1 << 30) \
            .astype(jnp.uint32)
        row = {}
        for impl in ("ref", "pallas"):
            f_p = jax.jit(lambda f, impl=impl, bits=bits:
                          ops.pack_fields(f, bits, impl=impl))
            f_u = jax.jit(lambda w, impl=impl, bits=bits:
                          ops.unpack_fields(w, kk, bits, impl=impl))
            us_p = timeit(f_p, fields16, n=n_light)
            us_u = timeit(f_u, words, n=n_light)
            record(f"wire_pack{bits}", impl, (R, kk), us_p,
                   f"bit-pack {R}x{kk} {bits}b fields")
            record(f"wire_unpack{bits}", impl, (R, nwords), us_u,
                   f"bit-unpack {R}x{kk} {bits}b fields")
            row[impl] = us_p[0] + us_u[0]
        row["ratio_ref_over_fused"] = row["ref"] / max(row["pallas"], 1e-9)
        out[f"wire_pack{bits}"] = row

    # ragged variant: counts-aware pack (valid-count masking on the same
    # streaming pass, DESIGN.md §9) vs the plain kernel
    counts = jax.random.randint(jax.random.fold_in(key, 77), (R,), 1, kk) \
        .astype(jnp.int32)
    for impl in ("ref", "pallas"):
        f_r = jax.jit(lambda f, c, impl=impl: ops.pack_fields(
            f, 8, counts=c, period=kk, impl=impl))
        us_r = timeit(f_r, fields16, counts, n=n_light)
        record("wire_pack8_ragged", impl, (R, kk), us_r,
               f"counts-masked bit-pack {R}x{kk} 8b fields")

    # ---- ref vs fused EF two-pass compression on paper layer shapes ----
    shapes = EF_LAYER_SHAPES_SMOKE if smoke else EF_LAYER_SHAPES
    for si, (name, shape) in enumerate(shapes):
        m = jax.random.normal(key, shape)
        g = jax.random.normal(jax.random.fold_in(key, 100 + si), shape)
        row = {}
        for impl in ("ref", "pallas"):
            f = jax.jit(lambda m, g, impl=impl: ops.fused_ef_compress(
                m, g, 0.1, gamma=0.01, impl=impl))
            us = timeit(f, m, g, n=n_heavy)
            record(f"ef2pass_{name}", impl, shape, us,
                   f"fused two-pass EF, {m.size} elems")
            row[impl] = us[0]
        row["ratio_ref_over_fused"] = row["ref"] / max(row["pallas"], 1e-9)
        out[f"ef2pass_{name}"] = row

        # telemetry-enabled pass 1 (DESIGN.md §10): the moments ride the
        # same streamed tile, so this op must track ef2pass_* within the
        # "fused telemetry" budget.  The certificate is the PAIRED ratio
        # record (ef2pass_tel_ratio_*, dimensionless, stored in the
        # median_ms field) — bench_diff gates it at <= 1.10x; the tel
        # median itself is recorded for the cross-run trajectory.
        f_t = jax.jit(lambda m, g: ops.fused_ef_compress(
            m, g, 0.1, gamma=0.01, telemetry=True, impl="pallas"))
        f_p = jax.jit(lambda m, g: ops.fused_ef_compress(
            m, g, 0.1, gamma=0.01, impl="pallas"))
        us_t = timeit(f_t, m, g, n=n_heavy)
        record(f"ef2pass_tel_{name}", "pallas", shape, us_t,
               f"fused two-pass EF + telemetry moments, {m.size} elems")
        ratio = paired_ratio(f_t, f_p, (m, g))
        record(f"ef2pass_tel_ratio_{name}", "pallas", shape, ratio * 1e3,
               "paired tel/plain wall-time ratio (x1000, dimensionless)",
               min_us=ratio * 1e3)
        out[f"ef2pass_tel_{name}"] = {
            "pallas": us_t[0], "ratio_tel_over_plain": ratio}

    # ---- bucketed vs per-leaf transport on a multi-leaf pytree ----------
    # The bucketed exchange (DESIGN.md §11) trades per-leaf collectives and
    # launches for O(1) coalesced ones; on CPU (one XLA program, no real
    # launch overhead) the win is per-leaf op dispatch, so the honest
    # workload is leaf-HEAVY: the unstacked-transformer shape regime the
    # tentpole targets (dozens-to-hundreds of per-row leaves).  The PAIRED
    # ratio is hard-gated at 1.0x by bench_diff — bucketed must never be
    # slower than the per-leaf reference it replaced.
    import functools
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.core import Compressor
    from repro.core.dcsgd import worker_compress_aggregate

    n_leaves = 64 if smoke else 96
    tree = {f"w{i}": jax.random.normal(jax.random.fold_in(key, 300 + i),
                                       (1024,)) for i in range(n_leaves)}
    tree["s0"] = jax.random.normal(jax.random.fold_in(key, 400), (2, 1024))
    tree["s1"] = jax.random.normal(jax.random.fold_in(key, 401), (2, 1024))
    tree["dense"] = jax.random.normal(jax.random.fold_in(key, 402), (50,))
    mem = jax.tree.map(jnp.zeros_like, tree)
    eta = jnp.float32(0.1)
    comp = Compressor(gamma=0.05, method="block_topk", block=512,
                      min_compress_size=64, value_bits=8)
    tname = f"{n_leaves + 3}leaves"

    def _make_step(transport, ctx=None):
        mesh = make_mesh((1,), ("data",))
        pspec = jax.tree.map(lambda _: P(), tree)
        n_out = 6 if ctx is not None else 5
        return jax.jit(shard_map(
            functools.partial(worker_compress_aggregate, comp=comp,
                              dp_axes=("data",), transport=transport,
                              transport_ctx=ctx),
            mesh=mesh, in_specs=(pspec, pspec, P()),
            out_specs=(pspec, pspec) + (P(),) * (n_out - 2),
            axis_names={"data"}, check_vma=False))

    f_bucketed = _make_step("bucketed")
    f_perleaf = _make_step("perleaf")
    for impl, f in (("bucketed", f_bucketed), ("perleaf", f_perleaf)):
        us = timeit(f, tree, mem, eta, n=n_heavy)
        record("exchange_step", impl, tname, us,
               f"worker_compress_aggregate, {n_leaves + 3} leaves")
    # deeper pairing than the tel records: the 1.0x gate has no slack, so
    # min-over-5-repeats keeps a transient load burst from failing CI
    ratio = paired_ratio(f_bucketed, f_perleaf, (tree, mem, eta),
                         n_pairs=16, repeats=5)
    record(f"bucketed_vs_perleaf_step_{tname}", "default", tname,
           ratio * 1e3,
           "paired bucketed/perleaf wall-time ratio (x1000, dimensionless)",
           min_us=ratio * 1e3)
    out["bucketed_vs_perleaf"] = ratio

    # guarded vs unguarded decode (DESIGN.md §16): the always-on verdict/
    # quarantine layer vs the same exchange traced with the guards
    # compiled out (``guards_disabled()`` is a trace-time switch, so the
    # unguarded arm must compile INSIDE the context).  Hard-gated at
    # 1.05x by bench_diff: the hostile-wire defenses must stay ~free on
    # the clean-wire fast path.
    from repro.comm import faults

    with faults.guards_disabled():
        f_unguarded = _make_step("bucketed")
        jax.block_until_ready(f_unguarded(tree, mem, eta))
    us = timeit(f_unguarded, tree, mem, eta, n=n_heavy)
    record("exchange_step", "unguarded", tname, us,
           f"worker_compress_aggregate, guards compiled out, "
           f"{n_leaves + 3} leaves")
    ratio = paired_ratio(f_bucketed, f_unguarded, (tree, mem, eta),
                         n_pairs=16, repeats=5)
    record(f"guarded_vs_unguarded_step_{tname}", "default", tname,
           ratio * 1e3,
           "paired guarded/unguarded wall-time ratio "
           "(x1000, dimensionless)",
           min_us=ratio * 1e3)
    out["guarded_vs_unguarded"] = ratio

    # gossip vs bucketed on the same pytree (DESIGN.md §12): the single-
    # worker ring(1) graph is degree 0, so this prices the serverless
    # path's fixed overhead — same selection/encode stage plus the
    # self-row decode/consensus arithmetic, no collectives on either
    # side.  Recorded (not gated): the trajectory keeps the overhead
    # honest without a brittle cross-transport threshold.
    from repro.comm.gossip import GossipConfig, GossipCtx, GossipState
    from repro.comm.topology import build_topology
    ctx = GossipCtx(topology=build_topology("ring", 1),
                    cfg=GossipConfig(), state=GossipState.init(()))
    f_gossip = _make_step("gossip", ctx=ctx)
    us = timeit(f_gossip, tree, mem, eta, n=n_heavy)
    record("exchange_step", "gossip", tname, us,
           f"gossip worker_compress_aggregate, {n_leaves + 3} leaves")
    ratio = paired_ratio(f_gossip, f_bucketed, (tree, mem, eta),
                         n_pairs=16, repeats=5)
    record(f"gossip_vs_bucketed_step_{tname}", "default", tname,
           ratio * 1e3,
           "paired gossip/bucketed wall-time ratio (x1000, dimensionless)",
           min_us=ratio * 1e3)
    out["gossip_vs_bucketed"] = ratio

    # overlap vs bucketed on the same pytree (DESIGN.md §14).  The GATED
    # pair runs the transport at delay=0: the chunked-ring schedule as a
    # bit-exact drop-in for the flat bucketed gather — the same "not
    # slower than the path it replaced" claim the bucketed/perleaf gate
    # makes, measurable on a 1-worker mesh where both sides do identical
    # codec work.  delay=1 (the overlapped mode) is timed as its own
    # informational record: its extra cost here is exactly the
    # launch-free EF roundtrip that keeps the residual current under
    # staleness, while the hiding it buys — the collective running
    # concurrently with compute — needs a real network; XLA's CPU runtime
    # serializes collectives, so a single-device wall clock cannot see
    # it.  The carried state rides as a traced argument so XLA cannot
    # constant-fold the stale decode away.
    from repro.comm.overlap import (OverlapConfig, OverlapCtx,
                                    init_overlap_state)

    flat = jax.tree.leaves(tree)
    st = init_overlap_state([x.shape for x in flat],
                            [x.ndim >= 2 for x in flat], comp)
    mesh1 = make_mesh((1,), ("data",))
    pspec1 = jax.tree.map(lambda _: P(), tree)
    st_spec = jax.tree.map(lambda _: P(), st)

    def _make_overlap(ov_cfg):
        return jax.jit(shard_map(
            lambda g, m, e, s: worker_compress_aggregate(
                g, m, e, comp, ("data",), transport="overlap",
                transport_ctx=OverlapCtx(cfg=ov_cfg, state=s)),
            mesh=mesh1, in_specs=(pspec1, pspec1, P(), st_spec),
            out_specs=(pspec1, pspec1) + (P(),) * 3 + (st_spec,),
            axis_names={"data"}, check_vma=False))

    f_stale = _make_overlap(OverlapConfig(n_chunks=2, delay=1))
    us = timeit(f_stale, tree, mem, eta, st, n=n_heavy)
    record("exchange_step", "overlap", tname, us,
           f"overlap worker_compress_aggregate (delay=1), "
           f"{n_leaves + 3} leaves")
    f_ring = _make_overlap(OverlapConfig(n_chunks=2, delay=0))
    ratio = paired_ratio(f_ring,
                         lambda g, m, e, s: f_bucketed(g, m, e),
                         (tree, mem, eta, st), n_pairs=16, repeats=5)
    record(f"bucketed_vs_overlap_step_{tname}", "default", tname,
           ratio * 1e3,
           "paired overlap(delay=0)/bucketed wall-time ratio "
           "(x1000, dimensionless)",
           min_us=ratio * 1e3)
    out["bucketed_vs_overlap"] = ratio

    # compressed downlink vs dense return (DESIGN.md §15): the same
    # bucketed exchange with the physically-simulated server bolted on —
    # one extra compress + launch-free wire roundtrip per compressed
    # leaf group, zero extra collectives (HLO-pinned in
    # tests/distributed/test_hlo_collectives.py).  The paired
    # dense_vs_downlink factor is informational in bench_diff: the
    # replicated recompute is the price of halving the accounted link
    # bytes, a design trade rather than a fusion claim.
    from repro.comm.downlink import (DownlinkCtx, DownlinkResult,
                                     DownlinkState, init_downlink_state)

    dls = init_downlink_state([x.shape for x in flat],
                              [x.ndim >= 2 for x in flat], comp,
                              comp.gamma)
    dl_spec = DownlinkState(memory=P(), gamma=P())
    f_downlink = jax.jit(shard_map(
        lambda g, m, e, s: worker_compress_aggregate(
            g, m, e, comp, ("data",),
            downlink_ctx=DownlinkCtx(state=s)),
        mesh=mesh1, in_specs=(pspec1, pspec1, P(), dl_spec),
        out_specs=(pspec1, pspec1) + (P(),) * 3
        + (DownlinkResult(dl_spec, P(), P()),),
        axis_names={"data"}, check_vma=False))
    us = timeit(f_downlink, tree, mem, eta, dls, n=n_heavy)
    record("downlink_step", "compressed", tname, us,
           f"worker_compress_aggregate + server recompression, "
           f"{n_leaves + 3} leaves")
    ratio = paired_ratio(lambda g, m, e, s: f_downlink(g, m, e, s),
                         lambda g, m, e, s: f_bucketed(g, m, e),
                         (tree, mem, eta, dls), n_pairs=16, repeats=5)
    record(f"dense_vs_downlink_step_{tname}", "default", tname,
           ratio * 1e3,
           "paired downlink/dense-return wall-time ratio "
           "(x1000, dimensionless)",
           min_us=ratio * 1e3)
    out["dense_vs_downlink"] = ratio

    # ---- federated cohort step (DESIGN.md §13) --------------------------
    # The vmap'd heterogeneous-client exchange, single device (dp_axes=
    # None: the whole cohort local, no collectives — what scales here is
    # the batched selection/encode, so clients/sec is the honest axis).
    # Informational in bench_diff: simulation throughput is a capacity
    # number, not a fusion claim.
    from repro.fed.clients import cohort_compress_aggregate

    comp_fed = Compressor(gamma=0.02, method="topk", min_compress_size=64,
                          value_bits=32, use_kernel=False, max_gamma=0.2)
    cohort_sizes = [16, 64] if smoke else [64, 256, 1024]
    f_fed = jax.jit(functools.partial(
        cohort_compress_aggregate, comp=comp_fed, dp_axes=None,
        aggregation="support"))
    for nc in cohort_sizes:
        gf = {"w": jax.random.normal(jax.random.fold_in(key, 500 + nc),
                                     (nc, 2, 1024)),
              "v": jax.random.normal(jax.random.fold_in(key, 501 + nc),
                                     (nc, 4096))}
        mf = jax.tree.map(jnp.zeros_like, gf)
        eta_c = jnp.full((nc,), 0.1, jnp.float32)
        gamma_c = jnp.linspace(0.02, 0.2, nc, dtype=jnp.float32)
        ones = jnp.ones((nc,), jnp.float32)
        us = timeit(lambda g, m, e, gc, p: f_fed(
            g, m, e, participation=p, gamma_c=gc),
            gf, mf, eta_c, gamma_c, ones, n=n_heavy)
        record(f"fed_cohort_step_{nc}c", "default", (nc,), us,
               f"cohort exchange, {nc} clients, "
               f"{nc / (us[0] / 1e6):,.0f} clients/s median")
        out[f"fed_cohort_step_{nc}c"] = us[0]

    path = out_path or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_kernels.json")
    with open(path, "w") as fh:
        json.dump({"backend": jax.default_backend(), "smoke": smoke,
                   "records": _RECORDS}, fh, indent=1)
    print(f"wrote {len(_RECORDS)} records -> {path}")
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale shapes/iterations (CI)")
    ap.add_argument("--out", default=None, help="JSON output path")
    a = ap.parse_args()
    main(smoke=a.smoke, out_path=a.out)
