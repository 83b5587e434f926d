"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process: set-up (weights from the seed, the cell's train step
compiled or read from the compile cache, the checked first steps), the
measured window of ``--seconds``, and then, with the program's state
freed, the reference's first steps and the comparison that decides
``correct``.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of the
window's first steps.  The last line of standard output is one JSON
object; the numbers compared, each with its limit, are the last lines of
standard error and the last key of that object.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

NO_CHIP = 2


class NoChip(RuntimeError):
    pass


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_reader(name: str):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def run_cell(args, *, require_chip: bool = True, step_wrapper=None,
             feed=None, root: pathlib.Path = ROOT, out=sys.stdout,
             compile_cache: bool = True):
    """One run: returns its result, the program's and the reference's
    records of the checked steps, and the metric readers' context.
    ``require_chip=False``, ``step_wrapper``, ``feed`` and
    ``compile_cache=False`` are for the tests, which drive a run on the
    CPU with the step broken."""
    import jax
    from bench import check, harness, manifest, weights

    man = manifest.load(root)
    entry = manifest.resolve(man, args.workload, root)
    chips = entry["cell"]["chips"]
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < chips):
        raise NoChip(f"cell {args.workload} needs {chips} TPU chip(s); "
                     f"JAX finds {len(devices)} {devices[0].platform} "
                     f"device(s)")
    devices = devices[:chips]
    if compile_cache:
        harness.env_cache_dir(root)
    traffic, config, arch = entry["traffic"], entry["config"], entry["arch"]
    seed = args.seed

    tr = harness.Trainer(config, traffic, arch, devices=devices)
    params, state = tr.fresh_state(seed)
    batch = tr.put(tr.host_batch(seed, 0))
    compile_s = tr.compile(params, state, batch)
    del batch
    if require_chip:
        off = {op: impls for op, impls in tr.ops.items()
               if set(impls) != {"pallas-tpu"}}
        if off:
            raise RuntimeError(f"ops off the TPU kernels: {off}")
    if step_wrapper is not None:
        tr.call = step_wrapper(tr.call)
    n_check = traffic["check_steps"]
    params, state, prog = harness.check_steps(tr, seed, params, state,
                                              n_check, feed=feed)
    setup_s = time.perf_counter() - T_START

    # ---- measured window ------------------------------------------------
    traced = bool(args.trace)
    steps, events, logdir = [], None, None
    losses = list(prog["loss"])
    step = n_check
    if traced:
        logdir = harness.profile_dir()
        harness.start_trace(logdir)
    t0 = time.perf_counter()
    while True:
        on = traced and len(steps) < traffic["trace_steps"]
        params, state, met, host = tr.one_step(seed, step, params, state,
                                               traced=on, feed=feed)
        steps.append({"host_s": host, "metrics": met})
        step += 1
        if traced and len(steps) == traffic["trace_steps"]:
            t_trace = time.perf_counter()
            events = harness.stop_trace(logdir)
            t0 += time.perf_counter() - t_trace
        if time.perf_counter() - t0 >= args.seconds:
            break
    window_s = time.perf_counter() - t0
    for s in steps:
        s["metrics"] = {k: float(s["metrics"][k]) for k in harness.METRIC_KEYS}
    losses += [s["metrics"]["loss"] for s in steps]
    if traced:
        while len(losses) < traffic["loss_steps"]:
            params, state, met, _ = tr.one_step(seed, len(losses), params,
                                                state, feed=feed)
            losses.append(float(met["loss"]))
    peak = harness.peak_memory(devices, tr.step_fn)
    tokens_per_step = tr.tokens_per_step
    harness.free(params, state)
    del tr

    # ---- correctness: the reference's first steps -----------------------
    t_ref = time.perf_counter()
    ref = harness.reference_record(config, traffic, arch, seed, n_check,
                                   devices=devices)
    nums = check.numbers(prog, ref)
    correct, rows = check.verdict(nums, entry["limits"])
    ref_s = time.perf_counter() - t_ref

    # the step's breaker skips every round whose loss or update is not
    # finite: the window's failures are the skips it added
    failed = int(steps[-1]["metrics"]["steps_skipped"] - prog["skipped"])
    m, seq = config["model"], traffic["seq_len"] - 1
    worker_rows = traffic["global_batch"] // traffic["mesh"][0]
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    kind = devices[0].device_kind
    if require_chip and kind not in peaks["devices"]:
        raise RuntimeError(f"no peaks for device kind {kind!r}")
    # off the chip (the tests) the rates are read against the v5e row
    peak_row = peaks["devices"].get(kind, peaks["devices"]["TPU v5 lite"])
    ctx = {
        "model": m, "traffic": traffic,
        "steps": steps, "window_s": window_s, "losses": losses,
        "tokens_per_step": tokens_per_step,
        "flops_per_token": arch.train_flops_per_token(m, seq),
        "flash_forward_flops": arch.flash_forward_flops(m, worker_rows, seq),
        "chips": len(devices), "peak": peak_row, "setup_s": setup_s,
        "events": events, "opt": harness.optimizer_of(traffic),
        "arch": arch, "shapes": weights.shapes(arch, m),
    }
    kind_key = "per_layer" if traced else "end_to_end"
    metrics = {}
    for spec in manifest.metrics_of(man, args.workload, kind_key):
        v = load_reader(spec["name"])(ctx)
        if v is not None:
            metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    dev = device_info(devices)
    dev["memory_peak_bytes"] = peak
    result = {"correct": bool(correct), "attempted": len(steps),
              "failed": int(failed), "metrics": metrics, "device": dev}
    if traced:
        from bench import breakdown
        dev["busy_s"], dev["window_s"] = breakdown.busy_window(events)
        result["breakdown"] = breakdown.summary(events)
    result["check"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    print(f"compile_s {compile_s} setup_s {setup_s} window_s {window_s} "
          f"steps {len(steps)} reference_s {ref_s} memory_peak_bytes {peak}",
          file=sys.stderr)
    for k, v, lim in rows:
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr)
    print(json.dumps(result), file=out, flush=True)
    return result, prog, ref, ctx


def main(argv=None) -> int:
    args = parse(argv)
    try:
        run_cell(args)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return NO_CHIP
    return 0


if __name__ == "__main__":
    sys.exit(main())
