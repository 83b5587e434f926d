"""Readings that the limits of a cell's correctness check are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --control 3 \
        --faults 3 --out <file.json>

One process on the cell's chips: the program's step is compiled once
and runs the checked first steps for each seed; then, with the step
released, the float32 reference runs them, and the numbers of
``bench/check.py`` are read.  On the first ``--control`` seeds it also
reads the control (the reference one precision below the
configuration's, in the program's place), and on the first ``--faults``
seeds the program with a fault of ``bench/faults.py`` planted under the
timed path: half of each worker's rows left out.  A step that returns
its state unchanged reads 1 on the gradient and the change by
construction and is not run.

With ``--write-limits`` it sets the cell's limits from these readings
and writes ``bench/limits/<cell>.json`` (see ``limits_from``).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


# readings that count as a number's upper one: the control's at 3x the
# sound runs' largest or more, a fault's at 10x (a state left unchanged,
# which reads 1 on the gradient and the change: 3x)
CONTROL_X, FAULT_X, UNCHANGED_X = 3.0, 10.0, 3.0
UNCHANGED_READS = ("grad_gap", "grad0_gap", "change_gap")
# numbers that a tipped Armijo decision of the checked steps moves by
# whole factors of its backtracking rate: their counterparts with each
# side's own steps divided out (loss0_gap, grad0_gap) are compared
SWINGS = {"alpha_gap": "the accepted step; one backtrack more or fewer "
                       "in steps 0-2 reads 0.25",
          "grad_gap": "scaled by 0.8 where step 0's decision tips; "
                      "grad0_gap divides each side's own step out"}


def _sig2(x: float) -> float:
    return float(f"{x:.2g}")


def limits_from(out: dict) -> dict:
    """Each number's limit between its lower reading (the largest over
    the sound runs) and its upper one (the smallest counted reading of
    the control or a fault): lower^0.3 * upper^0.7, nearer the upper,
    since fresh seeds read higher than a dozen did.  Exact numbers get
    0; a number with no upper reading, or one in ``SWINGS``, is not
    compared and the file says why."""
    from bench import check

    lower = {k: max(r[k] for r in out["program"]) for k in check.NUMBERS}
    control = {k: min(r[k] for r in out["control"]) for k in check.NUMBERS}
    faults = {name: {k: min(r[k] for r in rows) for k in check.NUMBERS}
              for name, rows in out["faults"].items() if rows}
    limits, not_compared, upper = {}, {}, {}
    for k in check.NUMBERS:
        if k in SWINGS:
            not_compared[k] = SWINGS[k]
            continue
        if lower[k] == 0 and control[k] == 0 and all(
                f[k] == 0 for f in faults.values()):
            limits[k] = 0
            continue
        ups = [(control[k], "control")] \
            if control[k] >= CONTROL_X * lower[k] else []
        ups += [(f[k], name) for name, f in faults.items()
                if f[k] >= FAULT_X * lower[k]]
        if k in UNCHANGED_READS and 1.0 >= UNCHANGED_X * lower[k]:
            ups.append((1.0, "unchanged"))
        if not ups or lower[k] == 0:
            not_compared[k] = (f"no upper reading: sound runs up to "
                               f"{lower[k]:.3g}, control {control[k]:.3g}"
                               + "".join(f", {n} {f[k]:.3g}"
                                         for n, f in faults.items()))
            continue
        up, by = min(ups)
        upper[k] = {"value": up, "by": by}
        limits[k] = _sig2(lower[k] ** 0.3 * up ** 0.7)
    return {"limits": limits, "not_compared": not_compared,
            "readings": {"device": out["device"],
                         "seeds": len(out["program"]),
                         "lower": lower, "control": control,
                         "faults": faults, "upper": upper}}


def main(argv=None) -> int:
    import jax

    from bench import check, faults as fault, harness, manifest

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=4_000_000_001)
    ap.add_argument("--out", required=True)
    ap.add_argument("--write-limits", action="store_true")
    args = ap.parse_args(argv)

    man = manifest.load()
    entry = manifest.resolve(man, args.workload)
    config, traffic, arch = entry["config"], entry["traffic"], entry["arch"]
    devices = jax.devices()[:entry["cell"]["chips"]]
    harness.env_cache_dir(ROOT)
    n = traffic["check_steps"]
    tr = harness.Trainer(config, traffic, arch, devices=devices)
    faults = {"half_batch": fault.half_batch(tr.workers)}
    out = {"workload": args.workload, "device": devices[0].device_kind,
           "program": [], "control": [], "faults": {k: [] for k in faults}}
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    # the program's runs first, then, with its step released, the
    # reference's: one chip holds one of the two at a time
    progs, fault_runs = {}, {k: {} for k in faults}
    for i, seed in enumerate(seeds):
        params, state = tr.fresh_state(seed)
        if tr.step_fn is None:
            tr.compile(params, state, tr.put(tr.host_batch(seed, 0)))
        params, state, progs[seed] = harness.check_steps(tr, seed, params,
                                                         state, n)
        harness.free(params, state)
        if i < args.faults:
            for name, feed in faults.items():
                params, state = tr.fresh_state(seed)
                params, state, fault_runs[name][seed] = harness.check_steps(
                    tr, seed, params, state, n, feed=feed)
                harness.free(params, state)
    del tr
    jax.clear_caches()
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        ref = harness.reference_record(config, traffic, arch, seed, n,
                                       devices=devices)
        prog = progs[seed]
        row = {"seed": seed, **check.numbers(prog, ref),
               "loss": prog["loss"], "ref_loss": ref["loss"],
               "alpha": prog["alpha"], "ref_alpha": ref["alpha"],
               "n_evals": prog["n_evals"], "ref_n_evals": ref["n_evals"],
               "leaves": ref["leaves"],
               **{f"{side}_{k}": rec[k] for side, rec in (("prog", prog),
                                                         ("ref", ref))
                  for k in ("grad_norms", "change_norms")}}
        out["program"].append(row)
        for name, runs in fault_runs.items():
            if seed in runs:
                out["faults"][name].append(
                    {"seed": seed, **check.numbers(runs[seed], ref)})
        if i < args.control:
            ctl = harness.reference_record(
                config, traffic, arch, seed, n,
                compute=harness.control_compute(config["model"]),
                devices=devices)
            out["control"].append({"seed": seed, **check.numbers(ctl, ref)})
        print(json.dumps({"seed": seed, "s": time.perf_counter() - t0,
                          **{k: row[k] for k in check.NUMBERS},
                          **{k: row[k] for k in ("alpha", "ref_alpha",
                                                  "n_evals",
                                                  "ref_n_evals")}}),
              flush=True)
    for kind in ("program", "control"):
        rows = out[kind]
        if rows:
            print(kind, {k: max(r[k] for r in rows) if kind == "program"
                         else min(r[k] for r in rows)
                         for k in check.NUMBERS}, flush=True)
    for name, rows in out["faults"].items():
        if rows:
            print(name, {k: min(r[k] for r in rows) for k in check.NUMBERS})
    pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(args.out).write_text(json.dumps(out, indent=1))
    if args.write_limits:
        lim = limits_from(out)
        print("limits", lim["limits"], flush=True)
        (ROOT / "bench" / "limits" / f"{args.workload}.json").write_text(
            json.dumps(lim, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
