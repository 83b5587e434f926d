"""Smoke run of the compressed-SGD trainer on a TPU chip.

    python chip_smoke.py                # one chip: phases A and B
    python chip_smoke.py --four-chips   # four chips: compressed vs dense

Drives ``repro.launch.train.main`` in this one process (a chip belongs to
one process, so no phase starts a child) on ``paper-lm-100m`` at its full
width with random weights from the trainer's seed:

* phase A — CSGD-ASSS with block top-k error feedback and the bucketed
  packed exchange, every dispatched op on its compiled TPU kernel;
* phase B — the same job with ``--no-kernel`` and every op forced to the
  jnp reference (``dispatch.using("ref")``): the reference phase A is
  held to.

It fails (non-zero exit) when JAX finds no TPU, when an op of phase A
resolved to ``ref`` or ``pallas-interpret``, when a loss is not finite,
or when A and B disagree: the first-step loss (same weights, same batch)
beyond :data:`FIRST_LOSS_RTOL` relative, or any step's wire bytes.

``--four-chips`` runs the paper's exchange, which exists only across
workers: the phase-A job on a ``4x1`` mesh and the same job with
``--opt dense`` (uncompressed pmean), and nothing else.  It fails when a
loss is not finite or the compressed uplink is not smaller than the dense
one.

Times printed here are smoke timings (one short run), not benchmark
numbers.  The last line of standard output is one JSON object naming the
device; nothing follows it.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402

from repro.kernels import dispatch  # noqa: E402
from repro.launch import train  # noqa: E402

STEPS = 20
JOB = ["--arch", "paper-lm-100m", "--opt", "csgd_asss",
       "--compress-method", "block_topk", "--transport", "bucketed",
       "--seq-len", "1024", "--global-batch", "8", "--steps", str(STEPS),
       "--log-every", "1"]
# Phase A vs phase B: the first step runs the same weights on the same
# batch, so its loss differs only by kernel-vs-reference arithmetic.
# Later steps may take different Armijo backtracks and are not compared.
FIRST_LOSS_RTOL = 1e-4


class SmokeFailure(Exception):
    pass


def run_phase(name: str, argv: list[str], *, force_ref: bool = False) -> dict:
    print(f"== phase {name}: train {' '.join(argv)}"
          + (" [every op forced to ref]" if force_ref else ""), flush=True)
    with dispatch.using("ref" if force_ref else None), \
            dispatch.recording() as seen:
        res = train.main(argv)
    res["ops"] = seen
    for op, impls in sorted(seen.items()):
        print(f"  op {op:20s} -> {', '.join(sorted(impls))}")
    steady = res["step_s"][1:]
    print(f"  compile {res['compile_s']:.2f}s; smoke timing: median step "
          f"{statistics.median(steady):.4f}s over steps 1..{len(steady)}")
    for m in res["log"]:
        print(f"  step {m['step']:2d} loss={m['loss']!r} alpha={m['alpha']!r} "
              f"n_evals={m['n_evals']!r} wire_bytes={m['wire_bytes']!r}")
    bad = [m["step"] for m in res["log"] if not math.isfinite(m["loss"])]
    if bad:
        raise SmokeFailure(f"phase {name}: non-finite loss at steps {bad}")
    if len(res["log"]) != STEPS:
        raise SmokeFailure(f"phase {name}: logged {len(res['log'])} of "
                           f"{STEPS} steps")
    return res


def one_chip() -> None:
    a = run_phase("A (kernels)", JOB + ["--mesh", "1x1"])
    off = {op: sorted(impls) for op, impls in a["ops"].items()
           if impls - {"pallas-tpu"}}
    if off:
        raise SmokeFailure(f"phase A: ops off the TPU kernels: {off}")
    b = run_phase("B (reference)", JOB + ["--mesh", "1x1", "--no-kernel"],
                  force_ref=True)
    la, lb = a["log"][0]["loss"], b["log"][0]["loss"]
    rel = abs(la - lb) / abs(lb)
    print(f"first-step loss A={la!r} B={lb!r} rel diff={rel!r} "
          f"(tolerance {FIRST_LOSS_RTOL})")
    if not rel <= FIRST_LOSS_RTOL:
        raise SmokeFailure(f"first-step loss A vs B: rel diff {rel} > "
                           f"{FIRST_LOSS_RTOL}")
    wa = [m["wire_bytes"] for m in a["log"]]
    wb = [m["wire_bytes"] for m in b["log"]]
    if wa != wb:
        raise SmokeFailure(f"wire bytes differ: A={wa} B={wb}")
    print(f"wire bytes per step equal in A and B: {wa[0]!r}")


def four_chips() -> None:
    if len(jax.devices()) < 4:
        raise SmokeFailure(f"--four-chips needs 4 devices, have "
                           f"{len(jax.devices())}")
    comp = run_phase("compressed 4x1", JOB + ["--mesh", "4x1"])
    dense = run_phase("dense 4x1", JOB + ["--mesh", "4x1", "--opt", "dense"])
    wc = comp["log"][-1]["wire_bytes"]
    wd = dense["log"][-1]["wire_bytes"]
    print(f"uplink wire bytes per worker per step: compressed={wc!r} "
          f"dense={wd!r} ratio={wc / wd!r}")
    if not wc < wd:
        raise SmokeFailure(f"compressed uplink {wc} not below dense {wd}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="compressed vs dense on a 4x1 mesh, nothing else")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}", flush=True)
    try:
        four_chips() if args.four_chips else one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
