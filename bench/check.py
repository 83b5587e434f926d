"""The numbers that decide ``correct``: the program's first steps against
the reference's, each against its limit.

* ``loss_gap``: the worst step's |loss - loss_ref| / loss_ref;
* ``loss0_gap``: the same for step 0 alone, before any Armijo decision;
* ``alpha_gap``: the worst step's relative gap of the accepted Armijo
  step (worker mean), which also moves with the number of evaluations;
* ``grad_gap``: the worst leaf's gap between the norms of the first
  gradient as the optimizer received it (sum over workers of eta_w g_w,
  read from the state after step 1), over the reference's norm of that
  leaf or of the median leaf, whichever is larger;
* ``grad0_gap``: the same with each side's gradient divided by its own
  first accepted step (worker mean), so an Armijo decision that rounding
  tips the other way does not move it; exact for one worker;
* ``change_gap``: the same for the parameters' change over the checked
  steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move by round-off alone);
* ``wire_bytes_gap``: the largest difference of per-worker wire bytes
  from the reference's count from shapes; exact, limit 0.
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("loss_gap", "loss0_gap", "alpha_gap", "grad_gap", "grad0_gap",
           "change_gap", "wire_bytes_gap")
NEGLIGIBLE = 1e-3


def _rel(a, b) -> float:
    a, b = np.asarray(a, float), np.asarray(b, float)
    return float(np.max(np.abs(a - b) / np.abs(b)))


def _leaf_gap(prog, ref, keep=None) -> float:
    prog, ref = np.asarray(prog, float), np.asarray(ref, float)
    scale = np.maximum(ref, np.median(ref))
    gap = np.abs(prog - ref) / scale
    if keep is not None:
        gap = gap[keep]
    return float(np.max(gap))


def numbers(prog: dict, ref: dict) -> dict[str, float]:
    g_ref = np.asarray(ref["grad_norms"], float)
    moving = g_ref >= NEGLIGIBLE * np.median(g_ref)
    return {
        "loss_gap": _rel(prog["loss"], ref["loss"]),
        "loss0_gap": _rel(prog["loss"][0], ref["loss"][0]),
        "alpha_gap": _rel(prog["alpha"], ref["alpha"]),
        "grad_gap": _leaf_gap(prog["grad_norms"], g_ref),
        "grad0_gap": _leaf_gap(
            np.asarray(prog["grad_norms"]) / prog["alpha"][0],
            g_ref / ref["alpha"][0]),
        "change_gap": _leaf_gap(prog["change_norms"], ref["change_norms"],
                                moving),
        "wire_bytes_gap": float(np.max(np.abs(
            np.asarray(prog["wire_bytes"]) - np.asarray(ref["wire_bytes"])))),
    }


def verdict(nums: dict[str, float], limits: dict | None):
    """(correct, [(name, value, limit)]) over the numbers the cell's
    limits file compares; a number it leaves out had no reading that
    could fail it (see the file).  No limits file: not correct."""
    if limits is None:
        return False, [(k, nums[k], None) for k in NUMBERS]
    rows = [(k, nums[k], limits["limits"][k]) for k in NUMBERS
            if k in limits["limits"]]
    ok = all(np.isfinite(v) and v <= lim for _, v, lim in rows)
    return bool(ok), rows
