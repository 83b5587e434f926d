"""The correctness check at CPU size: a sound run of the harness is
correct; the same run with the timed path broken underneath, and the
control (the reference one precision below the configuration's, e4m3
products for bfloat16, in the program's place), are not.

The harness's look for a chip is skipped; everything else of a run is
driven: set-up, the checked steps, a short window and the comparison,
against the cell's own limits.
"""
import json
import os
import pathlib

import pytest

from bench import check, faults, harness, run
from bench.tests import tiny

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "granite-moe-csgd-1chip"
SEED = 3_141_592_653


def drive(root, cell, **kw):
    args = run.parse(["--workload", cell, "--seed", str(SEED),
                      "--seconds", "1", "--trace", "0"])
    with open(os.devnull, "w") as sink:
        result, _, _, _ = run.run_cell(args, require_chip=False, root=root,
                                       compile_cache=False, out=sink, **kw)
    return result


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"), CELL)


def test_sound_run_is_correct(root):
    r = drive(root, CELL)
    assert r["correct"], r["check"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    limits = json.loads((ROOT / "bench" / "limits" / f"{CELL}.json")
                        .read_text())["limits"]
    assert set(r["check"]) == set(limits)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_fault_is_not_correct(root, fault):
    kw = ({"step_wrapper": faults.unchanged} if fault == "unchanged"
          else {"feed": faults.half_batch(1)})
    r = drive(root, CELL, **kw)
    assert not r["correct"], r["check"]


def test_control_is_not_correct(root):
    from bench import manifest
    man = manifest.load(root)
    e = manifest.resolve(man, CELL, root)
    ref = harness.reference_record(e["config"], e["traffic"], e["arch"],
                                   SEED, 3)
    ctl = harness.reference_record(e["config"], e["traffic"], e["arch"],
                                   SEED, 3, compute=harness.control_compute(
                                       e["config"]["model"]))
    ok, rows = check.verdict(check.numbers(ctl, ref), e["limits"])
    assert not ok, rows
