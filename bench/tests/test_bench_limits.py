"""The rule that sets a cell's limits from its readings."""
import pytest

from bench import calibrate, check


def rows(**vals):
    base = {k: 0.0 for k in check.NUMBERS}
    return [dict(base, **vals)]


def readings(program, control, half):
    return {"device": "test", "program": rows(**program),
            "control": rows(**control), "faults": {"half_batch":
                                                   rows(**half)}}


def test_limit_lies_between_the_readings_nearer_the_upper():
    out = calibrate.limits_from(readings(
        {"loss0_gap": 1e-4, "change_gap": 0.01},
        {"loss0_gap": 1e-3, "change_gap": 0.02},
        {"loss0_gap": 5e-4, "change_gap": 0.5}))
    lim = out["limits"]
    # control at 10x counts; the fault at 5x does not
    assert lim["loss0_gap"] == pytest.approx(1e-4 ** 0.3 * 1e-3 ** 0.7,
                                             rel=0.05)
    assert out["readings"]["upper"]["loss0_gap"]["by"] == "control"
    # control at 2x does not count; the fault at 50x does
    assert out["readings"]["upper"]["change_gap"]["by"] == "half_batch"
    for k in ("loss0_gap", "change_gap"):
        lo = out["readings"]["lower"][k]
        up = out["readings"]["upper"][k]["value"]
        assert lo < lim[k] < up and lim[k] / lo > up / lim[k]


def test_unchanged_state_and_exact_and_missing_upper():
    out = calibrate.limits_from(readings(
        {"grad0_gap": 0.01, "loss_gap": 1e-3},
        {"grad0_gap": 0.02, "loss_gap": 2e-3},
        {"grad0_gap": 0.05, "loss_gap": 5e-3}))
    # only the state left unchanged (reads 1) counts for the gradient
    assert out["readings"]["upper"]["grad0_gap"]["by"] == "unchanged"
    assert out["limits"]["wire_bytes_gap"] == 0
    assert "loss_gap" not in out["limits"]
    assert "no upper reading" in out["not_compared"]["loss_gap"]
    assert set(out["not_compared"]) >= set(calibrate.SWINGS)
