"""The trace reduction on hand-made events and on a small recorded trace."""
import json
import pathlib

import pytest

from bench import breakdown, trace
from bench.tests import tiny

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, name, start, dur, line="XLA Ops"):
    return {"plane": plane, "line": line if plane == DEV else "python",
            "name": name, "start": float(start), "dur": float(dur)}


@pytest.fixture
def events():
    # host: one step span [0, 100) with a batch span [0, 20)
    return [
        ev(HOST, "bench.step", 0, 100),
        ev(HOST, "bench.make_batch", 0, 20),
        ev(HOST, "bench.wait", 30, 70),
        ev(DEV, "fusion.1", 20, 30),            # [20, 50)
        ev(DEV, "all-gather.3", 40, 30),        # [40, 70): 20 exposed
        ev(DEV, "ef_apply_kernel", 70, 10),     # [70, 80)
        ev(DEV, "fusion.2", 85, 10),            # [85, 95)
    ]


def test_busy_and_idle(events):
    lo, hi = trace.window(events)
    assert (lo, hi) == (0.0, 100.0)
    assert trace.busy(events, DEV, lo, hi) == 70.0
    gaps = trace.idle_gaps(events, DEV, lo, hi)
    assert gaps[0] == ("bench.make_batch", 20.0)
    assert sorted(g[1] for g in gaps) == [5.0, 5.0, 20.0]


def test_kernel_time_and_exposed_collective(events):
    lo, hi = trace.window(events)
    assert sum(e["dur"] for e in trace.matching(events, DEV, "ef_apply")) \
        == 10.0
    assert trace.exposed_collective(events, DEV, lo, hi) == 20.0


def test_breakdown(events):
    busy, window = breakdown.busy_window(events)
    assert busy == pytest.approx(70e-9) and window == pytest.approx(100e-9)
    s = breakdown.summary(events)
    assert s["device_ops"][0] == ["fusion.1", 30e-9]
    assert s["idle_gaps"][0][0] == "bench.make_batch"


def test_nested_ops_self_time_and_collective():
    events = [
        ev(HOST, "bench.step", 0, 100),
        ev(DEV, "while.1", 0, 100),             # the loop spans its body
        ev(DEV, "fusion.1", 0, 40),
        ev(DEV, "all-gather.2", 40, 30),        # only the loop covers it
        ev(DEV, "fusion.3", 70, 30),
    ]
    tot = dict(trace.op_totals(events, DEV))
    assert tot["while.1"] == 0.0 and tot["fusion.1"] == 40.0
    assert trace.exposed_collective(events, DEV, 0, 100) == 30.0


def test_short_names():
    assert trace.short_name("%ef_apply.1 = (f32[8]) custom-call(%x)") == \
        "ef_apply.1"
    assert trace.short_name("jit_worker_fn(123)") == "jit_worker_fn(123)"


def test_union_clips_and_merges():
    assert trace.union([(5, 15), (0, 3), (2, 6), (20, 30)], 1, 25) == \
        [(1, 15), (20, 25)]


@pytest.fixture(scope="module")
def recorded():
    """The last ~115 ms of one traced lm100m-csgd-1chip step on one TPU v5
    lite: its device ops (the EF passes among them) and host spans."""
    path = pathlib.Path(__file__).parent / "data" / "trace_lm100m_1chip.json"
    return json.loads(path.read_text())


def test_recorded_trace(recorded):
    from bench import flops
    from bench.reference import csgd
    plane = trace.device_planes(recorded)[0]
    lo, hi = trace.window(recorded)
    window = hi - lo
    busy = trace.busy(recorded, plane, lo, hi)
    assert window == pytest.approx(115.272166e6)
    assert busy == pytest.approx(112.200292e6)
    assert trace.idle_gaps(recorded, plane, lo, hi)[0][0] == "bench.wait"
    stats = trace.matching(recorded, plane, r"^ef_stats_telemetry(\.\d+)?$")
    upd = trace.matching(recorded, plane, r"^ef_apply(\.\d+)?$")
    assert [e["dur"] for e in stats] == [3746874.0]
    assert [e["dur"] for e in upd] == [2676975.0]
    assert trace.exposed_collective(recorded, plane, lo, hi) == 0.0

    # the roofline reader on it, against the hand count: 107520 block rows
    # of f32 memory and gradient, two reads (+ 12 B a row) in pass 1, two
    # reads and two writes (+ 4 B a row) in pass 2, over 819 GB/s
    import importlib.util
    path = pathlib.Path(__file__).parents[1] / "metrics" / \
        "ef_topk_roofline.py"
    spec = importlib.util.spec_from_file_location("ef_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    m = tiny.lm100m()
    from bench import manifest
    from bench.weights import shapes
    lm = manifest.architecture("transformer_lm")
    opt = csgd.Optimizer(gamma=0.01, block=1024, value_bits=32)
    least = (2 * 107520 * 4096 + 107520 * 12
             + 4 * 107520 * 4096 + 107520 * 4) / 819e9
    share = mod.read({"events": recorded, "opt": opt,
                      "shapes": shapes(lm, m),
                      "peak": {"hbm_bytes_per_s": 819e9}})
    assert share == pytest.approx(100 * least / (6423849.0 / 1e9))
    assert 0 < share < 100
    assert flops.ef_rows(shapes(lm, m), opt) == 107520
