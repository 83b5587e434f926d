"""Kernels: the fused EF top-k passes' share of their HBM roofline.

The least time of each pass is the bytes it needs (bench/flops.py,
counted from the compressed leaves' shapes) over the HBM peak; the share
is the summed least time of the traced calls over their summed device
time.  Both passes are memory-bound (a few flops per byte)."""
from bench import flops, trace

# the kernels' op names in the device trace (``ef_stats_telemetry.1``)
PASSES = {"stats": r"^ef_stats_telemetry(\.\d+)?$",
          "update": r"^ef_apply(\.\d+)?$"}


def read(run):
    ev = run["events"]
    if not ev:
        return None
    opt = run["opt"]
    need = flops.ef_pass_bytes(flops.ef_rows(run["shapes"], opt), opt.block)
    planes = trace.device_planes(ev)
    if not planes:
        return None
    plane = planes[0]
    least = spent = 0.0
    for name, pattern in PASSES.items():
        calls = trace.matching(ev, plane, pattern)
        least += len(calls) * need[name] / run["peak"]["hbm_bytes_per_s"]
        spent += sum(e["dur"] for e in calls) / 1e9
    if spent == 0.0:
        return None
    return 100.0 * least / spent
