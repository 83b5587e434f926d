"""Kernels: the flash-attention forward kernel's share of its compute
roofline: the causal forward's FLOPs from shapes (bench/flops.py) over
the bf16 peak, per call, over the calls' summed device time."""
from bench import flops, trace

PATTERN = r"^flash_attention(\.\d+)?$"


def read(run):
    ev = run["events"]
    if not ev:
        return None
    planes = trace.device_planes(ev)
    if not planes:
        return None
    plane = planes[0]
    calls = trace.matching(ev, plane, PATTERN)
    spent = sum(e["dur"] for e in calls) / 1e9
    if not calls or spent == 0.0:
        return None
    t = run["traffic"]
    rows = t["global_batch"] // t["mesh"][0]
    f = flops.flash_forward_flops(run["model"], rows, t["seq_len"] - 1)
    return 100.0 * len(calls) * f / run["peak"]["bf16_flops"] / spent
