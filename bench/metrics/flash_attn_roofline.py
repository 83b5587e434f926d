"""Kernels: the flash-attention forward kernel's share of its compute
roofline: one call's causal forward FLOPs from shapes (the architecture
module's ``flash_forward_flops``, in the run's ``flash_forward_flops``)
over the bf16 peak, per call, over the calls' summed device time."""
from bench import trace

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
    return 100.0 * len(calls) * run["flash_forward_flops"] \
        / run["peak"]["bf16_flops"] / spent
