"""Device: the train step's model FLOPs (the architecture module's
``train_flops_per_token``) over its device time times the bf16 peak: the
whole step's share of the chip's peak.  The device time is the median
duration of the step program in the trace's module line (host gaps
between steps are left out; ``mfu`` keeps them)."""
import statistics

from bench import trace

PROGRAM = "jit_worker_fn"


def read(run):
    ev = run["events"]
    if not ev:
        return None
    planes = trace.device_planes(ev)
    durs = [e["dur"] for p in planes for e in trace.modules(ev, p)
            if e["name"].startswith(PROGRAM)]
    if not durs:
        return None
    flop = run["tokens_per_step"] * run["flops_per_token"] / run["chips"]
    return 100.0 * flop / (statistics.median(durs) / 1e9
                           * run["peak"]["bf16_flops"])
