"""Device: share of the traced window in which no operation runs,
1 - (union of device-op intervals / window), mean over the chips used."""
from bench import trace


def read(run):
    ev = run["events"]
    if not ev:
        return None
    lo, hi = trace.window(ev)
    planes = trace.device_planes(ev)
    if not planes:
        return None
    busy = sum(trace.busy(ev, p, lo, hi) for p in planes) / len(planes)
    return 100.0 * (1.0 - busy / (hi - lo))
