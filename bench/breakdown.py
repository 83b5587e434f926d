"""The traced window in a few numbers: device busy and window seconds,
the device operations that took most time and the longest idle gaps."""
from __future__ import annotations

from bench import trace

TOP = 10


def busy_window(events: list[dict]) -> tuple[float, float]:
    """(busy seconds averaged over the chips used, window seconds)."""
    lo, hi = trace.window(events)
    planes = trace.device_planes(events)
    busy = sum(trace.busy(events, p, lo, hi) for p in planes) \
        / max(len(planes), 1)
    return busy / 1e9, (hi - lo) / 1e9


def summary(events: list[dict]) -> dict:
    lo, hi = trace.window(events)
    planes = trace.device_planes(events)
    if not planes:
        return {"device_ops": [], "idle_gaps": []}
    plane = planes[0]
    return {
        "device_ops": [[n, t / 1e9]
                       for n, t in trace.op_totals(events, plane)[:TOP]],
        "idle_gaps": [[n, t / 1e9]
                      for n, t in trace.idle_gaps(events, plane, lo,
                                                  hi)[:TOP]],
    }
