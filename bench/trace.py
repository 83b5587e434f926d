"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a flat
list of events ``{"plane", "line", "name", "start", "dur"}`` (nanoseconds),
keeping the device planes' op and module lines and the host spans the
harness opens (names starting ``bench.``).  An op's name is its HLO
instruction name (``ef_apply.1``, ``while.52``); ops nest (a ``while``
spans its body), so time per op is self time.  Everything else here works
on that list, so the tests run it on a small recorded trace.

* busy time: the union of a device's op intervals inside the window;
* idle gaps: the complement of that union, each labelled by the host span
  open at the gap's middle;
* kernel time: the summed durations of the ops a name pattern matches;
* exposed collective time: the part of a device's collective-op
  intervals during which no other innermost op runs on that device.
"""
from __future__ import annotations

import glob
import os
import re

HOST_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|collective-permute|reduce-scatter|all-to-all"
    r"|allgather|allreduce|send|recv", re.IGNORECASE)
# op lines of a device plane, and the line of whole programs (modules)
OP_LINES = ("XLA Ops",)
MODULE_LINE = "XLA Modules"


def load(logdir: str) -> list[dict]:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    events = []
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            device = plane.name.startswith("/device:")
            for line in plane.lines:
                keep_line = device and (line.name in OP_LINES
                                        or line.name == MODULE_LINE)
                for e in line.events:
                    if keep_line or (not device
                                     and e.name.startswith(HOST_PREFIX)):
                        events.append({"plane": plane.name,
                                       "line": line.name,
                                       "name": short_name(e.name),
                                       "start": float(e.start_ns),
                                       "dur": float(e.duration_ns)})
    return events


def short_name(name: str) -> str:
    """``%ef_apply.1 = (f32[...]) custom-call(...)`` -> ``ef_apply.1``."""
    return name.split(" = ", 1)[0].lstrip("%")


def device_planes(events: list[dict]) -> list[str]:
    return sorted({e["plane"] for e in events
                   if e["plane"].startswith("/device:")})


def ops(events: list[dict], plane: str) -> list[dict]:
    return [e for e in events if e["plane"] == plane
            and e["line"] in OP_LINES]


def modules(events: list[dict], plane: str) -> list[dict]:
    return [e for e in events if e["plane"] == plane
            and e["line"] == MODULE_LINE]


def host_spans(events: list[dict]) -> list[dict]:
    return [e for e in events if not e["plane"].startswith("/device:")]


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals clipped to [lo, hi]."""
    out = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def window(events: list[dict]) -> tuple[float, float]:
    """The traced window: from the first to the end of the last host step
    span (``bench.step``)."""
    steps = [e for e in host_spans(events) if e["name"] == "bench.step"]
    if not steps:
        raise ValueError("trace holds no bench.step span")
    return (min(e["start"] for e in steps),
            max(e["start"] + e["dur"] for e in steps))


def busy(events: list[dict], plane: str, lo: float, hi: float) -> float:
    return length(union(((e["start"], e["start"] + e["dur"])
                         for e in ops(events, plane)), lo, hi))


def idle_gaps(events: list[dict], plane: str, lo: float,
              hi: float) -> list[tuple[str, float]]:
    """Gaps between the device's busy intervals, longest first, each named
    by the innermost host span open at its middle."""
    merged = union(((e["start"], e["start"] + e["dur"])
                    for e in ops(events, plane)), lo, hi)
    edges = [lo] + [x for ab in merged for x in ab] + [hi]
    spans = host_spans(events)
    gaps = []
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        open_ = [s for s in spans if s["start"] <= mid < s["start"] + s["dur"]]
        name = min(open_, key=lambda s: s["dur"])["name"] if open_ \
            else "no host span"
        gaps.append((name, b - a))
    return sorted(gaps, key=lambda g: -g[1])


def _nesting(evs: list[dict]) -> tuple[list[float], list[bool]]:
    """Per op: its self time (duration less the ops directly nested in
    it) and whether another op nests in it."""
    order = sorted(range(len(evs)),
                   key=lambda i: (evs[i]["start"], -evs[i]["dur"]))
    own = [e["dur"] for e in evs]
    parent = [False] * len(evs)
    stack: list[int] = []
    def end(j):
        return evs[j]["start"] + evs[j]["dur"]

    for i in order:
        while stack and end(stack[-1]) <= evs[i]["start"]:
            stack.pop()
        # the innermost open op that holds this one whole
        holder = next((j for j in reversed(stack) if end(j) >= end(i)),
                      None)
        if holder is not None:
            own[holder] -= evs[i]["dur"]
            parent[holder] = True
        stack.append(i)
    return own, parent


def self_times(evs: list[dict]) -> list[tuple[str, float]]:
    own, _ = _nesting(evs)
    return [(e["name"], t) for e, t in zip(evs, own)]


def op_totals(events: list[dict], plane: str) -> list[tuple[str, float]]:
    """Self time summed per op name, largest first."""
    tot: dict[str, float] = {}
    for name, t in self_times(ops(events, plane)):
        tot[name] = tot.get(name, 0.0) + t
    return sorted(tot.items(), key=lambda kv: -kv[1])


def matching(events: list[dict], plane: str, pattern: str) -> list[dict]:
    rx = re.compile(pattern)
    return [e for e in ops(events, plane) if rx.search(e["name"])]


def exposed_collective(events: list[dict], plane: str, lo: float,
                       hi: float) -> float:
    """Nanoseconds of collective ops during which no other op runs."""
    dev = ops(events, plane)
    _, parent = _nesting(dev)
    dev = [e for e, p in zip(dev, parent) if not p]
    coll = union(((e["start"], e["start"] + e["dur"]) for e in dev
                  if COLLECTIVE.search(e["name"])), lo, hi)
    comp = union(((e["start"], e["start"] + e["dur"]) for e in dev
                  if not COLLECTIVE.search(e["name"])), lo, hi)
    exposed = 0.0
    j = 0
    for a, b in coll:
        covered = 0.0
        while j < len(comp) and comp[j][1] <= a:
            j += 1
        k = j
        while k < len(comp) and comp[k][0] < b:
            covered += min(b, comp[k][1]) - max(a, comp[k][0])
            k += 1
        exposed += (b - a) - covered
    return exposed
