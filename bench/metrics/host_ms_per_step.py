"""Host loop: milliseconds per window step the harness's host timers
spend outside the step call (batch make, placement, divergence read)."""


def read(run):
    steps = run["steps"]
    return 1e3 * sum(s["host_s"] for s in steps) / len(steps)
