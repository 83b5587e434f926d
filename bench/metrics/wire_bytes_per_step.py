"""Exchange: the step's own ``wire_bytes`` (bytes one worker's packed
payload and dense leaves put on the wire), mean over the window."""


def read(run):
    steps = run["steps"]
    return sum(s["metrics"]["wire_bytes"] for s in steps) / len(steps)
