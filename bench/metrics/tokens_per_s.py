"""All tokens trained in the window over the window's wall time (host
clock, from the first step's batch to the last step's divergence read)."""


def read(run):
    return len(run["steps"]) * run["tokens_per_step"] / run["window_s"]
