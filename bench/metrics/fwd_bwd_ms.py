"""Train step: device milliseconds per traced step of the forward and
backward, the `csgd_grad` scope (value_and_grad over the micro-batches,
the gradient norm): self time of the step program's ops on the first
device plane (bench/scopes.py)."""
from bench import scopes


def read(run):
    return scopes.read(run, "csgd_grad")
