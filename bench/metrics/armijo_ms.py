"""Train step: device milliseconds per traced step of the Armijo search,
the `csgd_armijo` scope (the trial at alpha_max and the backtracking
loop's trial forwards): self time of the step program's ops on the
first device plane (bench/scopes.py)."""
from bench import scopes


def read(run):
    return scopes.read(run, "csgd_armijo")
