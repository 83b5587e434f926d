"""Device: milliseconds per traced step of the step program's ops that
no `csgd_*` scope claims (the gamma controller, the metric reductions,
state assembly): the guard that shows a scope a refactor dropped
(bench/scopes.py)."""
from bench import scopes


def read(run):
    return scopes.read(run, scopes.UNSCOPED)
