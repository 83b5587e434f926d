"""Train step: device milliseconds per traced step of the apply, the
`csgd_apply` scope (p - u, the breaker's all-finite check and its
selects, the health counters): self time of the step program's ops on
the first device plane (bench/scopes.py)."""
from bench import scopes


def read(run):
    return scopes.read(run, "csgd_apply")
