"""Exchange: device milliseconds per traced step of the error-feedback
selection and memory update, the `csgd_ef` scope (both fused EF passes,
the block top-k, the residual write): self time of the step program's
ops on the first device plane (bench/scopes.py)."""
from bench import scopes


def read(run):
    return scopes.read(run, "csgd_ef")
