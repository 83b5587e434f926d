"""Seconds from process start to the first window step: weights, the
step's compile (or compile-cache read) and the checked first steps."""


def read(run):
    return run["setup_s"]
