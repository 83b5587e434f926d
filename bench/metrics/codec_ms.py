"""Exchange: device milliseconds per traced step of the wire codec and
the collective, the `csgd_codec` scope (pack, gather, unpack, the
decoded mean, the dense leaves' pmean): self time of the step
program's ops on the first device plane (bench/scopes.py)."""
from bench import scopes


def read(run):
    return scopes.read(run, "csgd_codec")
