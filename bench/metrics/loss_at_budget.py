"""Train step: mean training loss over the 8 steps that end at the
traffic's ``loss_steps``, counted from the seeded initialisation."""

SPAN = 8


def read(run):
    n = run["traffic"]["loss_steps"]
    losses = run["losses"][n - SPAN:n]
    if len(losses) < SPAN:
        return None
    return sum(losses) / SPAN
