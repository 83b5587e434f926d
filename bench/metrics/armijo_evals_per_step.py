"""Train step: mean of the step's own Armijo ``n_evals`` (loss
evaluations of the search, worker mean) over the window."""


def read(run):
    steps = run["steps"]
    return sum(s["metrics"]["n_evals"] for s in steps) / len(steps)
