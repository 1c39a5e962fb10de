"""Mean rounds to convergence of the solves in the window
(`SolveResult.rounds`)."""
from benchlib.stats import mean


def read(run):
    return mean([r.stats["rounds"] for r in run.window.requests
                 if r.done is not None])
