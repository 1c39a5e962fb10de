"""Host time of the set-up plan build (`Solver.plan`), by the benchmark's
timer around the call."""


def read(run):
    return run.setup_info.get("plan_s")
