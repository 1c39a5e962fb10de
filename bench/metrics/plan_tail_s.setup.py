"""Host time of the plan build's COO tail (the program's `plan.tail` span,
inside the set-up `Solver.plan`)."""


def read(run):
    return run.setup_info.get("plan_tail_s")
