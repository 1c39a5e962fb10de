"""Set-up time: process start to the first measured request (graph
generation, plan build, compile-cache load and warm-up)."""


def read(run):
    return run.setup_s
