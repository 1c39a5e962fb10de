"""Host time of packing a restart's batch: the mean of the program's
`solver.pack` span over the window's restarts, in ms (the cached structure
and the call's keys or priorities)."""
from benchlib.stats import mean


def read(run):
    return mean([r.stats["pack_ms"] for r in run.window.requests
                 if r.done is not None and "pack_ms" in r.stats])
