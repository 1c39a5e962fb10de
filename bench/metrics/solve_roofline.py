"""Share of the chip's roofline the solves reach: the least time the chip
could take for the rounds the traced solves ran (`benchlib.roofline`: a
floor on the bytes any layout moves, over the HBM peak) over the device's
busy time in the traced window.  Nothing to read without a traced solve."""
from benchlib.roofline import least_time_s


def read(run):
    traced = [r for r in run.window.requests if r.traced]
    if run.trace is None or run.peaks is None or not traced \
            or run.trace.busy_s <= 0:
        return None
    least = 0.0
    for r in traced:
        n, u, _ = run.workload.graph(r.index)
        least += least_time_s(r.stats["rounds"], n, 2 * u.shape[0], run.peaks)
    return 100.0 * least / run.trace.busy_s
