"""Time per solve: the window from its start to the completion of the last
solve in it, over the solves completed."""
from benchlib.stats import per_item_ms


def read(run):
    answered = [r for r in run.window.requests if r.done is not None]
    if run.window.last_done is None:
        return None
    return per_item_ms(run.window.last_done, len(answered))
