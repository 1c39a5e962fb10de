"""Closed loop of one caller: it sends its next request when its last one is
answered, while the window is open.  A request is due when the previous
answer came."""


def drive(loop, params, seconds):
    loop.send(due=0.0)
    while loop.live() and loop.pending():
        for r in loop.step():
            if r.done < seconds:
                loop.send(due=r.done)
