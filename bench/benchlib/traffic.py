"""Requests, the window, and the loop that a traffic pattern drives.

A mix (`bench/traffic/<mix>.json`) is data: its `pattern` names a driver,
`bench/traffic/<pattern>.py`, whose `drive(loop, params, seconds)` decides
when each request is due and sends it through a `Loop`; the rest of the mix
is that driver's parameters.  The loop submits each request, steps the
system, times each request from when it was due to its answer, and starts
and stops the profiler between calls into the system.  After the window
closes, requests already sent are still answered, for up to `GRACE_S`
seconds; one never answered stays in the window as missing.
"""
from __future__ import annotations

import dataclasses
import math
import pathlib
import time
from typing import Callable, Dict, List, Optional

from benchlib.spec import load_module

GRACE_S = 60.0


@dataclasses.dataclass
class Request:
    index: int
    due: float                       # seconds after the window opened
    done: Optional[float] = None
    answer: object = None
    stats: Dict = dataclasses.field(default_factory=dict)
    traced: bool = False             # its step ran inside the traced window

    @property
    def latency(self) -> float:
        return math.inf if self.done is None else self.done - self.due


@dataclasses.dataclass
class Window:
    requests: List[Request]
    opened: float                    # clock reading at the window's start
    seconds: float
    last_done: Optional[float]       # seconds after `opened`


class Loop:
    """What a pattern may do: read the window's clock, send a request, and
    step the system.  With a `profiler`, tracing starts at the first step
    after `trace_from` seconds, the traced window opens one step later, and
    it closes at the first step after `trace_for` seconds more."""

    def __init__(self, system, seconds: float,
                 clock: Callable[[], float] = time.perf_counter,
                 profiler=None, trace_from: float = 0.0, trace_for: float = 0.0):
        self.system, self.seconds, self.clock = system, seconds, clock
        self.profiler, self.trace_from, self.trace_for = profiler, trace_from, trace_for
        self._waiting: Dict[int, Request] = {}
        self._done: List[Request] = []
        self._trace_start, self._steps_traced = math.inf, 0
        self.t0 = clock()

    def now(self) -> float:
        return self.clock() - self.t0

    def live(self) -> bool:
        """Whether the window, with its grace for late answers, is open."""
        return self.now() <= self.seconds + GRACE_S

    def pending(self) -> int:
        return self.system.pending()

    def send(self, due: float) -> Request:
        r = Request(index=len(self._waiting) + len(self._done), due=due)
        self._waiting[r.index] = r
        self.system.submit(r.index)
        return r

    def step(self) -> List[Request]:
        """One step of the system; the requests it answered."""
        self._tick()
        traced = self.profiler is not None and self.profiler.active
        answers = self.system.step()
        self._steps_traced += 1
        t = self.now()
        out = []
        for i, answer, stats in answers:
            r = self._waiting.pop(i)
            r.done, r.answer, r.stats, r.traced = t, answer, stats, traced
            out.append(r)
        self._done.extend(out)
        return out

    def _tick(self) -> None:
        p = self.profiler
        if p is None:
            return
        t = self.now()
        if not p.tracing and p.summary is None and t >= self.trace_from:
            p.start()
            self._steps_traced = 0
        elif p.tracing and not p.active and self._steps_traced:
            p.open_window()
            self._trace_start = self.now()
        elif p.active and t >= self._trace_start + self.trace_for:
            p.stop()

    def close(self) -> Window:
        if self.profiler is not None and self.profiler.tracing:
            self.profiler.stop()
        requests = sorted(self._done + list(self._waiting.values()),
                          key=lambda r: r.index)
        last = max((r.done for r in self._done), default=None)
        return Window(requests=requests, opened=self.t0, seconds=self.seconds,
                      last_done=last)


def drive(system, traffic: dict, seconds: float, bench: pathlib.Path,
          clock: Callable[[], float] = time.perf_counter, profiler=None,
          trace_from: float = 0.0, trace_for: float = 0.0) -> Window:
    """Run the window under the mix's pattern, found by name under
    `bench/traffic/`."""
    pattern = load_module(bench / "traffic" / f"{traffic['pattern']}.py")
    loop = Loop(system, seconds, clock, profiler, trace_from, trace_for)
    pattern.drive(loop, traffic, seconds)
    return loop.close()
