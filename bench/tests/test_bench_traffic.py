"""The traffic loop and the cells' patterns, called directly on a stand-in
system."""
import math
from collections import deque

import pytest

import bench_testkit
from benchlib.spec import SpecError
from benchlib.traffic import drive as drive_in

BENCH = bench_testkit.BENCH


def drive(system, traffic, seconds, **kw):
    return drive_in(system, traffic, seconds, BENCH, **kw)


class Clock:
    """A clock that advances only when the system works or the driver
    sleeps, so the counts below are exact."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class Stub:
    def __init__(self, clock, max_batch, step_s, drop_half=False):
        self.clock, self.max_batch, self.step_s = clock, max_batch, step_s
        self.drop_half = drop_half
        self.q = deque()
        self.batches = []

    def submit(self, i):
        self.q.append(i)

    def pending(self):
        return len(self.q)

    def step(self):
        batch = [self.q.popleft() for _ in range(min(self.max_batch, len(self.q)))]
        self.batches.append(len(batch))
        self.clock.t += self.step_s
        if self.drop_half:
            batch = batch[: len(batch) // 2]
        return [(i, i, {}) for i in batch]


@pytest.fixture(autouse=True)
def no_sleep(monkeypatch):
    import benchlib.traffic as traffic

    monkeypatch.setattr(traffic.time, "sleep", lambda s: None)


def test_closed_loop_counts_every_solve():
    clock = Clock()
    stub = Stub(clock, max_batch=1, step_s=0.25)
    w = drive(stub, {"pattern": "closed"}, 2.5, clock=clock)
    # solves start at 0, 0.25, ..., 2.25: ten of them, the last done at 2.5
    assert len(w.requests) == 10
    assert all(r.done is not None for r in w.requests)
    assert w.last_done == 2.5
    assert [r.latency for r in w.requests] == [0.25] * 10


def test_the_solve_that_straddles_the_close_is_waited_for():
    clock = Clock()
    stub = Stub(clock, max_batch=1, step_s=0.3)
    w = drive(stub, {"pattern": "closed"}, 1.0, clock=clock)
    # solves start at 0, 0.3, 0.6 and 0.9; the last is answered at 1.2,
    # after the close, and no solve starts after it
    assert [r.due for r in w.requests] == pytest.approx([0.0, 0.3, 0.6, 0.9])
    assert w.last_done == pytest.approx(1.2)
    assert all(r.done is not None for r in w.requests)


def test_an_unanswered_request_stays_in_the_window_as_missing():
    clock = Clock()
    stub = Stub(clock, max_batch=1, step_s=0.25, drop_half=True)
    w = drive(stub, {"pattern": "closed"}, 1.0, clock=clock)
    assert len(w.requests) == 1 and math.isinf(w.requests[0].latency)


def test_unknown_pattern_is_refused():
    with pytest.raises(SpecError):
        drive(Stub(Clock(), 1, 0.1), {"pattern": "sets"}, 1.0)


class FakeProfiler:
    """The `trace.Profiler` state machine without a profiler."""

    def __init__(self, clock):
        self.clock, self.tracing, self._open, self.summary = clock, False, False, None
        self.events = []

    @property
    def active(self):
        return self._open

    def start(self):
        self.tracing = True
        self.events.append(("start", self.clock.t))

    def open_window(self):
        self._open = True
        self.events.append(("open", self.clock.t))

    def stop(self):
        self.tracing = self._open = False
        self.summary = "reduced"
        self.events.append(("stop", self.clock.t))


def test_trace_window_opens_a_step_after_the_profiler_starts():
    clock = Clock()
    stub = Stub(clock, max_batch=1, step_s=0.25)
    prof = FakeProfiler(clock)
    w = drive(stub, {"pattern": "closed"}, 2.5, clock=clock,
              profiler=prof, trace_from=0.5, trace_for=0.5)
    assert prof.events == [("start", 0.5), ("open", 0.75), ("stop", 1.25)]
    # the solves stepped at 0.75 and 1.0 ran inside the window
    assert [r.index for r in w.requests if r.traced] == [3, 4]
