"""Profiler capture and the reduction from trace to metrics.

The reduction reads the `.xplane.pb` the JAX profiler writes, with nothing
but `jax.profiler.ProfileData`:

  device planes   `/device:TPU:<i>`; their op line (`XLA Ops`) holds one
                  event per operation run on the chip
  host plane      `/host:CPU`; the benchmark's own spans (`bench.*`,
                  written by `jax.profiler.TraceAnnotation`) sit on the
                  Python thread's line, on the same clock

and gives, inside the traced window (the `bench.window` span):

  busy_s      the union of the intervals in which an op ran, averaged over
              the device planes
  window_s    the length of the window
  device_ops  device seconds per op name, most first
  idle_gaps   the longest gaps with no op running, each named by the
              innermost benchmark span open on the host at its middle
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE = "XLA Ops"
TOP = 10

Interval = Tuple[float, float]   # (start_ns, end_ns)


@dataclasses.dataclass
class TraceSummary:
    busy_s: float
    window_s: float
    n_devices: int
    device_ops: List[Tuple[str, float]]   # (op name, seconds), most first
    idle_gaps: List[Tuple[str, float]]    # (host span, seconds), longest first

    @property
    def idle_pct(self) -> Optional[float]:
        if self.window_s <= 0:
            return None
        return 100.0 * max(0.0, 1.0 - self.busy_s / self.window_s)


def op_name(event_name: str) -> str:
    """The op's own name: a TPU op event is named by its HLO instruction
    text (`%fusion.3 = f32[...] fusion(...)`), whose name is before ` = `."""
    head, eq, _ = event_name.partition(" = ")
    return head.lstrip("%") if eq else event_name


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] around merged, clipped `busy`."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans: Sequence[Tuple[str, float, float]], t: float) -> str:
    """Name of the latest-starting span open at time `t`."""
    best, best_start = "none", float("-inf")
    for name, s, e in spans:
        if s <= t < e and s > best_start:
            best, best_start = name, s
    return best


def reduce_events(
    device_ops: Dict[int, List[Tuple[str, float, float]]],
    host_spans: List[Tuple[str, float, float]],
) -> TraceSummary:
    """The reduction itself, on plain (name, start_ns, duration_ns) events:
    `device_ops` by device plane, `host_spans` from the host."""
    windows = [(s, s + d) for n, s, d in host_spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    lo, hi = windows[0]
    spans = [(n, s, s + d) for n, s, d in host_spans
             if n.startswith(SPAN_PREFIX) and n != WINDOW_SPAN]
    per_op: Dict[str, float] = {}
    busy_total = 0.0
    all_busy: List[Interval] = []
    for events in device_ops.values():
        inside = [(n, s, s + d) for n, s, d in events if s + d > lo and s < hi]
        for n, s, e in inside:
            per_op[n] = per_op.get(n, 0.0) + (min(e, hi) - max(s, lo))
        busy = merge(clip(((s, e) for _, s, e in inside), lo, hi))
        busy_total += sum(e - s for s, e in busy)
        all_busy.extend(busy)
    n_dev = max(len(device_ops), 1)
    idle = gaps(merge(all_busy), lo, hi)
    idle.sort(key=lambda g: g[0] - g[1])
    return TraceSummary(
        busy_s=busy_total / n_dev / 1e9,
        window_s=(hi - lo) / 1e9,
        n_devices=len(device_ops),
        device_ops=[(n, t / 1e9) for n, t in
                    sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]],
        idle_gaps=[(innermost(spans, (s + e) / 2), (e - s) / 1e9)
                   for s, e in idle[:TOP]],
    )


def read_xplane(path: str):
    """(device ops by plane index, host spans) from one `.xplane.pb`."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops: Dict[int, List[Tuple[str, float, float]]] = {}
    host_spans: List[Tuple[str, float, float]] = []
    for i, plane in enumerate(pd.planes):
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    device_ops[i] = [(op_name(e.name), e.start_ns, e.duration_ns)
                                     for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans.extend(
                    (e.name, e.start_ns, e.duration_ns) for e in line.events
                    if e.name.startswith(SPAN_PREFIX)
                )
    return device_ops, host_spans


def reduce_file(path: str) -> TraceSummary:
    device_ops, host_spans = read_xplane(path)
    if not device_ops:
        raise ValueError(f"{path}: no {_OPS_LINE!r} line on a TPU plane")
    return reduce_events(device_ops, host_spans)


class Profiler:
    """Profiles one stretch of a run into a temporary directory and reduces
    it.  `start()`, `open_window()` and `stop()` are called between calls
    into the system, so the traced window holds whole solves or steps.  The
    window opens only after the first call under the profiler, which the
    profiler's own start-up slows."""

    def __init__(self):
        self.dir: Optional[str] = None
        self._annotation = None
        self.tracing = False
        self.summary: Optional[TraceSummary] = None

    @property
    def active(self) -> bool:
        """Whether the traced window is open."""
        return self._annotation is not None

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.tracing = True

    def open_window(self) -> None:
        import jax

        self._annotation = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._annotation.__enter__()

    def stop(self) -> None:
        import jax

        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        jax.profiler.stop_trace()
        self.tracing = False
        try:
            found = glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not found:
                raise ValueError("the profiler wrote no .xplane.pb")
            self.summary = reduce_file(found[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def span(name: str):
    """A host span in the profiler's trace (a no-op when none is running)."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)

