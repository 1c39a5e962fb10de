"""Device time by the program's own names: a second reduction of a profiler
trace, beside `benchlib.trace`, which it leaves as it is.

  self time      an op's time less the ops nested inside it on the same
                 line: a `while` holds its body's ops, so summed self times
                 equal busy time instead of counting the loop twice
  scope time     self time summed by the program's scope map (`{op name:
                 "mis.*" scope}`, from `Solver.program_scopes`), the rest
                 under `unscoped`
  program spans  host spans the program opens (`solver.*`, `plan.*`,
                 `service.*`) beside the benchmark's `bench.*`; an idle gap
                 is named by the innermost span of either kind

All of it reads plain (name, start_ns, duration_ns) events, as
`benchlib.trace.reduce_events` does, inside the `bench.window` span.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

from benchlib import trace

PROGRAM_PREFIXES = ("solver.", "plan.", "service.")
SPAN_PREFIXES = (trace.SPAN_PREFIX,) + PROGRAM_PREFIXES
UNSCOPED = "unscoped"

Event = Tuple[str, float, float]   # (name, start_ns, duration_ns)


@dataclasses.dataclass
class ScopeSummary:
    scope_s: Dict[str, float]             # seconds per scope, averaged over devices
    idle_gaps: List[Tuple[str, float]]    # (innermost span, seconds), longest first

    def seconds(self, prefix: str) -> float:
        """Seconds under every scope that starts with `prefix`."""
        return sum(t for s, t in self.scope_s.items() if s.startswith(prefix))

    def path_seconds(self, path: str) -> float:
        """Seconds under every scope whose path sub-scope is `path`."""
        return sum(t for s, t in self.scope_s.items()
                   if s.endswith("/" + path))


def self_times(events: Sequence[Event], lo: float, hi: float) -> List[Tuple[str, float]]:
    """(op name, self ns inside [lo, hi]) of one line's events: each op's
    clipped time less that of the ops directly nested in it."""
    clipped = lambda s, e: max(0.0, min(e, hi) - max(s, lo))
    order = sorted(((s, s + d, n) for n, s, d in events),
                   key=lambda x: (x[0], -x[1]))
    out: List[List] = []
    stack: List[int] = []                 # indices into `out`, open ops
    ends: List[float] = []
    for s, e, n in order:
        while stack and ends[-1] <= s:
            stack.pop()
            ends.pop()
        own = clipped(s, e)
        if stack and e <= ends[-1]:
            out[stack[-1]][1] -= own
        out.append([n, own])
        stack.append(len(out) - 1)
        ends.append(e)
    return [(n, t) for n, t in out]


def reduce_scopes(device_ops: Mapping[int, Sequence[Event]],
                  host_spans: Sequence[Event],
                  scopes: Optional[Mapping[str, str]] = None) -> ScopeSummary:
    """Scope times and idle gaps named by program or benchmark spans, in the
    traced window.  With no scope map every op is `unscoped`."""
    windows = [(s, s + d) for n, s, d in host_spans if n == trace.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {trace.WINDOW_SPAN!r} span")
    lo, hi = windows[0]
    scopes = scopes or {}
    per_scope: Dict[str, float] = {}
    all_busy = []
    for events in device_ops.values():
        for name, ns in self_times(events, lo, hi):
            key = scopes.get(name, UNSCOPED)
            per_scope[key] = per_scope.get(key, 0.0) + ns
        all_busy.extend(trace.merge(trace.clip(
            ((s, s + d) for _, s, d in events), lo, hi)))
    n_dev = max(len(device_ops), 1)
    spans = [(n, s, s + d) for n, s, d in host_spans
             if n.startswith(SPAN_PREFIXES) and n != trace.WINDOW_SPAN]
    idle = trace.gaps(trace.merge(all_busy), lo, hi)
    idle.sort(key=lambda g: g[0] - g[1])
    return ScopeSummary(
        scope_s={k: v / n_dev / 1e9 for k, v in
                 sorted(per_scope.items(), key=lambda kv: -kv[1])},
        idle_gaps=[(trace.innermost(spans, (s + e) / 2), (e - s) / 1e9)
                   for s, e in idle[:trace.TOP]],
    )


def read_xplane(path: str):
    """(device ops by plane index, host spans of every prefix we read) from
    one `.xplane.pb`; `benchlib.trace.read_xplane` keeps `bench.*` alone."""
    from jax.profiler import ProfileData

    device_ops, _ = trace.read_xplane(path)
    host_spans: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans.extend(
                    (e.name, e.start_ns, e.duration_ns) for e in line.events
                    if e.name.startswith(SPAN_PREFIXES))
    return device_ops, host_spans
