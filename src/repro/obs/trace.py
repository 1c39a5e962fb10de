"""Span tracing: nested wall-clock phases for one request, JSONL export;
and the names the solve program gives its own parts.

A `Trace` is a per-request recorder; `trace_span(trace, "plan")` is the one
instrumentation primitive, a context manager that times its body and
appends a `Span` with the current nesting depth.  Every span also enters a
`jax.profiler.TraceAnnotation` of its name, so it lands on the host plane
of any running profiler capture, on the device trace's clock; with no
capture running that costs one annotation object and no timer reads.
Passing ``trace=None`` (the default everywhere) records nothing.

Span taxonomy (DESIGN.md §14) — names are dotted, layer-first:

    service.step            one queue drain
      service.batch         one packed bucket (meta: bucket, batch_size)
    solver.solve            one front-door call
      solver.plan           plan-cache lookup / tiling build
        plan.key            content hash (recorded on a cache miss only)
        plan.tiles          cells and tile counts from the edges, packed
                            tiles (the full list, or the hybrid dense
                            sub-tiling)
        plan.tail           hybrid COO tail from the sub-threshold cells
      solver.pack           block-diagonal batch packing: the cached
                            structure and the call's priorities or keys
        pack.structure      the key-independent structure (pack-cache
                            miss only)
      solver.compile        cold-path lower().compile() (AOT; cache misses only)
      solver.execute        compiled-program dispatch + block_until_ready
      solver.fetch          device→host copy of the answer, un-permute
      solver.validate       response validity check
    solver.update           dyngraph repair route (meta: mode)

The conflated pre-PR `solve_ms` split: on a compile-stat miss ("compiled",
the existing `_note_signature` signal) the solver lowers and compiles
ahead-of-time under `solver.compile`, then executes the compiled program
under `solver.execute`; on a hit, only `solver.execute` appears.

Inside the compiled program the parts are `jax.named_scope`s, not spans
(`SCOPE_*`, `PATH_*` below): the optimized HLO carries them as op
metadata, and `hlo_scopes` maps each op the device runs to its scope.
"""
from __future__ import annotations

import json
import re
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

# Named scopes of the solve program (core.tc_mis, core.engine): the round
# body's three phases, the code outside the round loop, and the compaction
# ladder's masks and moves (core.compaction).
SCOPE_INIT = "mis.init"         # priorities, packed structures, state₀
SCOPE_P1 = "mis.p1"             # ① candidates (both H3 Max_Np passes)
SCOPE_P2 = "mis.p2"             # ② hits / counts (fused: the ②+③ kernel)
SCOPE_P3 = "mis.p3"             # ③ state update / merge
SCOPE_RESULT = "mis.result"     # epilogue: unpack, slice
SCOPE_COMPACT = "mis.compact"   # live-stream masks and their compaction
SCOPE_PRIORITIES = "mis.priorities"  # a packed batch's member priorities
# Path sub-scopes inside a phase: which substrate ran the op.
PATH_EDGE = "edge"              # per-edge gather/scatter (edge list, COO tail)
PATH_TILE = "tile"              # the tile schedule (jnp or Pallas)


@dataclass
class Span:
    name: str
    start_ms: float          # offset from trace start
    dur_ms: float
    depth: int
    meta: Dict[str, object] = field(default_factory=dict)

    def to_dict(self) -> Dict[str, object]:
        d = dict(
            name=self.name,
            start_ms=round(self.start_ms, 3),
            dur_ms=round(self.dur_ms, 3),
            depth=self.depth,
        )
        if self.meta:
            d["meta"] = self.meta
        return d


class Trace:
    """Per-request span recorder.  Not thread-safe by design — one Trace
    belongs to one request flowing through one service step."""

    def __init__(self, request_id: str = ""):
        self.request_id = request_id
        self.spans: List[Span] = []
        self._t0 = time.perf_counter()
        self._depth = 0

    # -- recording --------------------------------------------------------

    @contextmanager
    def span(self, name: str, **meta):
        start = time.perf_counter()
        self._depth += 1
        try:
            yield self
        finally:
            self._depth -= 1
            end = time.perf_counter()
            self.spans.append(Span(
                name=name,
                start_ms=(start - self._t0) * 1e3,
                dur_ms=(end - start) * 1e3,
                depth=self._depth,
                meta={k: v for k, v in meta.items() if v is not None},
            ))

    def note(self, name: str, dur_ms: float, *,
             end: Optional[float] = None, **meta) -> None:
        """Record an already-measured duration as a span (for timings that
        come from outside the context manager, e.g. a queue wait) ending
        at `end` (a `time.perf_counter()` reading; default now)."""
        end = time.perf_counter() if end is None else end
        self.spans.append(Span(
            name=name,
            start_ms=(end - self._t0) * 1e3 - dur_ms,
            dur_ms=float(dur_ms),
            depth=self._depth,
            meta={k: v for k, v in meta.items() if v is not None},
        ))

    # -- query ------------------------------------------------------------

    def total_ms(self, name: str) -> float:
        return sum(s.dur_ms for s in self.spans if s.name == name)

    # -- export -----------------------------------------------------------

    def to_dict(self) -> Dict[str, object]:
        # spans are appended at exit, i.e. children before parents; emit in
        # start order so the report tree reads top-down
        ordered = sorted(self.spans, key=lambda s: s.start_ms)
        return dict(
            request_id=self.request_id,
            spans=[s.to_dict() for s in ordered],
        )

    def to_jsonl_line(self) -> str:
        return json.dumps({"kind": "trace", **self.to_dict()}, sort_keys=True)


@contextmanager
def trace_span(trace: Optional[Trace], name: str, **meta):
    """`with trace_span(trace, "solver.plan"): ...` — the single seam
    primitive every layer uses.  Always a profiler annotation of `name`
    (a no-op unless a capture is running); recorded into `trace` when one
    is given."""
    with TraceAnnotation(name):
        if trace is None:
            yield None
            return
        with trace.span(name, **meta):
            yield trace


class JsonlWriter:
    """Append-only JSONL sink for trace / rounds / metrics records.

    Opens lazily on first write so constructing a service with a trace path
    configured but never exercised leaves no empty file behind."""

    def __init__(self, path: str):
        self.path = path
        self._fh = None

    def write_line(self, line: str) -> None:
        if self._fh is None:
            self._fh = open(self.path, "a")
        self._fh.write(line + "\n")
        self._fh.flush()

    def write_trace(self, trace: Trace) -> None:
        self.write_line(trace.to_jsonl_line())

    def write_rounds(self, rt) -> None:
        self.write_line(rt.to_jsonl_line())

    def write_metrics(self, snapshot: Dict[str, object]) -> None:
        self.write_line(json.dumps(
            {"kind": "metrics", "metrics": snapshot}, sort_keys=True))

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None


# -- op → scope map from the compiled program's HLO text ----------------------

_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%([^\s(]+) ")
_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([^\s=]+) = ")
_HLO_OPCODE = re.compile(r"(?<![\w.\-])([a-z][a-z0-9\-]*)\(")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLS = re.compile(r"\b(calls|to_apply)=%([^\s,})]+)")
_HLO_REF = re.compile(r"%([^\s,(){}]+)")
# instructions that move no data on the device
_HLO_FREE = ("parameter", "tuple", "get-tuple-element", "constant", "bitcast")


def scope_of(op_name: str) -> Optional[str]:
    """The innermost `mis.*` scope of an op_name, with its path sub-scope
    when one follows: `jit(f)/while/body/mis.p2/edge/gather` → `mis.p2/edge`."""
    parts = op_name.split("/")
    idx = [i for i, p in enumerate(parts) if p.startswith("mis.")]
    if not idx:
        return None
    i = idx[-1]
    if i + 1 < len(parts) and parts[i + 1] in (PATH_EDGE, PATH_TILE):
        return f"{parts[i]}/{parts[i + 1]}"
    return parts[i]


@dataclass
class _Instr:
    name: str
    opcode: str
    op_name: Optional[str]       # None: the instruction carries no op_name
    operands: List[str]


def _parse_hlo(hlo_text: str):
    """(computations as lists of `_Instr`, names of nested computations)."""
    comps: Dict[str, List[_Instr]] = {}
    nested = set()
    current: Optional[List[_Instr]] = None
    for line in hlo_text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m is not None and current is not None:
            rest = line[m.end():]
            op = _HLO_OPCODE.search(rest)
            opcode = op.group(1) if op else ""
            for kind, callee in _HLO_CALLS.findall(rest):
                if kind == "calls" and opcode == "fusion" or \
                        kind == "to_apply" and opcode != "call":
                    nested.add(callee)
            meta = _HLO_OP_NAME.search(rest)
            args = rest[op.end():] if op else ""
            current.append(_Instr(m.group(1), opcode,
                                  meta.group(1) if meta else None,
                                  _HLO_REF.findall(args)))
            continue
        m = _HLO_COMPUTATION.match(line)
        if m is not None and line.rstrip().endswith("{"):
            current = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            current = None
    return comps, nested


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """`{instruction name: innermost "mis.*" scope path}` for the top-level
    instructions of a compiled program's HLO text
    (`jit(f).lower(...).compile().as_text()`) — the ops a device trace
    names, each by the same instruction name.

    Top-level means every computation except fusion bodies and reducers
    (`calls=` of a fusion, `to_apply=` of anything but a `call`); a fusion
    carries the metadata of its root.  An instruction XLA made itself
    carries no op_name (an inserted copy, a broadcast of a constant, a
    rewritten reduction): it takes the scope of the first op that consumes
    it, else (none of its consumers scoped) of the first value it reads,
    looking through tuples and other instructions that move no data
    (`_HLO_FREE`, themselves left out of the map).  Instructions whose
    op_name holds no `mis.*` scope (the loop's own condition, code outside
    the solve) are left out."""
    comps, nested = _parse_hlo(hlo_text)
    out: Dict[str, str] = {}
    for comp, instrs in comps.items():
        if comp in nested:
            continue
        scope = {i.name: scope_of(i.op_name) for i in instrs if i.op_name}
        users: Dict[str, List[str]] = {}
        for i in instrs:
            for o in i.operands:
                users.setdefault(o, []).append(i.name)
        # free instructions (a tuple into a nested loop) pass scopes on
        orphans = [i for i in instrs if i.op_name is None]
        for near in (lambda i: users.get(i.name, []), lambda i: i.operands):
            changed = True
            while changed:
                changed = False
                for i in orphans:
                    if scope.get(i.name) is not None:
                        continue
                    found = next((scope[o] for o in near(i)
                                  if scope.get(o) is not None), None)
                    if found is not None:
                        scope[i.name] = found
                        changed = True
        out.update({i.name: scope[i.name] for i in instrs
                    if i.opcode not in _HLO_FREE
                    and scope.get(i.name) is not None})
    return out
