"""One run of one cell: set-up, the measured window, the check, the result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run finds everything by the names in `BENCHMARK.json` (see
`benchlib.spec`).  It needs a TPU: it exits non-zero and prints no result
when JAX finds none, or fewer chips than the cell asks for.  The last line
of standard output is the result; the last lines of standard error are the
numbers compared, each beside its limit.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import pathlib
import sys
import time
from typing import Callable, Dict, Optional

from benchlib import trace as tracing
from benchlib.peaks import Peaks, peaks_for
from benchlib.spec import ROOT, Cell, SpecError, load_cell, load_module
from benchlib.traffic import Window, drive
from benchlib.workload import Workload

CACHE_DIR = ".jax_cache"        # under the checkout, unless the env names one


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cell: Cell
    workload: Workload
    window: Window
    setup_s: float
    setup_info: Dict[str, float]
    trace: Optional[tracing.TraceSummary]
    peaks: Optional[Peaks]
    jit_events: Dict[str, int]


class JitEvents:
    """Counts XLA compiles and persistent-cache loads, from JAX's own
    monitoring events."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if name == self.COMPILE:
            self.compiles += 1

    def _event(self, name, **kw):
        if name == self.CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self) -> Dict[str, int]:
        return {"compiles": self.compiles, "cache_loads": self.cache_hits}


def configure_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent cache: `JAX_COMPILATION_CACHE_DIR` when set, else a
    fixed directory in the checkout.  Every program is kept, however quick
    its compile, so a second run compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def judge(cell: Cell, workload: Workload, window: Window):
    """(correct, the numbers compared, the count of failed requests).

    Each answered request is checked against the plain reference on the
    benchmark's own copy of its graph; an unanswered one is `missing`, and
    one equal to an earlier answer of the window under another key is a
    `repeats`.  The configuration's `limits` give each number its limit:
    `at_most` for counts, summed over the window, and `at_least` for sizes,
    taken at the window's smallest.  A request fails if one of its own
    numbers breaks its limit."""
    import hashlib

    import numpy as np

    ref = load_module(cell.bench / "references" / f"{cell.config['reference']}.py")
    limits = cell.config["limits"]
    most = {k: lim["at_most"] for k, lim in limits.items() if "at_most" in lim}
    least = {k: lim["at_least"] for k, lim in limits.items() if "at_least" in lim}
    values = {k: 0 for k in most}
    values.update({k: None for k in least})
    seen: Dict[str, int] = {}
    failed = 0
    for r in window.requests:
        if r.done is None:
            found = {"missing": 1}
        else:
            n, u, v = workload.graph(r.index)
            found = ref.check(n, u, v, r.answer)
            key = workload.key(r.index)
            bits = np.packbits(np.atleast_1d(np.asarray(r.answer, dtype=bool)))
            if seen.setdefault(hashlib.sha256(bits).hexdigest(), key) != key:
                found["repeats"] = 1
        bad = False
        for k, x in found.items():
            if k in most:
                values[k] += x
                bad |= x > 0
            elif k in least:
                values[k] = x if values[k] is None else min(values[k], x)
                bad |= x < least[k]
        failed += bad
    checks = {k: {"value": values[k], "limit": most[k], "keep": "at_most"}
              for k in most}
    checks.update({k: {"value": values[k], "limit": least[k], "keep": "at_least"}
                   for k in least})
    correct = all(values[k] <= most[k] for k in most) and \
        all(values[k] is not None and values[k] >= least[k] for k in least)
    return correct, checks, failed


def memory_peak(devices) -> Optional[int]:
    peaks = [d.memory_stats().get("peak_bytes_in_use")
             for d in devices if d.memory_stats()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def execute(cell: Cell, seed: int, seconds: float, traced: bool,
            t_start: float, peaks: Optional[Peaks] = None,
            make_system: Optional[Callable] = None):
    """Set up and drive the window.  Returns the run and the system, which
    the caller frees before it judges the run.  `make_system` stands a
    fault or a control in for the cell's own system."""
    workload = Workload(cell, seed)
    if make_system is None:
        entry = cell.config["system"]["entry"]
        make_system = load_module(cell.bench / "systems" / f"{entry}.py").System
    jit = JitEvents()
    system = make_system(cell.config, workload)
    system.setup()
    profiler = tracing.Profiler() if traced else None
    jit_setup = jit.snapshot()
    setup_s = time.perf_counter() - t_start
    window = drive(system, cell.traffic, seconds, cell.bench, profiler=profiler,
                   trace_from=float(cell.traffic.get("trace_from_s", 0.0)),
                   trace_for=float(cell.traffic.get("trace_s", 0.0)))
    jit_window = jit.snapshot()
    run = Run(
        cell=cell, workload=workload, window=window, setup_s=setup_s,
        setup_info=dict(system.setup_info),
        trace=profiler.summary if profiler else None, peaks=peaks,
        jit_events={
            "setup_compiles": jit_setup["compiles"],
            "setup_cache_loads": jit_setup["cache_loads"],
            "window_compiles": jit_window["compiles"] - jit_setup["compiles"],
            "window_cache_loads":
                jit_window["cache_loads"] - jit_setup["cache_loads"],
        },
    )
    return run, system


def metrics_of(run: Run, kind: str) -> Dict[str, Dict]:
    out = {}
    for m in run.cell.metrics:
        if m.kind != kind:
            continue
        value = m.reader(run.cell.bench).read(run)
        if value is None or not math.isfinite(value):
            continue
        out[m.name] = {"value": value, "unit": m.unit}
    return out


def parse_args(argv):
    p = argparse.ArgumentParser(description="Run one benchmark cell once.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv, t_start: float, root: pathlib.Path = ROOT) -> int:
    args = parse_args(argv)
    try:
        cell = load_cell(args.workload, root)
    except (SpecError, KeyError, ValueError) as e:
        log(f"bench: {e}")
        return 2
    src = root / "src"
    if not (src / "repro").is_dir():
        log(f"bench: no program under {src}; run from a checkout of the repository")
        return 2
    sys.path.insert(0, str(src))
    cache = configure_compile_cache(root)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"bench: needs a TPU, JAX found {dev.platform}")
        return 1
    if len(devices) < cell.chips:
        log(f"bench: {cell.name} needs {cell.chips} chips, JAX sees {len(devices)}")
        return 1
    peaks = peaks_for(dev.device_kind)
    log(f"bench: {cell.name} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} device={dev.device_kind} x{len(devices)} "
        f"cache={cache}")

    run, system = execute(cell, args.seed, args.seconds, bool(args.trace),
                          t_start, peaks=peaks)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak(devices)}
    del system
    gc.collect()

    correct, checks, failed = judge(cell, run.workload, run.window)
    kind = "per_layer" if args.trace else "end_to_end"
    result = {
        "correct": correct,
        "attempted": len(run.window.requests),
        "failed": failed,
        "metrics": metrics_of(run, kind),
        "device": device,
    }
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {
            "device_ops": [list(x) for x in run.trace.device_ops],
            "idle_gaps": [list(x) for x in run.trace.idle_gaps],
        }
    result["checks"] = checks
    log(f"bench: setup {json.dumps(run.setup_info)} jit {json.dumps(run.jit_events)}")
    for name, c in checks.items():
        log(f"check {name} = {c['value']} ({c['keep'].replace('_', ' ')} {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0
