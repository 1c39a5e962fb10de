#!/usr/bin/env python3
"""The control and the planted faults: runs that the comparison deciding
`correct` has to fail.

    python3 bench/control.py --workload road-solve --seconds 40 \\
        --mode control --seeds 101 102 103

Each seed is a whole run of the cell (its inputs, traffic and window), with
the system under test replaced or broken underneath:

  control  the plain reference (`references/<reference>.py`, Luby's
           algorithm) in the program's place, stopped one round before it
           converges: it breaks the maximality the configuration states
  uniform  the program with its uniform random priorities (`heuristic`
           "h1") in place of the configured ones: valid sets, smaller than
           the configuration's size floor
  flip     the program, with one vertex of each answer flipped where the
           answer is produced
  stale    the program, each answer after the first replaced by the one
           before it (a solve that returns its state unchanged)

It prints one JSON line per seed with the numbers compared; `correct` must
come out false.  Needs a TPU unless the mode is `control`.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
from collections import deque

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from benchlib import harness  # noqa: E402
from benchlib.spec import ROOT, load_cell, load_module  # noqa: E402

MODES = ("control", "uniform", "flip", "stale")


class ReferenceSystem:
    """The plain reference as the system: each step answers one request with
    `solve(..., rounds_short)` under the request's key."""

    def __init__(self, config, workload, bench, rounds_short=1):
        self.ref = load_module(bench / "references" / f"{config['reference']}.py")
        self.workload = workload
        self.rounds_short = rounds_short
        self._queue = deque()
        self.setup_info = {}

    def setup(self):
        pass

    def submit(self, i):
        self._queue.append(i)

    def pending(self):
        return len(self._queue)

    def step(self):
        i = self._queue.popleft()
        n, u, v = self.workload.graph(i)
        answer = self.ref.solve(n, u, v, seed=self.workload.key(i),
                                rounds_short=self.rounds_short)
        return [(i, answer, {"rounds": 0})]


class Faulty:
    """The cell's own system with one fault planted in what it answers."""

    def __init__(self, inner, fault):
        self.inner = inner
        self.fault = fault
        self.setup_info = inner.setup_info
        self._last = None

    def setup(self):
        self.inner.setup()

    def submit(self, i):
        self.inner.submit(i)

    def pending(self):
        return self.inner.pending()

    def step(self):
        import numpy as np

        out = []
        for i, answer, stats in self.inner.step():
            answer = np.array(answer, dtype=bool)
            if self.fault == "flip":
                answer[0] = not answer[0]
            elif self.fault == "stale":
                answer, self._last = (
                    self._last if self._last is not None else answer, answer)
            out.append((i, answer, stats))
        return out


def factory(cell, mode):
    """`make_system` for `harness.execute`."""
    if mode == "control":
        return lambda config, workload: ReferenceSystem(config, workload, cell.bench)
    entry = cell.config["system"]["entry"]
    own = load_module(cell.bench / "systems" / f"{entry}.py").System
    if mode == "uniform":
        return lambda config, workload: own(uniform(config), workload)
    return lambda config, workload: Faulty(own(config, workload), mode)


def uniform(config):
    """The configuration with the program's uniform random priorities."""
    system = dict(config["system"])
    system["options"] = dict(system.get("options", {}), heuristic="h1")
    return dict(config, system=system)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--mode", choices=MODES, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    cell = load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    harness.configure_compile_cache(ROOT)
    import jax

    if args.mode != "control" and jax.devices()[0].platform != "tpu":
        print("control: the program's modes need a TPU", file=sys.stderr)
        return 1
    for seed in args.seeds:
        run, system = harness.execute(cell, seed, args.seconds, False,
                                      time.perf_counter(),
                                      make_system=factory(cell, args.mode))
        del system
        correct, checks, failed = harness.judge(cell, run.workload, run.window)
        print(json.dumps({"workload": cell.name, "mode": args.mode,
                          "seed": seed, "correct": correct,
                          "attempted": len(run.window.requests),
                          "failed": failed, "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
