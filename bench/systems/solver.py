"""The `Solver` front door as the system under test: one request is one
`Solver.solve(plan, key=...)` whose result reaches the host.

Set-up plans the graph (timed as `plan_s`) and warms the solve program with
two keys outside the request pool.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Dict, List, Tuple

from benchlib.trace import span
from benchlib.workload import Workload, half_edges


def program_graph(g):
    """The program's input type for an edge list: both directions, sorted
    by sender."""
    import jax.numpy as jnp
    from repro.graphs.graph import Graph

    s, r = half_edges(g)
    return Graph(senders=jnp.asarray(s), receivers=jnp.asarray(r),
                 n_nodes=int(g[0]), n_edges=int(s.shape[0]))


class System:
    """One-graph solves through `Solver(SolveOptions(**options))`."""

    def __init__(self, config: dict, workload: Workload):
        from repro.api import SolveOptions, Solver

        self.solver = Solver(SolveOptions(**config["system"].get("options", {})))
        self.workload = workload
        self._queue: deque = deque()
        self.plan = None
        self.setup_info: Dict[str, float] = {}

    def setup(self) -> None:
        import jax

        graph = program_graph(self.workload.graph())
        t0 = time.perf_counter()
        with span("plan"):
            self.plan = self.solver.plan(graph)
        self.setup_info["plan_s"] = time.perf_counter() - t0
        for k in self.workload.warm_keys():
            with span("warmup"):
                self.solver.solve(self.plan, key=jax.random.key(k))

    def submit(self, i: int) -> None:
        self._queue.append(i)

    def pending(self) -> int:
        return len(self._queue)

    def step(self) -> List[Tuple[int, object, Dict]]:
        import jax

        i = self._queue.popleft()
        with span("solve"):
            res = self.solver.solve(self.plan, key=jax.random.key(self.workload.key(i)))
        return [(i, res.in_mis, {"rounds": res.rounds})]
