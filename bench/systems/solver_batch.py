"""The `Solver` front door on a fixed set of graphs: one request is one
restart, a `Solver.solve_many(member_plans, keys=...)` over every member of
the set, whose answers reach the host.

The configuration's graph is the disjoint union of the set's members, laid
out member after member (`bench/graphs/er_set.py`).  Set-up cuts it into
the members by the generator's `member_sizes`, plans each (timed as
`plan_s`), and warms the packed program with two restarts under keys
outside the pool.  Member j of a restart under key k solves under
`fold_in(key(k), j)`.  A request's answer is the members' answers
concatenated, in the union's ids; its `rounds` is the members' largest,
and its stats hold the ms of the program's `solver.pack` span (`pack_ms`).

Set-up also keeps the packed batch's fill, by the program's own gauges
(`batch.edge_fill`, `batch.tile_fill` as `edge_fill`, `tile_fill`).

A program without a pack cache rebuilds the whole batch, priorities
included, on every call, and compiles the priorities of every new member
size on the way: minutes of set-up at this size.  On such a program the
system stops at once with an error.
"""
from __future__ import annotations

import pathlib
import time
from collections import deque
from typing import Dict, List, Tuple

import numpy as np

from benchlib.spec import load_module
from benchlib.trace import span
from benchlib.workload import Workload

base = load_module(pathlib.Path(__file__).with_name("solver.py"))

PACK_HITS = "solver.pack_cache.hits"
GAUGES = {"batch.edge_fill": "edge_fill", "batch.tile_fill": "tile_fill"}


class System:
    """Restarts of a set of graphs through `Solver(SolveOptions(**options))`."""

    def __init__(self, config: dict, workload: Workload):
        from repro.api import SolveOptions, Solver

        self.solver = Solver(SolveOptions(**config["system"].get("options", {})))
        if PACK_HITS not in self.solver.metrics.snapshot():
            raise RuntimeError(
                f"{config['name']} needs a Solver that caches packed batches "
                f"(no {PACK_HITS!r} counter): this program repacks the batch "
                "on every restart, which at this size takes minutes of set-up")
        self.workload = workload
        self.gen = workload.gen
        self._queue: deque = deque()
        self.plans: List = []
        self.setup_info: Dict[str, float] = {}
        self._member_keys = None

    def members(self):
        """The members' edge lists (n, u, v), in member ids."""
        n, u, v = self.workload.graph()
        params = self.workload.params
        sizes = self.gen.member_sizes(params, int(params["seed"]))
        offs = np.concatenate([[0], np.cumsum(sizes)])
        if offs[-1] != n:
            raise ValueError(f"member sizes sum to {offs[-1]}, the union has {n}")
        cut = np.searchsorted(u, offs)
        out = []
        for j, m in enumerate(sizes):
            uu = u[cut[j]:cut[j + 1]] - offs[j]
            vv = v[cut[j]:cut[j + 1]] - offs[j]
            if vv.size and vv.max() >= m:
                raise ValueError(f"member {j} has an edge leaving it")
            out.append((int(m), uu, vv))
        return out

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        graphs = [base.program_graph(g) for g in self.members()]
        t0 = time.perf_counter()
        with span("plan"):
            self.plans = [self.solver.plan(g) for g in graphs]
        self.setup_info["plan_s"] = time.perf_counter() - t0
        ids = jnp.arange(len(self.plans))
        self._member_keys = jax.jit(lambda k: jax.vmap(
            lambda j: jax.random.fold_in(jax.random.key(k), j))(ids))
        for k in self.workload.warm_keys():
            with span("warmup"):
                self.restart(k)
        gauges = self.solver.metrics.snapshot()
        for name, key in GAUGES.items():
            self.setup_info[key] = float(gauges[name])

    def restart(self, k: int):
        """One `solve_many` of every member under key `k`: (answer in the
        union's ids, the members' largest rounds, ms of `solver.pack`)."""
        from repro.obs import Trace

        trace = Trace()
        res = self.solver.solve_many(self.plans, keys=self._member_keys(k),
                                     trace=trace)
        answer = np.concatenate([r.in_mis for r in res])
        return answer, max(r.rounds for r in res), trace.total_ms("solver.pack")

    def submit(self, i: int) -> None:
        self._queue.append(i)

    def pending(self) -> int:
        return len(self._queue)

    def step(self) -> List[Tuple[int, object, Dict]]:
        i = self._queue.popleft()
        with span("solve"):
            answer, rounds, pack_ms = self.restart(self.workload.key(i))
        return [(i, answer, {"rounds": rounds, "pack_ms": pack_ms})]
