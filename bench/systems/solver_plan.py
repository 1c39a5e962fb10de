"""The `Solver` system of `solver.py`, with the plan build read by the
program's own names: set-up plans under a `repro.obs.Trace` and copies into
`setup_info` the seconds of its `plan.tail` span (`plan_tail_s`) and the
solver's `plan.*` gauges (`plan_dense_tiles`, `tail_entries`,
`tail_capacity`, `plan_device_bytes`).  `plan_s` is timed as `solver.py`
times it.  A program without such a span or gauge leaves that key out.

The configurations that use it need a plan build sized by edges
(`repro.core.tiling.TileCells`): a program that builds the full tile list
before it partitions it packs about one tile per half-edge of a Kronecker
graph, which at scale 17 outgrows the 40 GiB host of a one-chip machine.
On such a program the system stops at once with an error, before the
graph is made, instead of being killed for memory minutes later.
"""
from __future__ import annotations

import pathlib
import time

from benchlib.spec import load_module
from benchlib.trace import span

base = load_module(pathlib.Path(__file__).with_name("solver.py"))
program_graph = base.program_graph

GAUGES = {
    "plan.dense_tiles": "plan_dense_tiles",
    "plan.tail_entries": "tail_entries",
    "plan.tail_capacity": "tail_capacity",
    "plan.device_bytes": "plan_device_bytes",
}


class System(base.System):
    def __init__(self, config: dict, workload):
        from repro.core import tiling

        if not hasattr(tiling, "TileCells"):
            raise RuntimeError(
                f"{config['name']} needs a plan build sized by edges; this "
                "program builds the full tile list first, which does not "
                "fit the host's memory at this size")
        super().__init__(config, workload)

    def setup(self) -> None:
        import jax
        from repro.obs import Trace

        graph = program_graph(self.workload.graph())
        trace = Trace()
        t0 = time.perf_counter()
        with span("plan"):
            self.plan = self.solver.plan(graph, trace=trace)
        self.setup_info["plan_s"] = time.perf_counter() - t0
        if any(s.name == "plan.tail" for s in trace.spans):
            self.setup_info["plan_tail_s"] = trace.total_ms("plan.tail") / 1e3
        gauges = self.solver.metrics.snapshot()
        for name, key in GAUGES.items():
            if name in gauges:
                self.setup_info[key] = float(gauges[name])
        for k in self.workload.warm_keys():
            with span("warmup"):
                self.solver.solve(self.plan, key=jax.random.key(k))
