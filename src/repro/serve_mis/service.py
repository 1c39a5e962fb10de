"""The MIS serving loop: requests in, validated per-graph solutions out.

Request lifecycle (DESIGN.md §9):

    submit ─ ingest (io) ─ plan (planner cache) ─┐
    submit ─ ingest ─ plan ───────────────────────┤ queue
    submit_update ─ (targets a served result) ────┤
    ...                                           │
                 step(): pop ≤ max_batch ─ Solver.solve_many (block-diagonal
                 pack, ONE dispatch per batch; updates patch their cached
                 plan tile-locally + warm-repair, DESIGN.md §12) ─ fused
                 validity post-condition per member ─ Response

Every response carries per-request stats — queue time, plan-cache layer
(mem/disk/built), bucket signature, whether this batch reused a compiled
program, batch solve time, the member's OWN convergence round, |MIS| — and
the post-condition verdict from `validate.is_valid_mis_jit` (one fused
jitted check per member).

The execution seam is `repro.api.Solver` (DESIGN.md §10): the service owns
the queue and the per-request bookkeeping, the Solver owns planning,
routing (batched here; large graphs can peel off to the shard_map path on
multi-device hosts) and compiled-program reuse — its jit cache is keyed by
the packed batch's static shapes, which the batcher buckets, so a steady
request mix converges onto a handful of compiled programs.
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict, deque
from typing import Deque, Dict, List, Optional, Union

import jax.numpy as jnp
import numpy as np

from repro.api import Solver, SolveOptions
from repro.core.validate import is_valid_mis_jit
from repro.dyngraph.delta import EdgeDelta
from repro.graphs.graph import Graph
from repro.obs.metrics import REGISTRY, MetricsRegistry
from repro.obs.trace import JsonlWriter, Trace, trace_span
from repro.serve_mis.io import load_graph
from repro.serve_mis.planner import TilePlan


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Knobs of the serving layer (the solve knobs mirror `SolveOptions`)."""
    tile_size: int = 32
    heuristic: str = "h3"
    engine: str = "fused_pallas"   # any registered round engine
    phase1: str = "segment"
    lanes: int = 8
    skip_dma: bool = False
    max_rounds: int = 1024
    max_batch: int = 8             # requests per packed dispatch
    reorder: Optional[str] = None  # None | 'rcm'
    storage: str = "auto"          # tile storage: auto | int8 | bitpack
    cache_dir: Optional[str] = None
    plan_cache_entries: int = 256  # memory-layer LRU bound (disk is unbounded)
    validate: bool = True
    seed: int = 0
    repair: str = "auto"           # delta-update policy (SolveOptions.repair)
    # completed-result retention (the targets `submit_update` may name).
    # Each retained result pins its Plan — tiles included — so this bound
    # matches plan_cache_entries by default: retention must not out-pin
    # the plan cache's own memory bound.
    result_entries: int = 256
    # observability (repro.obs, DESIGN.md §14): `telemetry` turns on the
    # on-device round buffer (responses carry per-round series);
    # `trace_path` appends span traces + round series as JSONL there (each
    # worker step is one Trace).  Both off = the pre-obs zero-cost path.
    telemetry: bool = False
    trace_path: Optional[str] = None

    def solve_options(self) -> SolveOptions:
        """The Solver half of this config (the front door, DESIGN.md §10)."""
        return SolveOptions(
            heuristic=self.heuristic,
            engine=self.engine,
            phase1=self.phase1,
            lanes=self.lanes,
            skip_dma=self.skip_dma,
            max_rounds=self.max_rounds,
            tile_size=self.tile_size,
            reorder=self.reorder,
            storage=self.storage,
            placement="auto",
            seed=self.seed,
            cache_dir=self.cache_dir,
            plan_cache_entries=self.plan_cache_entries,
            repair=self.repair,
            telemetry=self.telemetry,
        )


@dataclasses.dataclass
class Request:
    id: int
    source: str
    plan: TilePlan
    plan_status: str      # mem | disk | built
    t_enqueue: float


@dataclasses.dataclass
class UpdateRequest:
    """A graph-mutation request: patch request `base_id`'s graph with
    `delta` and repair its solution (DESIGN.md §12).  `base_id` must name a
    COMPLETED request — chain mutations by targeting each update's own id
    once it has been served."""
    id: int
    base_id: int
    source: str
    delta: EdgeDelta
    t_enqueue: float


@dataclasses.dataclass
class Response:
    id: int
    source: str
    in_mis: np.ndarray    # (n_nodes,) bool, ORIGINAL vertex ids
    mis_size: int
    independent: bool
    maximal: bool
    converged: bool       # BATCH-global (the shared while_loop's flag)
    rounds: int           # this member's OWN convergence round
    stats: Dict[str, object]

    @property
    def valid(self) -> bool:
        """Per-member verdict — deliberately NOT ANDed with `converged`.

        `converged` is batch-global, so one max_rounds-limited member must
        not poison its batchmates.  The invariants alone are exact per
        member: a member cut off mid-solve still has alive vertices, and an
        alive vertex is by construction unselected with no selected
        neighbour — which is precisely a maximality violation, so
        `maximal` is False for any unconverged member.
        """
        return self.independent and self.maximal

    def summary(self) -> Dict[str, object]:
        """JSON-friendly per-request record (solution vector elided)."""
        return dict(
            id=self.id,
            source=self.source,
            n_nodes=int(self.in_mis.shape[0]),
            mis_size=self.mis_size,
            valid=self.valid,
            rounds=self.rounds,
            **self.stats,
        )


class MISService:
    """Request-queue MIS worker over the `Solver` front door."""

    def __init__(self, config: ServeConfig = ServeConfig()):
        self.config = config
        self.solver = Solver(config.solve_options())  # raises on bad engine
        self.planner = self.solver.plans
        self._queue: Deque[Union[Request, UpdateRequest]] = deque()
        self._next_id = 0
        self._steps = 0
        # completed results by request id — the targets `submit_update`
        # may name (bounded FIFO; a long stream retires old targets)
        self._results: "OrderedDict[int, object]" = OrderedDict()
        # compat aliases for introspection (tests, tooling): the Solver owns
        # the base key and the jitted packed dispatch now
        self._base_key = self.solver._base_key
        self._solve = self.solver._jit_packed
        # observability (repro.obs): service-level metrics registry + the
        # optional JSONL sink for span traces and round series
        self.metrics = MetricsRegistry("service")
        self.metrics.counter("service.requests")
        self._trace_writer = (
            JsonlWriter(config.trace_path) if config.trace_path else None
        )

    @property
    def stats(self) -> Dict[str, int]:
        return {
            "requests": self.metrics.counter("service.requests").value,
            "batches": self.solver.stats["batches"],
            "compiles": self.solver.stats["compiles"],
        }

    def metrics_snapshot(self) -> Dict[str, object]:
        """One operator-facing dict over every registry this service can
        see: its own instruments, the Solver's, the plan cache's, and the
        process-wide registry (batcher priority cache, repair decisions).
        Names are layer-prefixed (`service.*`, `solver.*`, `plan_cache.*`,
        `batcher.*`, `repair.*`), so the flat merge cannot collide."""
        out: Dict[str, object] = {}
        for reg in (REGISTRY, self.solver.metrics, self.planner.metrics,
                    self.metrics):
            out.update(reg.snapshot())
        return out

    # -- intake ------------------------------------------------------------

    def submit(
        self,
        source: Union[str, Graph],
        *,
        fmt: Optional[str] = None,
        n_nodes: Optional[int] = None,
        stream: bool = False,
    ) -> int:
        """Ingest + plan (cache-aware) and enqueue; returns the request id.

        `stream=True` ingests file sources through the chunked readers
        (`repro.dyngraph.stream.load_graph_stream`) — same Graph, same
        plan-cache hits, without the whole-file line list."""
        if isinstance(source, Graph):
            graph, name = source, f"<graph:{source.n_nodes}v>"
        elif stream:
            from repro.dyngraph.stream import load_graph_stream

            name = str(source)
            graph = load_graph_stream(name, fmt=fmt, n_nodes=n_nodes)
        else:
            name = str(source)
            graph = load_graph(name, fmt=fmt, n_nodes=n_nodes)
        plan, status = self.planner.plan(graph)
        req = Request(
            id=self._next_id,
            source=name,
            plan=plan,
            plan_status=status,
            t_enqueue=time.perf_counter(),
        )
        self._next_id += 1
        self.metrics.counter("service.requests").inc()
        self._queue.append(req)
        return req.id

    def submit_update(self, base_id: int, delta: EdgeDelta) -> int:
        """Enqueue a graph mutation against a COMPLETED request (DESIGN.md
        §12): the base request's cached plan is patched tile-locally and
        its solution repaired per `config.repair` — never a re-ingest, and
        for small deltas never a cold re-solve.  Chain mutations by
        targeting the previous update's own id once it has been served;
        an unknown or not-yet-completed `base_id` raises KeyError."""
        if base_id not in self._results:
            raise KeyError(
                f"update targets request {base_id}, which has not completed "
                f"(updates chain off served results; drain first)"
            )
        # fail fast on the cheap structural check; set-strictness (absent
        # removes / present adds) surfaces at step time as an error response
        delta.check_bounds(self._results[base_id].plan.n_nodes)
        req = UpdateRequest(
            id=self._next_id,
            base_id=base_id,
            source=f"<update:{base_id}+{delta.n_add}-{delta.n_remove}>",
            delta=delta,
            t_enqueue=time.perf_counter(),
        )
        self._next_id += 1
        self.metrics.counter("service.requests").inc()
        self._queue.append(req)
        return req.id

    @property
    def pending(self) -> int:
        return len(self._queue)

    # -- the worker step ----------------------------------------------------

    def step(self) -> List[Response]:
        """Pop ≤ max_batch requests, solve them through the Solver, respond.

        Solve requests in the window share one batched dispatch; update
        requests repair individually (each is one warm-started dispatch
        against its own patched plan).  A failing update — a delta that
        violates set strictness against the graph it targets, or a base
        result that aged out of retention — yields an INVALID error
        response; it never kills the stream or its window-mates.  Response
        order is pop order.
        """
        if not self._queue:
            return []
        reqs = [
            self._queue.popleft()
            for _ in range(min(self.config.max_batch, len(self._queue)))
        ]
        # one Trace per worker step, created only when a sink is configured
        # — tr=None keeps the Solver on its untraced (pre-obs) dispatch path
        tr = (
            Trace(f"step-{self._steps}")
            if self._trace_writer is not None else None
        )
        self._steps += 1
        self.metrics.counter("service.steps").inc()
        self.metrics.histogram("service.window").observe(len(reqs))
        # health gauges (DESIGN.md §17), sampled once per worker step:
        # what's still waiting behind this window, and what's in flight now
        self.metrics.gauge("service.queue_depth").set(len(self._queue))
        self.metrics.gauge("service.inflight").set(len(reqs))
        t_pop = time.perf_counter()
        solves = [r for r in reqs if isinstance(r, Request)]
        with trace_span(tr, "service.step", size=len(reqs)):
            with trace_span(tr, "service.batch", size=len(solves)):
                results = dict(zip(
                    (r.id for r in solves),
                    self.solver.solve_many(
                        [r.plan for r in solves], trace=tr
                    ),
                ))
            for r in reqs:
                if isinstance(r, UpdateRequest):
                    try:
                        results[r.id] = self._run_update(r, tr)
                    except (ValueError, KeyError) as e:
                        results[r.id] = e

        responses = []
        for req, res in ((r, results[r.id]) for r in reqs):
            queue_ms = round((t_pop - req.t_enqueue) * 1e3, 3)
            self.metrics.histogram("service.queue_ms").observe(queue_ms)
            if isinstance(res, Exception):
                self.metrics.counter("service.errors").inc()
                responses.append(Response(
                    id=req.id, source=req.source,
                    in_mis=np.zeros(0, dtype=bool), mis_size=0,
                    independent=False, maximal=False, converged=False,
                    rounds=0,
                    stats=dict(
                        queue_ms=queue_ms,
                        error=f"{type(res).__name__}: {res}",
                        batch_size=len(reqs),
                    ),
                ))
                continue
            independent = maximal = True
            if self.config.validate:
                with trace_span(tr, "service.validate", id=req.id):
                    independent, maximal = is_valid_mis_jit(
                        res.plan.g, jnp.asarray(res.in_mis_plan)
                    )
            in_mis = np.asarray(res.in_mis).astype(bool)
            is_update = isinstance(req, UpdateRequest)
            stats = dict(
                queue_ms=queue_ms,
                solve_ms=res.stats.get("solve_ms", 0.0),
                plan_cache=res.stats["patch"] if is_update else req.plan_status,
                bucket=res.stats.get("bucket", res.placement),
                compile=res.stats.get("compile", "n/a"),
                batch_size=len(reqs),
            )
            # traced dispatches split solve_ms into its phases — surface
            # them (plus the batch wall the member's share came from)
            for k in ("batch_ms", "compile_ms", "execute_ms"):
                if k in res.stats:
                    stats[k] = res.stats[k]
            if is_update:
                stats.update(
                    repair=res.stats["repair"],
                    plan_epoch=res.stats["plan_epoch"],
                    base_id=req.base_id,
                )
            rt = getattr(res, "telemetry", None)
            if rt is not None:
                stats["rounds_summary"] = rt.summary()
            # per-op SLO latency (enqueue → response built): one fixed-
            # bucket histogram per op, so p50/p95/p99 read per route
            op = ("update" if is_update
                  else "batched" if res.placement == "batched" else "solve")
            self.metrics.histogram(f"service.latency_ms.{op}").observe(
                round((time.perf_counter() - req.t_enqueue) * 1e3, 3)
            )
            responses.append(Response(
                id=req.id,
                source=req.source,
                in_mis=in_mis,
                mis_size=int(in_mis.sum()),
                independent=independent,
                maximal=maximal,
                converged=res.converged,
                rounds=res.rounds,
                stats=stats,
            ))
            self._results[req.id] = res
            while len(self._results) > max(self.config.result_entries, 1):
                self._results.popitem(last=False)
        self.metrics.gauge("service.inflight").set(0)
        if tr is not None:
            # per-stage latency distributions over the span taxonomy
            # (service.step ⊃ service.batch/validate; solver.solve ⊃
            # plan/pack/compile/execute; solver.update) — traced steps
            # only, so the untraced path records nothing extra
            for s in tr.spans:
                self.metrics.histogram(
                    f"service.span_ms.{s.name}"
                ).observe(round(s.dur_ms, 3))
        if self._trace_writer is not None:
            self._trace_writer.write_trace(tr)
            # one rounds record per distinct RoundTrace — batched members
            # share the batch-global series, so dedupe by object identity
            seen_ids = set()
            for req in reqs:
                res = results[req.id]
                rt = getattr(res, "telemetry", None)
                if rt is not None and id(rt) not in seen_ids:
                    seen_ids.add(id(rt))
                    self._trace_writer.write_rounds(rt)
        return responses

    def _run_update(self, r: UpdateRequest, trace: Optional[Trace] = None):
        """One update's repair dispatch, under the CONTENT-DERIVED key of
        the patched graph — the key a fresh submission of that mutated
        graph would be solved under (`Solver.request_key`), and, for an
        empty delta, exactly the key the base response was solved under.
        That keeps update responses bit-consistent with the service's own
        solve path in every repair mode (a plain `Solver.update` defaults
        to the classic seed key instead, matching `Solver.solve`)."""
        if r.base_id not in self._results:
            raise KeyError(
                f"update {r.id} targets request {r.base_id}, whose result "
                f"aged out of retention (result_entries="
                f"{self.config.result_entries})"
            )
        prior = self._results[r.base_id]
        # this first patch is the authoritative cache probe; Solver.update's
        # own apply_delta then mem-hits by construction, so ITS patch stat
        # would always read 'mem' — overwrite with the real layer
        plan2, patch_status = self.solver.plans.apply_delta(prior.plan, r.delta)
        res = self.solver.update(
            prior, r.delta, key=self.solver.request_key(plan2), trace=trace
        )
        res.stats["patch"] = patch_status
        return res

    def drain(self) -> List[Response]:
        """Run worker steps until the queue is empty."""
        out: List[Response] = []
        while self._queue:
            out.extend(self.step())
        return out
