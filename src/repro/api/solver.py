"""`Solver` — the one front door to every MIS execution path.

The paper's pitch is that ONE tiled SpMV schedule serves every phase of
MIS; the Solver is that idea at the API layer: one object that decides
*where and how* a graph is solved (BLEST/HC-SpMM treat kernel choice as a
pluggable policy over one schedule — placement is the same kind of policy
one level up).  Routing (DESIGN.md §10):

    solve(graph)         placement policy per graph:
                           local    one jitted `lax.while_loop` dispatch
                                    on the configured round engine
                           sharded  the `core.distributed` shard_map path
                                    (auto: big padded graphs, >1 device)
    solve_many(graphs)   [] → [];  one graph → the single-graph path (no
                         bucket is ever built for a singleton);  many →
                         block-diagonal batcher, ONE dispatch per
                         tile-size group, members bit-identical to solo
                         runs; sharded-routed members peel off to their
                         own dispatch
    update(prior, delta) dynamic graphs (DESIGN.md §12): patch the plan
                         tile-locally through the cache, then repair the
                         solution per `options.repair` — warm-started
                         round-engine re-entry for small deltas, cold
                         re-solve otherwise

The Solver owns compiled-program reuse: one jitted single-graph program and
one jitted packed-batch program (their caches keyed by jax on the static
shape buckets), a bounded cache of shard_map programs, and the signature
set behind the `compile: reused|compiled` stat.  Determinism contract:
`solve` uses `jax.random.key(options.seed)` — the classic single-graph
spelling — while batched members get content-derived `request_key`s, so a
member's solution never depends on its batch, slot, or arrival order.
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Dict, Iterable, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.options import SolveOptions
from repro.api.plan import Plan, PlanCache, choose_tile_size, resolve_storage
from repro.core.engine import get_engine, resolve_frontier
from repro.core.heuristics import PACKED, make_priorities
from repro.core.luby import MISResult
from repro.core.tc_mis import _tc_mis_impl
from repro.core.tiling import full_tiling
from repro.graphs.graph import Graph
from repro.obs.metrics import MetricsRegistry
from repro.obs.rounds import RoundTrace
from repro.obs.trace import SCOPE_PRIORITIES, Trace, hlo_scopes, trace_span
from repro.perf.roofline import device_peaks, round_cost_attribution

GraphLike = Union[Graph, Plan]

_DIST_PROGRAM_CACHE = 16       # shard_map closures kept per Solver (LRU)
_SEEN_SIGNATURE_CAP = 4096     # compile-stat signature set bound (FIFO)
_AOT_PROGRAM_CACHE = 16        # AOT-compiled programs kept for traced runs
_PACK_CACHE_ENTRIES = 8        # packed batch structures kept per Solver (LRU)
_PACK_CACHE_BYTES = 2 << 30    # ...and the device bytes they may hold


def _fetch(result: MISResult):
    """(in_mis, rounds, converged, stream_share) as host values, in one
    device→host transfer."""
    return jax.device_get((
        result.in_mis, result.rounds, result.converged, result.stream_share,
    ))


@dataclasses.dataclass(frozen=True)
class SolveResult:
    """One graph's solution, in ORIGINAL vertex numbering.

    `rounds` is this graph's OWN convergence round — for batched solves the
    per-member counter (max of the member's per-vertex settle rounds), never
    the batch-slowest.  `converged` is exact for local/single solves and
    batch-global for packed members (one `lax.while_loop` flag is shared;
    an unconverged member still fails maximality on its own, which is how
    the serving layer's per-member verdict stays sound).
    """
    in_mis: np.ndarray          # (n_nodes,) bool, original vertex ids
    rounds: int
    converged: bool
    placement: str              # local | batched | sharded
    plan: Plan
    stats: Dict[str, object] = dataclasses.field(default_factory=dict)
    # per-round alive/frontier/selected/tiles-skipped series — populated only
    # when SolveOptions.telemetry is on (repro.obs.rounds; batched members
    # share the bucket's batch-global series, meta marks the scope)
    telemetry: Optional[RoundTrace] = None

    @property
    def mis_size(self) -> int:
        return int(np.asarray(self.in_mis).sum())

    @property
    def in_mis_plan(self) -> np.ndarray:
        """The solution in PLAN-id numbering (RCM-permuted when the plan
        reorders) — what validators over `plan.g` expect."""
        return self.plan.to_plan_ids(self.in_mis)


class Solver:
    """Plan → route → execute, with compiled-program reuse (DESIGN.md §10)."""

    def __init__(
        self,
        options: SolveOptions = SolveOptions(),
        *,
        plans: Optional[PlanCache] = None,
    ):
        get_engine(options.engine)   # fail fast, before any graph is planned
        self.options = options
        self.plans = plans if plans is not None else PlanCache(
            tile_size=options.tile_size or 32,
            reorder=options.reorder,
            storage=options.storage,   # cache default mirrors the Solver
            hybrid=options.hybrid,
            hybrid_threshold=options.hybrid_threshold,
            cache_dir=options.cache_dir,
            max_mem_entries=options.plan_cache_entries,
        )
        self._base_key = jax.random.key(options.seed)
        # host-side per-member priority cache for the batcher's host
        # heuristics (sound per Solver: one base key, one heuristic, and
        # ONLY default request_keys — solve_many bypasses it when the caller
        # supplies custom keys, since entries are keyed by plan content alone)
        self._priority_cache: Dict = {}
        # packed batch structures by (member plan keys, storage), LRU and
        # bounded by entries and device bytes: a restart of the same members
        # under fresh keys rebuilds and copies nothing key-independent
        self._packs: "OrderedDict[tuple, object]" = OrderedDict()
        # bounded FIFO set behind the `compile: reused|compiled` stat (note:
        # jax's own jit cache still grows per distinct static shape — a
        # stream of unboundedly many distinct single-graph shapes should
        # prefer solve_many, whose pow2 buckets bound the compiled programs)
        self._seen_signatures: "OrderedDict" = OrderedDict()
        self._dist_runs: "OrderedDict[str, object]" = OrderedDict()
        # AOT-compiled programs (lower().compile()), built only on TRACED
        # cold dispatches so the compile/execute span split is measured, not
        # estimated.  Untraced dispatches never touch this — they keep the
        # plain jit wrappers below, so jax's jit caches (which tests and the
        # default service path observe) behave exactly as before.
        self._aot: "OrderedDict[tuple, object]" = OrderedDict()
        # the metrics registry behind the legacy `stats` view (repro.obs)
        self.metrics = MetricsRegistry("solver")
        for k in ("solver.solves", "solver.batches", "solver.compiles",
                  "solver.pack_cache.hits", "solver.pack_cache.misses"):
            self.metrics.counter(k)
        # the two compiled-program seams: jax's jit cache keys on the packed
        # containers' static shape buckets, so a steady request mix converges
        # onto a handful of compiled programs
        self._jit_single = jax.jit(
            lambda g, tiled, key: _tc_mis_impl(g, tiled, key, options)
        )
        # the packed program takes the stacked member keys and builds H3's
        # priorities itself (`core.heuristics.PACKED`); other heuristics
        # arrive as packed priority vectors
        packed_priorities = PACKED.get(options.heuristic)

        def packed(g, tiled, alive0, gate, pri, slots):
            if packed_priorities is not None:
                with jax.named_scope(SCOPE_PRIORITIES):
                    pri = packed_priorities(pri, slots)
            return _tc_mis_impl(
                g, tiled, self._base_key, options,
                priorities=pri, alive0=alive0, col_gate=gate,
                member_rounds=True,
            )

        self._jit_packed = jax.jit(packed)
        # the warm-start (delta-repair) program; built on the first
        # `update` — repro.dyngraph imports the serving layer, so the seam
        # resolves lazily rather than at api-import time
        self._jit_repair = None

    @property
    def stats(self) -> Dict[str, int]:
        """Read-only view over the metrics registry in the legacy spelling
        (`{"solves": .., "batches": .., "compiles": ..}`) — downstream code
        reads these keys; writes go through `self.metrics`."""
        m = self.metrics
        return {
            "solves": m.counter("solver.solves").value,
            "batches": m.counter("solver.batches").value,
            "compiles": m.counter("solver.compiles").value,
        }

    # -- planning ----------------------------------------------------------

    def plan(self, graph: GraphLike, *, trace: Optional[Trace] = None) -> Plan:
        """Plan a graph through the content-addressed cache (a `Plan` passes
        through untouched).  Auto-T applies when `options.tile_size` is
        None; `options.storage='auto'` resolves per graph (bitpack once the
        estimated tile payload crosses the threshold, DESIGN.md §11).

        Runs under a `solver.plan` span; a cache miss records the build's
        stages inside it (`plan.key`, `plan.tiles`, `plan.tail`).  Sets the
        `plan.*` gauges (`_note_plan`) for the plan it returns."""
        with trace_span(trace, "solver.plan"):
            return self._plan(graph, trace)

    def _plan(self, graph: GraphLike, trace: Optional[Trace]) -> Plan:
        if isinstance(graph, Plan):
            return graph
        tile_size = self.options.tile_size or choose_tile_size(
            graph.n_nodes, graph.n_edges
        )
        storage = resolve_storage(
            self.options.storage, graph.n_nodes, graph.n_edges, tile_size
        )
        # the hybrid policy only partitions where an engine can use it —
        # the segment engine has no tile schedule to split, so hybrid plans
        # for it would carry dead partition arrays through every dispatch
        hybrid = self.options.hybrid
        if not get_engine(self.options.engine).supports_hybrid:
            hybrid = "off"
        plan, _ = self.plans.plan(
            graph, tile_size=tile_size, storage=storage,
            hybrid=hybrid, hybrid_threshold=self.options.hybrid_threshold,
            trace=trace,
        )
        self._note_plan(plan)
        return plan

    def _note_plan(self, plan: Plan) -> None:
        """Gauges of a plan's shape: `plan.dense_tiles` (tiles the tile
        schedule walks: the dense sub-tiling's, or every tile without a
        partition), `plan.tail_entries` / `plan.tail_capacity` (the COO
        tail's real and padded entries, 0 without one) and
        `plan.device_bytes` (`Plan.device_bytes`)."""
        part = plan.tiled.partition
        m = self.metrics
        m.gauge("plan.dense_tiles").set(
            plan.tiled.n_tiles if part is None else part.n_dense_tiles)
        m.gauge("plan.tail_entries").set(0 if part is None else part.sp_nnz)
        m.gauge("plan.tail_capacity").set(
            0 if part is None else int(part.sp_rows.shape[0]))
        m.gauge("plan.device_bytes").set(plan.device_bytes)

    def request_key(self, plan: Plan) -> jax.Array:
        """The content-derived per-graph key batched members are solved
        under (`serve_mis.batcher.request_key` semantics): independent of
        batch, slot and arrival order."""
        from repro.serve_mis.batcher import request_key

        return request_key(self._base_key, plan)

    # -- routing -----------------------------------------------------------

    def route(self, plan: Plan) -> str:
        """The placement policy: where would this plan execute?"""
        if self.options.placement != "auto":
            return self.options.placement
        big = plan.tiled.n_padded >= self.options.shard_threshold
        if big and jax.device_count() > 1:
            return "sharded"
        return "local"

    def program_scopes(self, graph: GraphLike) -> Dict[str, str]:
        """`{op name: "mis.*" scope path}` for the compiled program
        `solve(graph)` runs (`repro.obs.trace.hlo_scopes`): what splits a
        device trace of the solve by phase and path, since the trace names
        each op by its instruction name alone.  Lowers the same jit wrapper
        with the plan's own argument shapes, so after a warm solve under a
        persistent compile cache this is a cache load, not a compile."""
        plan = self.plan(graph)
        if self.route(plan) != "local":
            raise ValueError("only the local solve program carries mis.* scopes")
        key = jax.random.key(self.options.seed)
        compiled = self._jit_single.lower(plan.g, plan.tiled, key).compile()
        return hlo_scopes(compiled.as_text())

    # -- execution ---------------------------------------------------------

    def solve(
        self,
        graph: GraphLike,
        *,
        key: Optional[jax.Array] = None,
        trace: Optional[Trace] = None,
    ) -> SolveResult:
        """Solve one graph on whatever path the routing policy picks.

        `trace` (repro.obs.Trace, default None = zero-overhead) records
        plan/compile/execute spans; on a cold traced dispatch the program is
        compiled ahead-of-time so `compile_ms` and `execute_ms` are measured
        separately instead of conflated into `solve_ms`."""
        with trace_span(trace, "solver.solve"):
            plan = self.plan(graph, trace=trace)
            if key is None:
                key = jax.random.key(self.options.seed)
            if self.route(plan) == "sharded":
                return self._solve_sharded(plan, key, trace)
            return self._solve_local(plan, key, trace)

    def solve_many(
        self,
        graphs: Iterable[GraphLike],
        *,
        keys: Optional[Sequence[jax.Array]] = None,
        trace: Optional[Trace] = None,
    ) -> List[SolveResult]:
        """Solve a workload, batching where it pays.

        Empty input returns `[]` and a single graph routes through the
        single-graph path — neither ever builds a bucket.  Two or more
        local-routed members pack block-diagonally (grouped by tile size,
        since a batch must share T) into ONE dispatch each; sharded-routed
        members peel off to their own shard_map dispatch.  Results keep the
        input order.  `keys` may be one stacked key array; a batch of the
        same member plans as a recent call takes its packed structure from
        the pack cache (`_pack`), so best-of-K restarts move only keys.
        """
        with trace_span(trace, "solver.plan"):
            plans = [self._plan(g, trace) for g in graphs]
        if not plans:
            return []
        # the priority cache is keyed by plan content under the DEFAULT
        # request_key; custom keys must bypass it or they would silently
        # receive the cached default-key priorities
        default_keys = keys is None
        if default_keys:
            keys = [self.request_key(p) for p in plans]
        elif len(keys) != len(plans):
            raise ValueError(f"{len(plans)} graphs but {len(keys)} keys")
        if len(plans) == 1:
            return [self.solve(plans[0], key=keys[0], trace=trace)]

        out: List[Optional[SolveResult]] = [None] * len(plans)
        # a batch must share T AND tile storage (one block-diagonal dtype)
        groups: "OrderedDict[tuple, List[int]]" = OrderedDict()
        for i, p in enumerate(plans):
            if self.route(p) == "sharded":
                out[i] = self._solve_sharded(p, keys[i], trace)
            else:
                groups.setdefault((p.tile_size, p.tiled.storage), []).append(i)
        for idxs in groups.values():
            if len(idxs) == 1:
                i = idxs[0]
                out[i] = self._solve_local(plans[i], keys[i], trace)
                continue
            # a stacked key array of the whole input passes on unsliced
            whole = isinstance(keys, jax.Array) and len(idxs) == len(plans)
            solved = self._solve_batched(
                [plans[i] for i in idxs],
                keys if whole else [keys[i] for i in idxs],
                use_priority_cache=default_keys, trace=trace,
            )
            for i, r in zip(idxs, solved):
                out[i] = r
        return out   # type: ignore[return-value]

    def update(
        self,
        prior: SolveResult,
        delta,
        *,
        key: Optional[jax.Array] = None,
        trace: Optional[Trace] = None,
    ) -> SolveResult:
        """Apply an `EdgeDelta` to a solved graph and re-solve (DESIGN.md §12).

        The plan is patched tile-locally through the plan cache
        (`PlanCache.apply_delta` — delta-chained epoch key, stale pre-delta
        entry evicted), then the mutated graph is re-solved per
        `options.repair`:

          incremental   warm-start the round engine from `prior.in_mis`
                        with only the dirty frontier alive — small deltas
                        converge in a handful of rounds
          cold          a fresh `solve` of the patched plan
          auto          incremental while the delta touches ≤
                        `options.repair_threshold` of the vertices; also
                        falls back to cold when the patched plan routes
                        sharded (the shard_map loop has no warm seam yet)

        `prior` must be a converged result for the plan the delta applies
        to (chain updates by passing each result to the next `update`).
        Both paths solve under the same key and NEW-graph priorities, so an
        empty delta returns the prior solution bit-exactly either way.
        Stats gain `repair` (the mode taken), `patch` (plan-cache layer of
        the patched plan), `plan_epoch` and the delta sizes.
        """
        from repro.dyngraph.repair import dirty_mask, note_repair, repair_mis

        with trace_span(trace, "solver.plan"):
            plan2, patch_status = self.plans.apply_delta(prior.plan, delta)
        extra = dict(
            patch=patch_status, plan_epoch=plan2.epoch,
            delta_add=delta.n_add, delta_remove=delta.n_remove,
        )
        touched = delta.touched()
        mode = self.options.repair
        if mode == "auto":
            frac = touched.size / max(plan2.n_nodes, 1)
            mode = "incremental" if frac <= self.options.repair_threshold \
                else "cold"
        if mode == "incremental" and self.route(plan2) == "sharded":
            mode = "cold"
        note_repair(mode, dirty_frac=touched.size / max(plan2.n_nodes, 1))
        if mode == "cold":
            with trace_span(trace, "solver.update", mode="cold"):
                res = self.solve(plan2, key=key, trace=trace)
            return dataclasses.replace(
                res, stats=dict(res.stats, repair="cold", **extra)
            )

        if self._jit_repair is None:
            opts = self.options
            # priorities build INSIDE the compiled program from the key —
            # the same construction (new-graph degrees, same heuristic) the
            # cold path jits, so neither path pays eager priority dispatches
            self._jit_repair = jax.jit(
                lambda g, tiled, key, prior_mis, dirty: repair_mis(
                    g, tiled, key, opts, prior_mis, dirty
                )
            )
        if key is None:
            key = jax.random.key(self.options.seed)
        touched_plan = touched if plan2.inv is None else \
            np.asarray(plan2.inv)[touched]
        dirty = jnp.asarray(dirty_mask(plan2.n_nodes, touched_plan))
        prior_plan = jnp.asarray(plan2.to_plan_ids(prior.in_mis).astype(bool))
        t = plan2.tiled
        sig = ("repair", t.tile_size, t.storage, t.n_block_rows,
               t.n_block_cols, t.n_tiles, int(t.tiles.shape[0]), t.n_nodes,
               plan2.g.n_nodes, plan2.g.n_edges, plan2.g.e_pad,
               self._partition_sig(t))
        compile_stat = self._note_signature(sig)
        with trace_span(trace, "solver.update", mode="incremental"):
            out, timing = self._dispatch(
                self._jit_repair, sig, compile_stat, trace,
                plan2.g, plan2.tiled, key, prior_plan, dirty,
            )
        result, rt = self._split_telemetry(out, plan2.g, plan2.tiled,
                                           scope="repair")
        self.metrics.counter("solver.solves").inc()
        self.metrics.histogram("solver.solve_ms").observe(timing["solve_ms"])
        self._note_attribution(plan2.tiled, rt, timing["solve_ms"])
        return self._wrap(plan2, result, "local", dict(
            compile=compile_stat, batch_size=1,
            repair="incremental", **timing, **extra,
        ), telemetry=rt, trace=trace)

    # -- the three execution paths ----------------------------------------

    def _wrap(
        self,
        plan: Plan,
        result: MISResult,
        placement: str,
        stats: Dict,
        telemetry: Optional[RoundTrace] = None,
        trace: Optional[Trace] = None,
    ) -> SolveResult:
        with trace_span(trace, "solver.fetch"):
            in_mis_plan, rounds, converged, share = _fetch(result)
            in_mis = plan.to_original(in_mis_plan.astype(bool)).astype(bool)
            rounds, converged = int(rounds), bool(converged)
            stats = dict(stats, stream_share=self._note_stream_share(share))
        return SolveResult(
            in_mis=in_mis,
            rounds=rounds,
            converged=converged,
            placement=placement,
            plan=plan,
            stats=stats,
            telemetry=telemetry,
        )

    def _note_stream_share(self, share) -> float:
        """The solve's `stream_share` (`core.compaction`), fetched: stream
        entries its rounds read over what the full streams would have read;
        also the `solve.stream_share` gauge."""
        share = float(share)
        self.metrics.gauge("solve.stream_share").set(share)
        return share

    @staticmethod
    def _partition_sig(tiled):
        """The hybrid partition's static trace inputs (None when absent):
        threshold + both compacted list shapes.  Joins every jit-cache
        signature a partitioned tiling can reach — the partition is a
        pytree child of `BlockTiledGraph`, so jax already recompiles on
        these; the signature must agree or the compile stat lies."""
        p = tiled.partition
        if p is None:
            return None
        return (p.threshold, p.n_dense_tiles, int(p.dense.tiles.shape[0]),
                p.n_sparse_tiles, int(p.sp_rows.shape[0]))

    def _note_signature(self, sig) -> str:
        reused = sig in self._seen_signatures
        self._seen_signatures[sig] = True
        if not reused:
            self.metrics.counter("solver.compiles").inc()
            while len(self._seen_signatures) > _SEEN_SIGNATURE_CAP:
                self._seen_signatures.popitem(last=False)
        return "reused" if reused else "compiled"

    def _dispatch(self, jit_fn, sig, compile_stat, trace, *args):
        """One compiled-program dispatch → (output, timing stats dict).

        Untraced (the default): call the jit wrapper under a `solver.execute`
        profiler annotation, book the conflated wall clock as `solve_ms`.
        Traced: on a cold signature, lower + compile AHEAD of time under a
        `solver.compile` span (program kept in the bounded `_aot` cache,
        keyed by the same signature as the compile stat), then run under
        `solver.execute` — so `compile_ms` / `execute_ms` are measured
        separately and `solve_ms` is their sum, not a conflation.
        """
        t0 = time.perf_counter()
        if trace is None:
            with trace_span(None, "solver.execute"):
                out = jit_fn(*args)
                jax.block_until_ready(out)
            return out, {"solve_ms": round((time.perf_counter() - t0) * 1e3, 3)}
        timing = {}
        compiled = self._aot.get(sig)
        if compiled is None and compile_stat == "compiled":
            tc = time.perf_counter()
            with trace_span(trace, "solver.compile"):
                compiled = jit_fn.lower(*args).compile()
            timing["compile_ms"] = round((time.perf_counter() - tc) * 1e3, 3)
            self.metrics.histogram("solver.compile_ms").observe(
                timing["compile_ms"]
            )
            self._aot[sig] = compiled
            while len(self._aot) > _AOT_PROGRAM_CACHE:
                self._aot.popitem(last=False)
        fn = compiled if compiled is not None else jit_fn
        te = time.perf_counter()
        with trace_span(trace, "solver.execute"):
            out = fn(*args)
            jax.block_until_ready(out)
        now = time.perf_counter()
        timing["execute_ms"] = round((now - te) * 1e3, 3)
        timing["solve_ms"] = round((now - t0) * 1e3, 3)
        return out, timing

    def _split_telemetry(
        self, out, g: Graph, tiled, *, scope: str = "solve", batch_size: int = 1
    ):
        """Telemetry-off: identity → (result, None).  Telemetry-on: unpack
        the `(result, buffer)` pair `_tc_mis_impl` returns and materialise
        the buffer — THE one device→host telemetry transfer — into a
        `RoundTrace`."""
        if not self.options.telemetry:
            return out, None
        result, buf = out
        rounds = np.asarray(result.rounds)
        # vector (member_rounds) mode: the batch-global executed round count
        # is the max per-vertex settle round — the last round's selections
        # are real member vertices, all inside the slice
        rounds = int(rounds.max()) if rounds.ndim else int(rounds)
        engine = get_engine(self.options.engine)
        meta = dict(
            scope=scope,
            engine=self.options.engine,
            storage=tiled.storage,
            frontier=resolve_frontier(
                self.options, engine, storage=tiled.storage,
                member_rounds=batch_size > 1,
            ),
            n_nodes=g.n_nodes,
        )
        if batch_size > 1:
            meta["batch_size"] = batch_size
        # the tiles the schedule walks: a partition's dense sub-tiling
        walked = tiled if tiled.partition is None else tiled.partition.dense
        rt = RoundTrace.from_buffer(
            np.asarray(buf), rounds,
            tiles_total=int(walked.tile_cols.shape[0]), meta=meta,
        )
        return result, rt

    def _note_attribution(self, tiled, rt: Optional[RoundTrace],
                          solve_ms: float) -> None:
        """Roofline model-error gauges (DESIGN.md §17): predicted vs
        measured per-round cost from the telemetry dispatch-mix columns.

        Telemetry-on only (`rt is None` → no-op, so the telemetry-off path
        stays bit-identical), eager, and recorded only on a chip with a
        `DEVICE_PEAKS` entry: a CPU run has no roofline to score against.
        Gauges, not histograms: the operator question is "what is the
        model error NOW / is it trending" — `perf.roofline_error_pct`
        drifting under churn means the dispatch mix no longer matches what
        the plan priced.
        """
        if rt is None or not rt.rounds:
            return
        peaks = device_peaks()
        if peaks is None:
            return
        dense = sum(rt.tiles_dense) / rt.rounds if rt.tiles_dense else 0.0
        if dense <= 0.0 and rt.tiles_total:
            # engines that don't fill COL_TILES_DENSE (segment): every
            # non-skipped stored tile went through the one dense path
            dense = max(
                rt.tiles_total - sum(rt.tiles_skipped) / rt.rounds, 0.0
            )
        p = tiled.partition
        # the sentinel-padded COO tail is the per-round sparse stream
        # length — padding entries are processed too, so they cost
        sparse = float(p.sp_rows.shape[0]) if p is not None else 0.0
        att = round_cost_attribution(
            dense_tiles=dense, sparse_edges=sparse,
            tile_size=tiled.tile_size, storage=tiled.storage,
            measured_s=(solve_ms / 1e3) / rt.rounds, peaks=peaks,
        )
        self.metrics.gauge("perf.roofline_predicted_us").set(
            att["predicted_us"])
        self.metrics.gauge("perf.roofline_measured_us").set(
            att["measured_us"])
        self.metrics.gauge("perf.roofline_error_pct").set(att["error_pct"])

    def _solve_local(
        self, plan: Plan, key: jax.Array, trace: Optional[Trace] = None
    ) -> SolveResult:
        # every static trace input of the jitted program, or the stat lies
        t = plan.tiled
        sig = ("local", t.tile_size, t.storage, t.n_block_rows, t.n_block_cols,
               t.n_tiles, int(t.tiles.shape[0]), t.n_nodes, plan.g.n_nodes,
               plan.g.n_edges, plan.g.e_pad, self._partition_sig(t))
        compile_stat = self._note_signature(sig)
        out, timing = self._dispatch(
            self._jit_single, sig, compile_stat, trace, plan.g, plan.tiled, key
        )
        result, rt = self._split_telemetry(out, plan.g, plan.tiled)
        self.metrics.counter("solver.solves").inc()
        self.metrics.histogram("solver.solve_ms").observe(timing["solve_ms"])
        self._note_attribution(plan.tiled, rt, timing["solve_ms"])
        return self._wrap(plan, result, "local", dict(
            compile=compile_stat, batch_size=1, **timing,
        ), telemetry=rt, trace=trace)

    def _pack(self, plans: Sequence[Plan], trace: Optional[Trace]):
        """The packed structure of `plans`, from the pack cache or built
        under a `pack.structure` span; sets the `batch.edge_fill` and
        `batch.tile_fill` gauges."""
        from repro.serve_mis.batcher import pack_batch

        key = (tuple(p.key for p in plans), plans[0].tiled.storage)
        batch = self._packs.get(key)
        if batch is not None:
            self._packs.move_to_end(key)
            self.metrics.counter("solver.pack_cache.hits").inc()
        else:
            self.metrics.counter("solver.pack_cache.misses").inc()
            with trace_span(trace, "pack.structure"):
                batch = pack_batch(plans)
            self._packs[key] = batch
            while self._packs and (
                len(self._packs) > _PACK_CACHE_ENTRIES
                or sum(b.device_bytes for b in self._packs.values())
                > _PACK_CACHE_BYTES
            ):
                self._packs.popitem(last=False)
        self.metrics.gauge("batch.edge_fill").set(batch.edge_fill)
        self.metrics.gauge("batch.tile_fill").set(batch.tile_fill)
        return batch

    def _packed_args(self, plans, keys, use_priority_cache, trace):
        """(structure, arguments of the packed program) for one call."""
        from repro.serve_mis.batcher import stack_keys

        with trace_span(trace, "solver.pack", batch_size=len(plans)):
            batch = self._pack(plans, trace)
            if self.options.heuristic in PACKED:
                pri = stack_keys(keys, batch.bucket.n_members)
            else:
                pri = batch.priorities(
                    plans, keys, self.options.heuristic,
                    priority_cache=self._priority_cache
                    if use_priority_cache else None,
                )
        return batch, (batch.g, batch.tiled, batch.alive0, batch.col_gate,
                       pri, batch.slots)

    def batch_program_scopes(self, graphs: Iterable[GraphLike]) -> Dict[str, str]:
        """`{op name: "mis.*" scope path}` for the packed program that
        `solve_many(graphs)` runs, when its members pack into one batch:
        `program_scopes` for a batch.  A cache load after a warm call under
        a persistent compile cache, as there."""
        plans = [self.plan(g) for g in graphs]
        if len(plans) < 2 or any(self.route(p) != "local" for p in plans) or \
                len({(p.tile_size, p.tiled.storage) for p in plans}) > 1:
            raise ValueError("the graphs do not pack into one local batch")
        keys = [self.request_key(p) for p in plans]
        _, args = self._packed_args(plans, keys, True, None)
        return hlo_scopes(self._jit_packed.lower(*args).compile().as_text())

    def _solve_batched(
        self,
        plans: Sequence[Plan],
        keys: Sequence[jax.Array],
        use_priority_cache: bool = True,
        trace: Optional[Trace] = None,
    ) -> List[SolveResult]:
        batch, args = self._packed_args(plans, keys, use_priority_cache, trace)
        sig = batch.signature()
        compile_stat = self._note_signature(sig)
        self.metrics.counter("solver.batches").inc()
        self.metrics.histogram("solver.batch_size").observe(len(plans))

        out_raw, timing = self._dispatch(
            self._jit_packed, sig, compile_stat, trace, *args
        )
        result, rt = self._split_telemetry(
            out_raw, batch.g, batch.tiled, scope="batch",
            batch_size=len(plans),
        )
        self.metrics.counter("solver.solves").inc(len(plans))
        self.metrics.histogram("solver.batch_ms").observe(timing["solve_ms"])
        self._note_attribution(batch.tiled, rt, timing["solve_ms"])
        in_mis, rounds, converged, share = _fetch(result)
        converged = bool(converged)

        # attribution (DESIGN.md §14): ONE dispatch served the whole bucket,
        # so each member's `solve_ms` is its 1/batch share, with the shared
        # wall clock reported explicitly as `batch_ms` — summing members'
        # solve_ms across a workload now totals real device time instead of
        # multiply-counting every bucket by its size.  `compile_ms` (cold
        # traced dispatches) stays whole-bucket — compilation is not
        # per-member work.
        batch_ms = timing.pop("solve_ms")
        shared = dict(
            solve_ms=round(batch_ms / len(plans), 3),
            batch_ms=batch_ms, bucket=sig,
            compile=compile_stat, batch_size=len(plans), **timing,
            stream_share=self._note_stream_share(share),
        )
        out = []
        for plan, mis, rnd in zip(
            plans, batch.unpack(in_mis), batch.unpack(rounds)
        ):
            in_mis_plan = np.asarray(mis).astype(bool)
            out.append(SolveResult(
                in_mis=plan.to_original(in_mis_plan).astype(bool),
                rounds=int(np.max(rnd)) if rnd.size else 0,
                converged=converged,
                placement="batched",
                plan=plan,
                stats=dict(shared),
                telemetry=rt,   # batch-global series, shared by members
            ))
        return out

    def _solve_sharded(
        self, plan: Plan, key: jax.Array, trace: Optional[Trace] = None
    ) -> SolveResult:
        from repro.core.distributed import (
            DistConfig, build_distributed_mis, shard_tiled,
        )

        n_dev = jax.device_count()
        run = self._dist_runs.get(plan.key)
        compile_stat = "reused" if run is not None else "compiled"
        if run is None:
            self.metrics.counter("solver.compiles").inc()
            with trace_span(trace, "solver.compile", placement="sharded"):
                mesh = jax.make_mesh(
                    (n_dev,), ("shard",),
                    axis_types=(jax.sharding.AxisType.Auto,),
                )
                # documented dense-only fallback (DESIGN.md §16): the
                # shard_map loop has no sparse-tail seam, so it takes the
                # full tile list, rebuilt from the partition
                tiled_full = full_tiling(plan.tiled)
                sharded = shard_tiled(tiled_full, n_shards=n_dev)
                run = build_distributed_mis(sharded, mesh, DistConfig(
                    max_rounds=self.options.max_rounds,
                    bitpack=self.options.bitpack,
                    lanes=self.options.lanes,
                ))
            self._dist_runs[plan.key] = run
            while len(self._dist_runs) > _DIST_PROGRAM_CACHE:
                self._dist_runs.popitem(last=False)

        pri = make_priorities(
            self.options.heuristic, key, plan.g.n_nodes, plan.g.degrees()
        )
        t0 = time.perf_counter()
        with trace_span(trace, "solver.execute", placement="sharded"):
            res = run(pri)
            jax.block_until_ready(res.in_mis)
        solve_ms = (time.perf_counter() - t0) * 1e3
        self.metrics.counter("solver.solves").inc()
        self.metrics.histogram("solver.solve_ms").observe(round(solve_ms, 3))
        rounds = int(res.rounds)
        in_mis_plan = np.asarray(res.in_mis)[: plan.g.n_nodes].astype(bool)
        return SolveResult(
            in_mis=plan.to_original(in_mis_plan).astype(bool),
            rounds=rounds,
            # the shard_map loop returns no explicit flag; exiting before the
            # bound is the (conservative) convergence signal
            converged=rounds < self.options.max_rounds,
            placement="sharded",
            plan=plan,
            stats=dict(
                solve_ms=round(solve_ms, 3), compile=compile_stat,
                n_shards=n_dev, batch_size=1,
            ),
        )
