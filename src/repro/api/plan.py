"""`Plan` — the immutable solve artifact — and its content-addressed cache.

BLEST and HC-SpMM both measure the format/preprocessing layer — not the
kernel — as the dominant cost of end-to-end tensor-core graph workloads, and
this repo is no different: RCM reordering plus the BSR tile scatter dwarfs a
converged MIS solve at serving scale.  A `Plan` is everything that cost
buys — the canonical (optionally RCM-permuted) graph, its per-graph BSR
tiling, the build parameters (tile size, reorder choice), and the
permutation to map results back — keyed by a sha256 over the canonical edge
list and the build parameters, so a repeat request for the same graph (same
*content*, regardless of which file or object it arrived in) skips
preprocessing entirely:

    memory hit    dict lookup, zero work
    disk hit      one `np.load` (plans persist across processes)
    miss          full build, then written through to both layers

`Plan.build(graph, cache=...)` is the front door; the `PlanCache` it wraps
(formerly `repro.serve_mis.planner`, absorbed here) stays available for
callers that want cache-layer stats.  Per-graph plans are also exactly the
unit the block-diagonal batcher (`serve_mis.batcher`) concatenates: a batch
never re-tiles its members, it offsets their cached tile lists.

This module also owns the default **auto-T policy**: when no tile size is
given, `choose_tile_size` picks the largest MXU-friendly T whose worst-case
BSR payload fits a per-chip byte budget — the paper's §3.2 memory/regularity
trade-off made explicit (hub-less meshes take full 128×128 MXU tiles,
hub-heavy power-law graphs fall back to smaller tiles exactly as the paper's
16×16 WMMA does).  `configs.tcmis` drives the same `fit_tile_size` loop with
its measured-occupancy estimator for the full-scale dry-runs.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import os
import time
import uuid
import warnings
from collections import OrderedDict
from typing import Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tiling import (
    STORAGES as TILE_STORAGES,
    BlockTiledGraph,
    TileCells,
    attach_partition,
    build_block_tiles,
    coo_tail,
    full_tiling,
    next_pow2,
    partition_pays,
    partitioned_tiling,
    rcm_ordering,
    tiles_from_cells,
)
from repro.graphs.graph import Graph, from_edges
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Trace, trace_span

# the PlanCache's legacy stats spelling, now a view over its metrics
# registry (repro.obs; DESIGN.md §14)
_PLAN_STAT_KEYS = ("mem_hits", "disk_hits", "misses", "evicted_stale")

# v2: the storage axis (DESIGN.md §11) — packed uint32 tiles on disk, storage
# in the cache key, and a version+storage tail on the npz `meta` record.
# The version is deliberately NOT part of the cache key: future bumps land
# on the SAME filename, where `_load`'s meta check detects the stale layout,
# warns once per eviction, deletes the file and rebuilds.  (v1 files are the
# one exception — `storage` joined the key string in v2, so they sit at old
# key paths; `PlanCache.plan` probes the legacy v1 key on a disk miss and
# evicts those too.)  Patched plans (`Plan.apply_delta`, DESIGN.md §12)
# persist in the same v2 layout under delta-chained keys (`delta_cache_key`)
# with an optional `epoch` tail record; superseded pre-delta entries are
# retired through the same eviction machinery (`PlanCache.apply_delta`).
#
# v3: the hybrid axis (DESIGN.md §16) — the tile-partition POLICY (mode +
# resolved nnz threshold) joins the meta record and, for hybrid != 'off',
# the cache key (`|h{mode}:{threshold}` tail; 'off' keys are unchanged so
# off-mode requests land on the v2 paths and the version check retires the
# old layout in place).
#
# v4: a partitioned plan holds no full tile list (DESIGN.md §16), so its
# entry persists the partition itself: `tiles`/`tile_rows`/`tile_cols`/
# `row_starts` are the dense sub-tiling's, `sp_rows`/`sp_cols` the COO
# tail, and the meta record gains the dense tile count and the tail's real
# entry count.  An unpartitioned entry keeps the v3 arrays (dense count
# -1, no tail arrays).
#
# v5: the COO tail's capacity is `tail_capacity(sp_nnz)` (a multiple of
# 1024 from 1024 entries up), no longer the next power of two; a v4 tail
# would still solve, but stream up to twice the entries it needs.
_PLAN_VERSION = 5
# n_nodes, n_edges, n_tiles, tile_size, nbr, nbc, version, storage,
# hybrid mode, hybrid threshold, dense tiles (-1: unpartitioned), tail nnz
_META_LEN = 12

# partition policy axis, in meta-index order (0 = off keeps the v2 keys)
HYBRID_MODES = ("off", "auto", "forced")

# --------------------------------------------------------------------------
# the auto-T policy (paper §3.2: largest T whose BSR fits the budget)
# --------------------------------------------------------------------------

DEFAULT_TILE_BUDGET = 512 << 20   # bytes of BSR payload per chip
TILE_CANDIDATES = (128, 64, 32, 16)


def worst_case_tile_bytes(n_nodes: int, n_edges: int, tile_size: int) -> float:
    """Worst-case stored int8 BSR payload: `min(E, nb²)·T²` — every
    half-edge its own tile, capped by the block grid, so the bound never
    under-estimates.  THE shared estimate of both auto policies (auto-T
    and auto-storage): one definition, or their decisions desynchronise."""
    T = int(tile_size)
    nb = -(-max(int(n_nodes), 1) // T)
    return min(max(int(n_edges), 1), nb * nb) * T * T


def fit_tile_size(
    payload_bytes: Callable[[int], float],
    *,
    budget: int = DEFAULT_TILE_BUDGET,
    candidates: Tuple[int, ...] = TILE_CANDIDATES,
) -> int:
    """Largest candidate T whose estimated per-chip payload fits `budget`.

    `payload_bytes(T)` estimates the stored-BSR bytes at tile size T — the
    caller chooses the estimator (worst-case bound here, measured block
    occupancy in `configs.tcmis.choose_tile_size`).  Falls back to the
    smallest candidate when nothing fits (the paper's 16×16 WMMA floor).
    """
    for T in candidates:
        if payload_bytes(T) <= budget:
            return T
    return candidates[-1]


# --------------------------------------------------------------------------
# the auto-storage policy (DESIGN.md §11: bitpack once tile bytes bite)
# --------------------------------------------------------------------------

BITPACK_AUTO_THRESHOLD = 1 << 20   # est. int8 tile payload bytes → bitpack


def resolve_storage(
    storage: str,
    n_nodes: int,
    n_edges: int,
    tile_size: int,
    *,
    threshold: int = BITPACK_AUTO_THRESHOLD,
) -> str:
    """Concrete tile storage for a graph: 'auto' flips to bitpack once the
    worst-case int8 tile payload (`worst_case_tile_bytes`, shared with the
    auto-T policy so the two agree on the estimate) crosses `threshold`
    bytes — small graphs keep the simpler dense tiles, large ones take the
    8× HBM/DMA reduction.  Concrete spellings pass through."""
    if storage in TILE_STORAGES:
        return storage
    if storage != "auto":
        raise ValueError(
            f"unknown storage {storage!r}; valid: {('auto',) + TILE_STORAGES}"
        )
    est = worst_case_tile_bytes(n_nodes, n_edges, tile_size)
    return "bitpack" if est >= threshold else "int8"


# --------------------------------------------------------------------------
# the hybrid-partition policy (DESIGN.md §16: roofline break-even threshold)
# --------------------------------------------------------------------------


def resolve_hybrid_threshold(
    tile_size: int, storage: str, threshold: Optional[int] = None
) -> int:
    """Concrete nnz classifier cut for a plan: the caller's override, or the
    analytic roofline break-even for this (tile size, storage) — the edge
    count at which one dense tile pass costs the same as streaming its
    edges through the COO/segment tail (`repro.perf.hybrid_density_threshold`).
    Resolved at PLAN time so the cache key and the persisted meta record
    name a concrete integer, never a policy that could drift."""
    if threshold is not None:
        return int(threshold)
    from repro.perf.roofline import hybrid_density_threshold

    return hybrid_density_threshold(tile_size, storage)


def choose_tile_size(
    n_nodes: int,
    n_edges: int,
    *,
    n_chips: int = 1,
    budget: int = DEFAULT_TILE_BUDGET,
) -> int:
    """Default auto-T for an arbitrary graph (no structure measured yet).

    Worst-case tile count is `min(E, nb²)` — every half-edge its own tile,
    capped by the block grid — so the bound never under-estimates.  Tiny
    graphs are additionally capped to tiles no wider than their padded
    vertex range (a 50-vertex graph never takes 128×128 tiles).
    """
    cap = next_pow2(max(min(int(n_nodes), TILE_CANDIDATES[0]), TILE_CANDIDATES[-1]))
    candidates = tuple(T for T in TILE_CANDIDATES if T <= cap) or (TILE_CANDIDATES[-1],)

    def per_chip_bytes(T: int) -> float:
        return worst_case_tile_bytes(n_nodes, n_edges, T) / max(int(n_chips), 1)

    return fit_tile_size(per_chip_bytes, budget=budget, candidates=candidates)


# --------------------------------------------------------------------------
# the plan artifact
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Plan:
    """One graph's cached preprocessing artefacts — the immutable solve unit.

    `g` and `tiled` index *plan ids*: the RCM-permuted vertex numbering when
    `perm` is set, the original numbering otherwise.  Results computed on
    plan ids map back through :meth:`to_original`.

    `epoch` counts applied `EdgeDelta`s along this plan's lineage
    (DESIGN.md §12): epoch 0 is a from-scratch build, and each
    :meth:`apply_delta` produces epoch+1 under a delta-chained cache key
    (`delta_cache_key`) — mutation never aliases the parent's entry.
    """
    g: Graph
    tiled: BlockTiledGraph
    key: str                           # content hash (the cache key)
    perm: Optional[np.ndarray] = None  # perm[plan_id] = original_id
    inv: Optional[np.ndarray] = None   # inv[original_id] = plan_id
    reorder: Optional[str] = None      # the reorder choice this plan was built with
    epoch: int = 0                     # deltas applied since the epoch-0 build
    hybrid: str = "off"                # tile-partition policy (DESIGN.md §16)
    hybrid_threshold: int = 0          # resolved nnz cut (0 iff hybrid == 'off')
    occupancy0: float = 0.0            # stored-tile density at the epoch-0
    #                                    build — the locality-decay baseline
    #                                    (DESIGN.md §17); 0.0 = unknown
    #                                    (directly-constructed plans)

    @property
    def n_nodes(self) -> int:
        return self.g.n_nodes

    @property
    def n_blocks(self) -> int:
        return self.tiled.n_block_rows

    @property
    def tile_size(self) -> int:
        return self.tiled.tile_size

    @property
    def storage(self) -> str:
        """Tile storage format this plan was built with (DESIGN.md §11)."""
        return self.tiled.storage

    @property
    def device_bytes(self) -> int:
        """Bytes of the device arrays the round program takes: the edge
        list and every array of the tiling (for a partitioned plan, the
        dense sub-tiling and the COO tail; no full tile list)."""
        return sum(
            int(x.size) * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves((self.g, self.tiled))
        )

    @functools.cached_property
    def graph_key(self) -> str:
        """Build-parameter-free content hash — the identity of the *graph*
        alone.  Per-request PRNG keys derive from this (not `key`, which
        bakes in tile_size/reorder/storage), so a member's priorities — and
        therefore its solution — are invariant across storage formats."""
        return graph_content_key(self.g)

    def to_original(self, x: np.ndarray) -> np.ndarray:
        """Map a per-vertex plan-id vector back to original vertex ids."""
        x = np.asarray(x)[: self.g.n_nodes]
        return x if self.inv is None else x[self.inv]

    def to_plan_ids(self, x: np.ndarray) -> np.ndarray:
        """Inverse of :meth:`to_original` (original-id vector → plan ids)."""
        x = np.asarray(x)[: self.g.n_nodes]
        return x if self.perm is None else x[self.perm]

    @classmethod
    def build(
        cls,
        graph: Union[Graph, "Plan"],
        *,
        tile_size: Optional[int] = None,
        reorder: Optional[str] = None,
        storage: str = "int8",
        hybrid: str = "off",
        hybrid_threshold: Optional[int] = None,
        cache: Optional["PlanCache"] = None,
    ) -> "Plan":
        """The front door: plan a graph, through a cache when one is given.

        `tile_size=None` applies the auto-T policy (`choose_tile_size`) —
        with or without a cache, so the same call plans the same graph
        identically either way (the cache's constructor `tile_size` is only
        the default of its own `plan()` method).  `storage` may be a
        concrete format or 'auto' (`resolve_storage`).  `hybrid` is the
        tile-partition policy (DESIGN.md §16); `hybrid_threshold=None`
        resolves to the analytic roofline cut (`resolve_hybrid_threshold`).
        A `Plan` passes through untouched — callers may hold either.
        """
        if isinstance(graph, Plan):
            return graph
        T = tile_size or choose_tile_size(graph.n_nodes, graph.n_edges)
        storage = resolve_storage(storage, graph.n_nodes, graph.n_edges, T)
        if cache is not None:
            return cache.plan(
                graph, tile_size=T, reorder=reorder, storage=storage,
                hybrid=hybrid, hybrid_threshold=hybrid_threshold,
            )[0]
        thr = 0 if hybrid == "off" else resolve_hybrid_threshold(
            T, storage, hybrid_threshold
        )
        key = plan_cache_key(graph, T, reorder, storage, hybrid, thr)
        return build_plan(
            graph, T, reorder, key, storage=storage,
            hybrid=hybrid, hybrid_threshold=thr,
        )

    def apply_delta(
        self, delta, *, cache: Optional["PlanCache"] = None
    ) -> "Plan":
        """Patch this plan with an `EdgeDelta` — tile-local, never a rebuild.

        The delta arrives in ORIGINAL vertex ids (the ids callers hold);
        RCM-reordered plans map it through their permutation first.  The
        patched plan keeps this plan's tile size, storage, reorder choice
        and permutation (the RCM ordering is NOT recomputed — locality can
        drift over many epochs; re-plan from scratch to re-anchor it) and
        carries `epoch + 1` under the delta-chained key.  An empty delta
        returns `self` unchanged — same key, same epoch — which is what
        keeps `repair="incremental"` bit-identical to cold on no-op
        updates.  With `cache`, the patch goes through
        :meth:`PlanCache.apply_delta` (memoised; stale pre-delta disk
        entries evicted).
        """
        if cache is not None:
            return cache.apply_delta(self, delta)[0]
        return patch_plan(self, delta)


# backwards-compatible spelling (`repro.serve_mis.planner.TilePlan`)
TilePlan = Plan


def graph_content_key(g: Graph) -> str:
    """Content hash of the graph ALONE — no build parameters.  The identity
    `request_key` derivations hang off (see `Plan.graph_key`): the same
    graph must draw the same priorities whatever tile size, reordering or
    storage format it was planned with."""
    h = hashlib.sha256()
    h.update(f"tcmis-graph|{g.n_nodes}".encode())
    h.update(np.asarray(g.senders)[: g.n_edges].astype(np.int32).tobytes())
    h.update(np.asarray(g.receivers)[: g.n_edges].astype(np.int32).tobytes())
    return h.hexdigest()


def plan_cache_key(
    g: Graph,
    tile_size: int,
    reorder: Optional[str],
    storage: str = "int8",
    hybrid: str = "off",
    hybrid_threshold: int = 0,
) -> str:
    """Content hash of (canonical edges, n_nodes, build params).

    `from_edges` already canonicalises (dedupe, both directions, sender-sorted),
    so any two loads of the same graph — different files, different formats,
    shuffled edge order — hash identically.  `storage` is a build param:
    int8 and bitpack plans of one graph are distinct cache entries.  So is
    the hybrid-partition policy — but ONLY when it is on: 'off' contributes
    nothing to the key, so hybrid-free keys (and their disk paths) are
    byte-identical to the v2 derivation and old entries retire through the
    in-place version check rather than orphaning.
    """
    h = hashlib.sha256()
    # no version in the key: a format bump must hit the SAME file so the
    # meta check in `PlanCache._load` can detect + evict the stale layout
    tail = "" if hybrid == "off" else f"|h{hybrid}:{int(hybrid_threshold)}"
    h.update(
        f"tcmis-plan|{g.n_nodes}|{tile_size}|{reorder or ''}|{storage}"
        f"{tail}".encode()
    )
    h.update(np.asarray(g.senders)[: g.n_edges].astype(np.int32).tobytes())
    h.update(np.asarray(g.receivers)[: g.n_edges].astype(np.int32).tobytes())
    return h.hexdigest()


def _legacy_v1_cache_key(g: Graph, tile_size: int, reorder: Optional[str]) -> str:
    """The pre-storage-axis (v1) key derivation — kept ONLY so the cache can
    find and evict v1 disk entries, which live at different paths because
    `storage` joined the key string in v2."""
    h = hashlib.sha256()
    h.update(f"tcmis-plan-v1|{g.n_nodes}|{tile_size}|{reorder or ''}".encode())
    h.update(np.asarray(g.senders)[: g.n_edges].astype(np.int32).tobytes())
    h.update(np.asarray(g.receivers)[: g.n_edges].astype(np.int32).tobytes())
    return h.hexdigest()


def delta_cache_key(parent_key: str, delta_content_key: str) -> str:
    """Cache key of a patched plan: sha256 chained over the parent plan's
    key and the delta's content hash (`EdgeDelta.content_key`).  Chaining —
    rather than re-hashing the mutated edge list — makes patching O(delta)
    and names the *lineage*: the same graph state reached through a
    different delta history keys differently, which is deliberate (the
    entry records how the tiling was patched, and epochs retire in lineage
    order)."""
    h = hashlib.sha256()
    h.update(f"tcmis-plan-delta|{parent_key}|{delta_content_key}".encode())
    return h.hexdigest()


def patch_plan(plan: Plan, delta) -> Plan:
    """The uncached patch path: map, mutate both representations, re-key.

    Graph-level strictness (`apply_graph_delta` raises on absent removes /
    present adds) runs FIRST, so the tile edit — which trusts its input —
    only ever sees a validated batch.
    """
    from repro.dyngraph import drift
    from repro.dyngraph.retile import apply_delta as apply_tiled_delta
    from repro.dyngraph.retile import apply_graph_delta

    if delta.is_empty:
        return plan
    mapped = delta if plan.inv is None else delta.mapped(plan.inv)
    g2 = apply_graph_delta(plan.g, mapped)
    tiled2 = apply_tiled_delta(plan.tiled, mapped)
    part = tiled2.partition
    # drift telemetry (DESIGN.md §17): this is the ONE funnel every actual
    # patch event passes through — cache mem/disk hits replay a patch that
    # was recorded when it happened, so each epoch counts exactly once.
    # Eager seam, observability only: never raise into the patch path.
    try:
        drift.note_drift(
            epoch=plan.epoch + 1,
            touched_tiles=drift.touched_tile_count(
                mapped, plan.tiled.tile_size, plan.tiled.n_block_cols
            ),
            n_tiles=tiled2.n_tiles,
            dirty_frac=drift.dirty_vertex_frac(mapped, plan.g.n_nodes),
            occupancy=drift.tile_occupancy(
                g2.n_edges, tiled2.n_tiles, tiled2.tile_size
            ),
            occupancy0=plan.occupancy0,
        )
    except Exception:  # noqa: BLE001
        pass
    if plan.hybrid == "auto":
        # `apply_tiled_delta` reclassifies an existing partition in place,
        # but only the PLAN knows the auto policy: a delta can push the
        # graph across the auto gate in either direction, so re-run it
        # (forced/off plans need nothing — present stays present, absent
        # stays absent).  A partition carries its own tile counts.
        if part is None:
            tiled2 = attach_partition(
                tiled2, mode="auto", threshold=plan.hybrid_threshold)
        elif not partition_pays("auto", tiled2.n_tiles, part.n_sparse_tiles):
            tiled2 = full_tiling(tiled2)
    return dataclasses.replace(
        plan,
        g=g2,
        tiled=tiled2,
        key=delta_cache_key(plan.key, delta.content_key),
        epoch=plan.epoch + 1,
    )


def build_plan(
    g: Graph,
    tile_size: int,
    reorder: Optional[str],
    key: str,
    storage: str = "int8",
    hybrid: str = "off",
    hybrid_threshold: int = 0,
    trace: Optional[Trace] = None,
) -> Plan:
    """The cache-miss path: (optional) RCM + BSR tiling + (optional) tile
    partition, no caching.  `hybrid_threshold` arrives already resolved
    (`resolve_hybrid_threshold`) — this function never invents policy.

    With the hybrid policy on, the build is sized by edges: the nonzero
    cells and per-tile counts come first (`TileCells`), then only the
    tiles at or above the threshold are packed, under `plan.tiles`, and
    the rest become the COO tail straight from their cells, under
    `plan.tail` — no full tile list is built.  Where 'auto' declines, the
    same cells give the full tiling, as `build_block_tiles` would."""
    perm = inv = None
    if reorder == "rcm":
        perm = np.asarray(rcm_ordering(g))
        inv = np.empty_like(perm)
        inv[perm] = np.arange(g.n_nodes)
        s = np.asarray(g.senders)[: g.n_edges]
        r = np.asarray(g.receivers)[: g.n_edges]
        g = from_edges(inv[s], inv[r], g.n_nodes)
    elif reorder is not None:
        raise ValueError(f"unknown reorder {reorder!r} (None or 'rcm')")
    thr = int(hybrid_threshold)
    dense = None
    with trace_span(trace, "plan.tiles"):
        if hybrid == "off":
            tiled = build_block_tiles(g, tile_size=tile_size, storage=storage)
        else:
            cells = TileCells.from_edges(
                np.asarray(g.senders)[: g.n_edges],
                np.asarray(g.receivers)[: g.n_edges],
                tile_size, -(-g.n_nodes // int(tile_size)),
            )
            dense = cells.counts >= thr
            n_sparse = cells.n_tiles - int(np.count_nonzero(dense))
            if not partition_pays(hybrid, cells.n_tiles, n_sparse):
                dense = None
            tiled = tiles_from_cells(cells, g.n_nodes, storage, select=dense)
    if dense is not None:
        with trace_span(trace, "plan.tail"):
            tiled = partitioned_tiling(
                tiled, coo_tail(cells, dense, tiled.n_padded), thr, n_sparse)
    from repro.dyngraph.drift import tile_occupancy

    return Plan(g=g, tiled=tiled, key=key, perm=perm, inv=inv,
                reorder=reorder, hybrid=hybrid,
                hybrid_threshold=int(hybrid_threshold),
                occupancy0=tile_occupancy(
                    g.n_edges, tiled.n_tiles, tile_size))


class PlanCache:
    """Two-layer (memory + optional disk) content-addressed plan store.

    The memory layer is a bounded LRU (`max_mem_entries`) — a long-running
    service must not pin every graph it has ever seen (tiles are the big
    arrays) in host/device memory.  The disk layer is unbounded by design:
    content-addressed `.npz` files are cheap, shared between processes, and
    an operator concern to garbage-collect.

    `tile_size`/`reorder`/`storage` given at construction are defaults;
    `plan` accepts per-call overrides (the `Solver`'s auto policies pick
    per-graph values), and the cache key includes all of them, so entries
    never collide across builds.  Disk entries carry the cache-format
    version (`_PLAN_VERSION`); entries written by an older format — e.g.
    pre-storage-axis v1 files — are detected on load, evicted with a
    warning, and rebuilt rather than mis-read.
    """

    def __init__(
        self,
        tile_size: int = 32,
        reorder: Optional[str] = None,
        cache_dir: Optional[str] = None,
        max_mem_entries: int = 256,
        storage: str = "int8",
        hybrid: str = "off",
        hybrid_threshold: Optional[int] = None,
    ):
        self.tile_size = int(tile_size)
        self.reorder = reorder
        self.storage = storage
        self.hybrid = hybrid
        self.hybrid_threshold = hybrid_threshold
        self.cache_dir = cache_dir
        self.max_mem_entries = max(int(max_mem_entries), 1)
        self._mem: "OrderedDict[str, Plan]" = OrderedDict()
        # per-instance metrics registry (repro.obs); the legacy `stats` dict
        # survives as a read-only property view below
        self.metrics = MetricsRegistry("plan_cache")
        for k in _PLAN_STAT_KEYS:
            self.metrics.counter(f"plan_cache.{k}")
        if cache_dir:
            os.makedirs(cache_dir, exist_ok=True)

    @property
    def stats(self) -> dict:
        """Read-only `{mem_hits, disk_hits, misses, evicted_stale}` view in
        the legacy spelling; mutation goes through `self.metrics`."""
        return {
            k: self.metrics.counter(f"plan_cache.{k}").value
            for k in _PLAN_STAT_KEYS
        }

    def _count(self, key: str) -> None:
        self.metrics.counter(f"plan_cache.{key}").inc()

    def _remember(self, key: str, plan: Plan) -> None:
        self._mem[key] = plan
        self._mem.move_to_end(key)
        while len(self._mem) > self.max_mem_entries:
            self._mem.popitem(last=False)

    def plan(
        self,
        g: Graph,
        *,
        tile_size: Optional[int] = None,
        reorder: Optional[str] = None,
        storage: Optional[str] = None,
        hybrid: Optional[str] = None,
        hybrid_threshold: Optional[int] = None,
        trace: Optional[Trace] = None,
    ) -> Tuple[Plan, str]:
        """Return (plan, status) with status ∈ {'mem', 'disk', 'built'}.

        A build (status 'built') records its stages into `trace`:
        `plan.key` (the content hash), `plan.tiles`, `plan.tail` (hybrid
        plans); a hit records none, its cost being the hash alone."""
        T = self.tile_size if tile_size is None else int(tile_size)
        ro = self.reorder if reorder is None else reorder
        st = resolve_storage(
            self.storage if storage is None else storage,
            g.n_nodes, g.n_edges, T,
        )
        hy = self.hybrid if hybrid is None else hybrid
        thr = 0 if hy == "off" else resolve_hybrid_threshold(
            T, st,
            self.hybrid_threshold if hybrid_threshold is None
            else hybrid_threshold,
        )
        t_key = time.perf_counter()
        with trace_span(None, "plan.key"):   # into `trace` on a miss only
            key = plan_cache_key(g, T, ro, st, hy, thr)
        key_end = time.perf_counter()
        hit = self._mem.get(key)
        if hit is not None:
            self._count("mem_hits")
            self._mem.move_to_end(key)
            return hit, "mem"
        if self.cache_dir:
            loaded = self._load(key, ro)
            if loaded is not None:
                self._count("disk_hits")
                self._remember(key, loaded)
                return loaded, "disk"
            # disk miss: a v1 entry for this graph (pre-storage-axis key)
            # may still sit at its legacy path — evict it so upgrades
            # clean up rather than orphan old-format files
            legacy = self._path(_legacy_v1_cache_key(g, T, ro))
            if os.path.exists(legacy):
                self._evict_stale(legacy, "pre-storage-axis entry (v1 key)")
            if hy != "off":
                # hybrid keys moved off the v2 paths — a pre-hybrid entry
                # for this graph sits at the hybrid-free key.  Evict it only
                # if it really is old-format: the same path is a LIVE v3
                # entry for hybrid='off' requests.
                self._evict_legacy_version(
                    self._path(plan_cache_key(g, T, ro, st))
                )
        self._count("misses")
        if trace is not None:
            trace.note("plan.key", (key_end - t_key) * 1e3, end=key_end)
        plan = build_plan(
            g, T, ro, key, storage=st, hybrid=hy, hybrid_threshold=thr,
            trace=trace,
        )
        self._remember(key, plan)
        if self.cache_dir:
            self._store(plan)
        return plan, "built"

    def _evict_legacy_version(self, path: str) -> None:
        """Evict the entry at `path` iff it predates the current format —
        used for probing legacy key locations that may also hold live
        current-format entries (never evict those)."""
        if not os.path.exists(path):
            return
        try:
            with np.load(path) as z:
                meta = z["meta"]
                version = int(meta[6]) if meta.shape[0] > 6 else 1
        except Exception:  # noqa: BLE001 — torn/unreadable: treat as stale
            version = 0
        if version != _PLAN_VERSION:
            self._evict_stale(path, f"pre-hybrid entry (format v{version})")

    def apply_delta(self, plan: Plan, delta) -> Tuple[Plan, str]:
        """Patch a plan through the cache: return (patched, status) with
        status ∈ {'mem', 'disk', 'built'} — 'built' here means *patched*,
        the tile-local `patch_plan`, never a from-scratch rebuild.

        The patched entry persists under the current (v2) npz format at its
        delta-chained key; the parent's now-stale pre-delta entry is then
        retired exactly like PR 4's v1-format entries — detected, warned
        about once, unlinked, and counted in `stats.evicted_stale` — so a
        mutating graph's lineage keeps ONE live disk entry instead of
        accreting an epoch per delta.  (A re-request of the pre-delta
        content simply rebuilds: for a graph that mutates between
        requests, the superseded epoch is the stale layout, the same way
        a superseded format version was.)
        """
        if delta.is_empty:
            return plan, "mem"
        key = delta_cache_key(plan.key, delta.content_key)
        hit = self._mem.get(key)
        if hit is not None:
            self._count("mem_hits")
            self._mem.move_to_end(key)
            return hit, "mem"
        if self.cache_dir:
            loaded = self._load(key, plan.reorder)
            if loaded is not None:
                self._count("disk_hits")
                self._remember(key, loaded)
                self._retire_parent(plan)
                return loaded, "disk"
        self._count("misses")
        patched = patch_plan(plan, delta)
        self._remember(patched.key, patched)
        if self.cache_dir:
            self._store(patched)
            self._retire_parent(plan)
        return patched, "built"

    def _retire_parent(self, parent: Plan) -> None:
        """Unlink the superseded pre-delta disk entry and drop its memory
        copy — the epoch analogue of the v1-format eviction."""
        path = self._path(parent.key)
        if os.path.exists(path):
            self._evict_stale(
                path, f"pre-delta entry (epoch {parent.epoch} superseded)"
            )
        self._mem.pop(parent.key, None)

    # -- disk layer --------------------------------------------------------

    def _path(self, key: str) -> str:
        return os.path.join(self.cache_dir, f"{key}.npz")

    def _store(self, plan: Plan) -> None:
        g, t = plan.g, plan.tiled
        part = t.partition
        # tiles persist AS STORED — a bitpack plan's disk entry is the same
        # 8× smaller than its int8 twin as its HBM copy; a partitioned
        # plan's tile arrays are its dense sub-tiling's
        d = t if part is None else part.dense
        arrays = dict(
            senders=np.asarray(g.senders)[: g.n_edges],
            receivers=np.asarray(g.receivers)[: g.n_edges],
            tiles=np.asarray(d.tiles),
            tile_rows=np.asarray(d.tile_rows),
            tile_cols=np.asarray(d.tile_cols),
            row_starts=np.asarray(d.row_starts),
            meta=np.asarray(
                [g.n_nodes, g.n_edges, t.n_tiles, t.tile_size,
                 t.n_block_rows, t.n_block_cols,
                 _PLAN_VERSION, TILE_STORAGES.index(t.storage),
                 HYBRID_MODES.index(plan.hybrid), plan.hybrid_threshold,
                 -1 if part is None else part.n_dense_tiles,
                 0 if part is None else part.sp_nnz],
                dtype=np.int64,
            ),
        )
        if part is not None:
            arrays["sp_rows"] = np.asarray(part.sp_rows)
            arrays["sp_cols"] = np.asarray(part.sp_cols)
        if plan.perm is not None:
            arrays["perm"] = plan.perm
        if plan.epoch:
            # optional tail record, like `perm`: patched plans stay within
            # the v2 layout (the 8-int meta is untouched), readers without
            # the field default to epoch 0
            arrays["epoch"] = np.asarray([plan.epoch], dtype=np.int64)
        # write under a per-writer temp name, publish atomically: concurrent
        # workers that both miss on one key each write their own temp file
        # and the last rename wins with identical content
        tmp = self._path(plan.key) + f".tmp.{os.getpid()}.{uuid.uuid4().hex[:8]}"
        try:
            with open(tmp, "wb") as f:
                np.savez(f, **arrays)
            os.replace(tmp, self._path(plan.key))
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)

    def _evict_stale(self, path: str, found: str) -> None:
        """Old-format disk entry: warn (one line), delete, let the caller
        rebuild — a stale layout must never be mis-read as current."""
        self._count("evicted_stale")
        warnings.warn(
            f"evicting stale plan-cache entry {os.path.basename(path)}: "
            f"{found}, current format v{_PLAN_VERSION} — rebuilding",
            stacklevel=3,
        )
        try:
            os.unlink(path)
        except OSError:
            pass

    def _load(self, key: str, reorder: Optional[str]) -> Optional[Plan]:
        path = self._path(key)
        if not os.path.exists(path):
            return None
        try:
            with np.load(path) as z:
                meta = z["meta"]
                if meta.shape[0] <= 6:
                    self._evict_stale(path, "pre-versioned entry (v1 layout)")
                    return None
                if int(meta[6]) != _PLAN_VERSION:
                    self._evict_stale(path, f"format v{int(meta[6])}")
                    return None
                n_nodes, n_edges, n_tiles, tile_size, nbr, nbc = (
                    int(v) for v in meta[:6]
                )
                storage = TILE_STORAGES[int(meta[7])]
                hybrid = HYBRID_MODES[int(meta[8])]
                hybrid_threshold = int(meta[9])
                n_dense, sp_nnz = int(meta[10]), int(meta[11])
                g = Graph(
                    senders=jnp.asarray(z["senders"]),
                    receivers=jnp.asarray(z["receivers"]),
                    n_nodes=n_nodes,
                    n_edges=n_edges,
                )
                tiled = BlockTiledGraph(
                    tiles=jnp.asarray(z["tiles"]),
                    tile_rows=jnp.asarray(z["tile_rows"]),
                    tile_cols=jnp.asarray(z["tile_cols"]),
                    row_starts=jnp.asarray(z["row_starts"]),
                    n_tiles=n_tiles if n_dense < 0 else n_dense,
                    n_nodes=n_nodes,
                    tile_size=tile_size,
                    n_block_rows=nbr,
                    n_block_cols=nbc,
                    storage=storage,
                )
                if n_dense >= 0:
                    tiled = partitioned_tiling(
                        tiled, (z["sp_rows"], z["sp_cols"], sp_nnz),
                        hybrid_threshold, n_tiles - n_dense,
                    )
                perm = np.asarray(z["perm"]) if "perm" in z.files else None
                epoch = int(z["epoch"][0]) if "epoch" in z.files else 0
            inv = None
            if perm is not None:
                inv = np.empty_like(perm)
                inv[perm] = np.arange(n_nodes)
            from repro.dyngraph.drift import tile_occupancy

            # occupancy0 is not persisted (the npz layout is frozen at v4):
            # a disk-loaded plan re-baselines locality decay at its load
            # state — exact for epoch-0 entries, a documented reset for
            # patched lineages (DESIGN.md §17)
            return Plan(g=g, tiled=tiled, key=key, perm=perm, inv=inv,
                        reorder=reorder, epoch=epoch, hybrid=hybrid,
                        hybrid_threshold=hybrid_threshold,
                        occupancy0=tile_occupancy(
                            n_edges, n_tiles, tile_size))
        except Exception:  # noqa: BLE001 — np.load raises BadZipFile/EOFError/
            return None    # pickle errors on torn files: any failure ⇒ rebuild
