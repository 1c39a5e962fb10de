"""Block-diagonal multi-graph packing: one engine dispatch, many graphs.

Small-graph MIS requests are latency-dominated by dispatch, not compute, so
the service amortises ONE jitted `tc_mis` invocation over a whole batch.
The packing is block-diagonal BSR concatenation of cached `TilePlan`s:

* every member graph's vertex range is padded up to a whole number of
  `T`-sized blocks before it is offset, so **no tile ever spans two
  graphs** — the batch adjacency is exactly block-diagonal and each
  member's neighbourhood structure is untouched;
* priorities are computed **per member** from its own key and degree
  statistics (Eq. 1's d̄ is a per-graph mean), at the member's offset.
  Zero cross-graph edges + per-graph priorities ⇒ each slot's round
  dynamics are bit-identical to a solo `tc_mis` run of that member, so
  the packed solve provably returns every member's solo MIS;
* padding-slot vertices start **dead** (`alive0`) — they never join the
  set, never cost a round — and the static `col_gate` pins their block
  columns inactive for the engine's empty-C tile skip (core.engine);
* batch shapes are rounded up to **buckets** (powers of two over the
  block, tile and edge counts), so request mixes of many sizes land on a
  bounded set of compiled programs.  `Graph.n_edges` and
  `BlockTiledGraph.n_tiles` are jit-STATIC pytree fields, so the packed
  containers declare the *bucket* counts, not the real ones — otherwise
  every distinct batch composition would be a fresh XLA compile and the
  bucket would bound nothing.  That makes every static field a pure
  function of the bucket.  It is sound because the padding is inert in
  every op the batch reaches: sentinel edges scatter into the dropped
  dummy segment row, and padding tiles are all-zero and pinned to the
  last real block-row (the same convention `build_block_tiles` uses).
  The real counts live in `PackedBatch.n_real_edges` / `n_real_tiles`.
  Corollary: never run edge-mask consumers that enumerate "real" edges
  (`build_csr`, `to_networkx`, `is_valid_mis`) on `batch.g` — validate
  per member on its plan graph, as the service does.

Tile lists concatenate from the plan cache — a batch offsets its members'
cached tiles (or their hybrid partitions); it re-tiles only a partitioned
member of a pack that cannot stay hybrid, whose full list it rebuilds.

The pack is in two parts (DESIGN.md §19).  `pack_batch` builds the
key-independent structure — graph, tiling, `alive0`, `col_gate`, offsets,
and the `MemberSlots` the packed program builds H3 priorities from — which
`repro.api.Solver` caches per set of member plans, so a restart under
fresh keys rebuilds and copies none of it.  `PackedBatch.priorities`
gives a call's priorities: H3's from the stacked member keys by the same
function the packed program runs (`core.heuristics.PACKED`), the other
heuristics' member by member on the host.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.heuristics import (
    PACKED,
    MemberSlots,
    Priorities,
    make_priorities,
)
from repro.core.spmv import _NEG
from repro.core.tiling import (
    BlockTiledGraph,
    full_tiling,
    next_pow2,
    packed_words,
    padded_tile_count,
    partitioned_tiling,
)
from repro.graphs.graph import Graph
# module-level code with no layer instance to own metrics records into the
# process-wide registry (repro.obs; DESIGN.md §14)
from repro.obs import metrics as obs_metrics
from repro.serve_mis.planner import TilePlan


class Bucket(NamedTuple):
    """Static shape class of a packed batch — the jit-compilation key."""
    tile_size: int
    n_blocks: int      # total block rows/cols (incl. empty trailing slots)
    n_tiles_pad: int   # padded stored-tile count
    e_pad: int         # padded half-edge count
    storage: str = "int8"   # tile storage format (members must agree)
    n_members: int = 2      # padded member count: the stacked keys' length


def bucket_for(plans: Sequence[TilePlan], tile_size: int) -> Bucket:
    """Smallest bucket that fits `plans`: pow2 rounding bounds the number of
    distinct compiled programs to O(log max_size) per dimension."""
    blocks = sum(p.n_blocks for p in plans)
    tiles = sum(p.tiled.n_tiles for p in plans)
    edges = sum(p.g.n_edges for p in plans)
    return Bucket(
        tile_size=int(tile_size),
        n_blocks=next_pow2(max(blocks, 1)),
        n_tiles_pad=next_pow2(max(tiles, 8)),
        e_pad=next_pow2(max(edges, 8)),
        storage=plans[0].tiled.storage if plans else "int8",
        n_members=next_pow2(max(len(plans), 2)),
    )


def request_key(base_key: jax.Array, plan: TilePlan) -> jax.Array:
    """Per-graph PRNG key, derived from graph *content* so the priorities a
    member gets do not depend on its batch, slot, or arrival order — the
    property that makes packed results reproducible against solo runs.

    Derived from `plan.graph_key` — the build-parameter-free hash — NOT the
    cache key, so the same graph draws the same priorities in either tile
    storage format (the int8-vs-bitpack bit-parity contract)."""
    return jax.random.fold_in(base_key, int(plan.graph_key[:8], 16) & 0x7FFFFFFF)


# host-side (select, resolve) per plan content hash — see
# `PackedBatch.priorities`.  Bounded FIFO: priority vectors are small next
# to plans, but a production stream of distinct graphs must not grow host
# memory without limit.
PriorityCache = Dict[str, Tuple[np.ndarray, Optional[np.ndarray]]]
PRIORITY_CACHE_CAP = 4096


def _member_priorities(
    plan: TilePlan,
    key: jax.Array,
    heuristic: str,
    cache: Optional[PriorityCache],
) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Priorities for one member, as host arrays ready to place in a slot
    (the heuristics outside `core.heuristics.PACKED`).

    Priorities are a pure function of (plan content, heuristic, key), and
    with `request_key` the key itself is content-derived — so a warm-path
    batch of already-seen graphs skips the per-member `degrees()` dispatch
    and priority construction entirely via `cache` (keyed by plan content
    hash; callers mixing base keys or heuristics must use separate caches,
    as `Solver` does by owning one cache per solver).
    """
    if cache is not None and plan.key in cache:
        obs_metrics.counter("batcher.priority_cache.hits").inc()
        return cache[plan.key]
    if cache is not None:
        obs_metrics.counter("batcher.priority_cache.misses").inc()
    pri = make_priorities(heuristic, key, plan.n_nodes, plan.g.degrees())
    entry = (
        np.asarray(pri.select),
        None if pri.resolve is None else np.asarray(pri.resolve),
    )
    if cache is not None:
        cache[plan.key] = entry
        while len(cache) > PRIORITY_CACHE_CAP:
            del cache[next(iter(cache))]  # FIFO eviction (dicts keep order)
    return entry


def stack_keys(keys, n_members: int) -> jax.Array:
    """The members' keys as one (n_members,) key array, padded with the
    first key: a stacked key array passes through without a dispatch per
    member."""
    if not isinstance(keys, jax.Array):
        keys = jnp.stack(list(keys))
    if keys.shape[0] < n_members:
        keys = jnp.concatenate(
            [keys, jnp.broadcast_to(keys[:1], (n_members - keys.shape[0],))])
    return keys


_packed_jit = {name: jax.jit(fn) for name, fn in PACKED.items()}


@dataclasses.dataclass(frozen=True)
class PackedBatch:
    """The key-independent part of a block-diagonal batch, ready for any
    number of `tc_mis` dispatches under fresh keys."""
    g: Graph                    # block-diagonal graph, n_nodes = n_blocks*T
    tiled: BlockTiledGraph
    alive0: jnp.ndarray         # (n_nodes,) bool, False in padding slots
    col_gate: jnp.ndarray       # (n_blocks,) int32 real-vertex occupancy
    slots: MemberSlots          # per-slot member, local id and degree
    offsets: Tuple[int, ...]    # member vertex offsets (multiples of T)
    sizes: Tuple[int, ...]      # member real vertex counts
    bucket: Bucket
    n_real_edges: int = 0       # g/tiled declare BUCKET counts (static jit
    n_real_tiles: int = 0       # keys); the real totals live here

    @property
    def n_graphs(self) -> int:
        return len(self.sizes)

    @property
    def device_bytes(self) -> int:
        """Bytes of the device arrays the structure holds."""
        return sum(
            int(x.size) * x.dtype.itemsize for x in jax.tree_util.tree_leaves(
                (self.g, self.tiled, self.alive0, self.col_gate, self.slots))
        )

    @property
    def edge_fill(self) -> float:
        """Real over padded half-edges: the share of the edge stream that
        is work."""
        return self.n_real_edges / self.bucket.e_pad

    @property
    def tile_fill(self) -> float:
        """Real over declared (padded) tiles."""
        return self.n_real_tiles / self.bucket.n_tiles_pad

    def signature(self) -> str:
        """Shape-class id: batches with equal signatures reuse one compile.

        A hybrid-partitioned batch carries the partition's static shapes
        (threshold + both padded compacted-list sizes): the partition is a
        pytree child of the tiling, so these are jit keys — two batches
        differing only there must not claim one compiled program.  The
        storage stays the terminal component (callers key on it)."""
        b = self.bucket
        part = self.tiled.partition
        hy = "" if part is None else (
            f".h{part.threshold}:{int(part.dense.tiles.shape[0])}"
            f":{int(part.sp_rows.shape[0])}"
        )
        return (
            f"T{b.tile_size}.b{b.n_blocks}.t{b.n_tiles_pad}.e{b.e_pad}"
            f".m{b.n_members}{hy}.{b.storage}"
        )

    def priorities(
        self,
        plans: Sequence[TilePlan],
        keys: Sequence[jax.Array],
        heuristic: str,
        *,
        priority_cache: Optional[PriorityCache] = None,
    ) -> Priorities:
        """Every member's priorities under its key, at its offset, `_NEG`
        in padding slots.  H3 (`core.heuristics.PACKED`) builds them on the
        device from the stacked keys, as the packed program does; the
        others member by member on the host, through `priority_cache`."""
        if len(keys) != self.n_graphs:
            raise ValueError(f"{self.n_graphs} members but {len(keys)} keys")
        if heuristic in _packed_jit:
            return _packed_jit[heuristic](
                stack_keys(keys, self.bucket.n_members), self.slots)
        pris = [_member_priorities(p, k, heuristic, priority_cache)
                for p, k in zip(plans, keys)]
        neg = int(_NEG)
        sel = np.full(self.g.n_nodes, neg, dtype=np.int32)
        res = None if pris[0][1] is None else np.full_like(sel, neg)
        for off, (s, r) in zip(self.offsets, pris):
            sel[off : off + s.shape[0]] = s
            if res is not None:
                res[off : off + r.shape[0]] = r
        return Priorities(
            select=jnp.asarray(sel),
            resolve=None if res is None else jnp.asarray(res),
        )

    def unpack(self, x) -> List[np.ndarray]:
        """Slice a packed per-vertex vector into per-member vectors (plan ids)."""
        x = np.asarray(x)
        return [x[off : off + n] for off, n in zip(self.offsets, self.sizes)]


def pack_batch(
    plans: Sequence[TilePlan],
    *,
    bucket: Optional[Bucket] = None,
) -> PackedBatch:
    """Concatenate cached per-graph plans into one block-diagonal batch
    structure (no priorities: `PackedBatch.priorities`)."""
    if not plans:
        raise ValueError("pack_batch needs at least one plan")
    T = plans[0].tiled.tile_size
    if any(p.tiled.tile_size != T for p in plans):
        raise ValueError("all plans in a batch must share tile_size")
    storage = plans[0].tiled.storage
    if any(p.tiled.storage != storage for p in plans):
        raise ValueError("all plans in a batch must share tile storage")
    if bucket is None:
        bucket = bucket_for(plans, T)
    need = bucket_for(plans, T)
    if (need.n_blocks > bucket.n_blocks or need.n_tiles_pad > bucket.n_tiles_pad
            or need.e_pad > bucket.e_pad or bucket.tile_size != T
            or bucket.storage != storage
            or need.n_members > bucket.n_members):
        raise ValueError(f"batch needs {need}, bucket {bucket} too small")

    n_total = bucket.n_blocks * T

    offsets: List[int] = []
    sizes: List[int] = []
    alive0 = np.zeros(n_total, dtype=bool)
    col_gate = np.zeros(bucket.n_blocks, dtype=np.int32)
    member = np.full(n_total, -1, dtype=np.int32)
    local = np.zeros(n_total, dtype=np.int32)

    # Hybrid routing survives batching only when it is coherent across the
    # whole pack: every member partitioned, all at one threshold.  Then the
    # members' dense sub-tilings and COO tails concatenate (offset like
    # everything else) — the same partition a from-scratch plan of the
    # packed graph would get, since both lists are in block order.  Any
    # other pack runs dense-only on the members' full tile lists.
    parts = [p.tiled.partition for p in plans]
    thr = None if parts[0] is None else parts[0].threshold
    hybrid = thr is not None and all(
        pt is not None and pt.threshold == thr for pt in parts)

    src_parts: List[np.ndarray] = []
    dst_parts: List[np.ndarray] = []
    tile_parts: List[np.ndarray] = []
    row_parts: List[np.ndarray] = []
    col_parts: List[np.ndarray] = []
    sp_parts: List[Tuple[np.ndarray, np.ndarray]] = []

    boff = 0
    for j, plan in enumerate(plans):
        g, t = plan.g, plan.tiled
        voff = boff * T
        offsets.append(voff)
        sizes.append(g.n_nodes)

        alive0[voff : voff + g.n_nodes] = True
        member[voff : voff + g.n_nodes] = j
        local[voff : voff + g.n_nodes] = np.arange(g.n_nodes, dtype=np.int32)
        col_gate[boff : boff + plan.n_blocks] = 1

        src_parts.append(np.asarray(g.senders)[: g.n_edges].astype(np.int64) + voff)
        dst_parts.append(np.asarray(g.receivers)[: g.n_edges].astype(np.int64) + voff)
        if hybrid:
            pt = t.partition
            sp_parts.append((np.asarray(pt.sp_rows)[: pt.sp_nnz] + voff,
                             np.asarray(pt.sp_cols)[: pt.sp_nnz] + voff))
            t = pt.dense
        else:
            t = full_tiling(t)
        if t.n_tiles:
            tile_parts.append(np.asarray(t.tiles)[: t.n_tiles])
            row_parts.append(np.asarray(t.tile_rows)[: t.n_tiles] + boff)
            col_parts.append(np.asarray(t.tile_cols)[: t.n_tiles] + boff)
        boff += plan.n_blocks

    # -- edges: concat + sentinel pad to the bucket's static e_pad.  The
    # Graph DECLARES n_edges = e_pad (see module docstring): n_edges is a
    # static jit key, and sentinel half-edges are inert in the segment ops
    # (their contributions land in the dropped dummy segment row).
    s = np.concatenate(src_parts) if src_parts else np.zeros(0, np.int64)
    r = np.concatenate(dst_parts) if dst_parts else np.zeros(0, np.int64)
    n_real_edges = int(s.shape[0])
    # each slot's degree in its member graph (`Graph.degrees`): the
    # block-diagonal packing keeps every member's half-edges, offset
    deg = np.bincount(r, minlength=n_total).astype(np.int32)
    pad = np.full(bucket.e_pad - n_real_edges, n_total, dtype=np.int64)
    batch_g = Graph(
        senders=jnp.asarray(np.concatenate([s, pad]).astype(np.int32)),
        receivers=jnp.asarray(np.concatenate([r, pad]).astype(np.int32)),
        n_nodes=n_total,
        n_edges=bucket.e_pad,
    )

    # -- tiles: concat + zero-tile pad pinned to the last real block-row.
    # Block-diagonal concatenation is storage-agnostic: packed members
    # concatenate their (nt, T, W) uint32 words exactly like int8 tiles,
    # and all-zero packed padding tiles are equally inert.
    if storage == "bitpack":
        empty_shape, tile_dtype = (0, T, packed_words(T)), np.uint32
    else:
        empty_shape, tile_dtype = (0, T, T), np.int8
    if tile_parts:
        tiles = np.concatenate(tile_parts)
        rows = np.concatenate(row_parts).astype(np.int32)
        cols = np.concatenate(col_parts).astype(np.int32)
    else:
        tiles = np.zeros(empty_shape, dtype=tile_dtype)
        rows = np.zeros(0, dtype=np.int32)
        cols = np.zeros(0, dtype=np.int32)
    n_real_tiles = int(tiles.shape[0])
    n_stored = padded_tile_count(n_real_tiles) if hybrid else bucket.n_tiles_pad
    n_pad_tiles = n_stored - n_real_tiles
    last_row = np.int32(rows[-1]) if n_real_tiles else np.int32(0)
    tiles = np.concatenate(
        [tiles, np.zeros((n_pad_tiles,) + tiles.shape[1:], tiles.dtype)]
    )
    rows = np.concatenate([rows, np.full(n_pad_tiles, last_row, np.int32)])
    cols = np.concatenate([cols, np.zeros(n_pad_tiles, np.int32)])

    counts = np.bincount(rows[:n_real_tiles], minlength=bucket.n_blocks)
    row_starts = np.zeros(bucket.n_blocks + 1, dtype=np.int32)
    np.cumsum(counts, out=row_starts[1:])

    # n_tiles DECLARES the bucket count (static jit key; see docstring).
    # All-zero padding tiles pinned to the last real block-row accumulate
    # nothing, and counting them "covered" only routes that row through the
    # kernel epilogue it already takes (zero real tiles ⇒ the zero tile
    # computes exactly the trivial n_c=0 rule the wrapper would patch in).
    # A hybrid pack's tiles are its dense sub-tiling, padded as a
    # partition's; the declared count moves to the tiling around it.
    batch_tiled = BlockTiledGraph(
        tiles=jnp.asarray(tiles),
        tile_rows=jnp.asarray(rows),
        tile_cols=jnp.asarray(cols),
        row_starts=jnp.asarray(row_starts),
        n_tiles=n_real_tiles if hybrid else bucket.n_tiles_pad,
        n_nodes=n_total,
        tile_size=T,
        n_block_rows=bucket.n_blocks,
        n_block_cols=bucket.n_blocks,
        storage=storage,
    )

    if hybrid:
        sp_r = np.concatenate([r for r, _ in sp_parts])
        sp_c = np.concatenate([c for _, c in sp_parts])
        sp_nnz = int(sp_r.shape[0])
        cap = next_pow2(max(sp_nnz, 8))
        tail = (np.concatenate([sp_r, np.full(cap - sp_nnz, n_total)]),
                np.concatenate([sp_c, np.full(cap - sp_nnz, n_total)]))
        tail = tuple(x.astype(np.int32) for x in tail) + (sp_nnz,)
        n_sparse = sum(pt.n_sparse_tiles for pt in parts)
        batch_tiled = dataclasses.replace(
            partitioned_tiling(batch_tiled, tail, thr, n_sparse),
            n_tiles=bucket.n_tiles_pad,
        )
        n_real_tiles += n_sparse

    member_sizes = np.zeros(bucket.n_members, dtype=np.int32)
    member_sizes[: len(sizes)] = sizes
    return PackedBatch(
        g=batch_g,
        tiled=batch_tiled,
        alive0=jnp.asarray(alive0),
        col_gate=jnp.asarray(col_gate),
        slots=MemberSlots(
            member=jnp.asarray(member), local=jnp.asarray(local),
            deg=jnp.asarray(deg), sizes=jnp.asarray(member_sizes),
        ),
        offsets=tuple(offsets),
        sizes=tuple(sizes),
        bucket=bucket,
        n_real_edges=n_real_edges,
        n_real_tiles=n_real_tiles,
    )
