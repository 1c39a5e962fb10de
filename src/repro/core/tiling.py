"""Block-tiled adjacency representation (the paper's §3.2, TPU-sized).

The adjacency matrix is cut into ``T×T`` dense tiles; only non-empty tiles are
stored, sorted by block-row then block-column (BSR order).  Sorting by
block-row is load-bearing: the Pallas SpMV kernel walks tiles in this order
and accumulates consecutive same-row tiles into one resident VMEM output
block — the TPU replacement for the paper's per-row-per-tile atomics.

The paper uses T=16 (WMMA fragment size).  The TPU MXU is a 128×128 systolic
array, so T defaults to 128 here; the builder takes any power of two ≥ 8 and
the benchmarks sweep it (see DESIGN.md §2 for the density trade-off).

Tiles are 0/1 matrices, stored in one of two formats — the `storage` axis of
the representation (DESIGN.md §11):

  int8      (nt, T, T) int8 — one byte per cell.  The original layout and
            the oracle substrate; kernels upcast to bf16/f32 at the MXU.
  bitpack   (nt, T, W) uint32 with W = max(T // 32, 1) — 1 bit per cell,
            packed along columns (bit j of word w of row v = column
            32·w + j).  8× less HBM, DMA traffic and plan-cache bytes; the
            Pallas kernels unpack per-tile in VMEM after the DMA, so HBM
            only ever sees packed words.

`pack_tile_bits` (host, numpy) and `unpack_tile_bits` (jnp, jit- and
kernel-safe) convert between them; every consumer detects the format from
the tile dtype, so raw-array call sites stay storage-polymorphic.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.graph import Graph

STORAGES = ("int8", "bitpack")   # concrete tile storage formats
_BITS = 32                       # bits per packed word (uint32)

# auto-mode gate for hybrid tile routing (DESIGN.md §16): attaching a
# partition only pays off once there are enough tiles for the split to
# matter AND a real sparse tail to peel off.
HYBRID_AUTO_MIN_TILES = 16
HYBRID_AUTO_MIN_SPARSE_FRAC = 0.25


def packed_words(tile_size: int) -> int:
    """Words per packed tile row: ceil over 32, floor 1 (T=8/16 use the low
    T bits of a single word)."""
    return max(int(tile_size) // _BITS, 1)


def pack_tile_bits(tiles) -> np.ndarray:
    """(..., T, T) 0/1 -> (..., T, W) uint32, bits packed along columns.

    Host-side (numpy): the build/cache path packs once; unpacking is the
    jit/kernel-side operation (`unpack_tile_bits`)."""
    t = np.asarray(tiles)
    T = t.shape[-1]
    W = packed_words(T)
    bits = (t != 0).astype(np.uint32)
    if W * _BITS != T:  # T < 32: pad columns up to one full word
        pad = np.zeros(t.shape[:-1] + (W * _BITS - T,), np.uint32)
        bits = np.concatenate([bits, pad], axis=-1)
    bits = bits.reshape(t.shape[:-1] + (W, _BITS))
    weights = np.uint32(1) << np.arange(_BITS, dtype=np.uint32)
    # disjoint bit positions ⇒ OR-reduce is an overflow-free sum
    return np.bitwise_or.reduce(bits * weights, axis=-1)


def unpack_tile_bits(packed: jnp.ndarray, tile_size: int) -> jnp.ndarray:
    """(..., T, W) uint32 -> (..., T, T) int8 — the jit/kernel-side inverse.

    Uses `broadcasted_iota` (not 1-D arange) so the same expression lowers
    inside Pallas TPU kernel bodies, where it runs on the VMEM-resident
    block right after the (8× smaller) DMA."""
    W = packed.shape[-1]
    shifts = jax.lax.broadcasted_iota(
        jnp.uint32, packed.shape + (_BITS,), len(packed.shape)
    )
    bits = (packed[..., None] >> shifts) & jnp.uint32(1)
    full = bits.reshape(packed.shape[:-1] + (W * _BITS,))
    return full[..., : int(tile_size)].astype(jnp.int8)


def unpack_tile_mask(packed: jnp.ndarray, tile_size: int) -> jnp.ndarray:
    """(..., T, W) uint32 -> (..., T, T) bool — `unpack_tile_bits` without the
    int8 materialisation.  Consumers that only need an edge *mask* (the
    neighbour-max `where`, the SpMV 0/1 upcast) should use this form: it
    skips one full elementwise pass over the dense tile (the int8 cast) —
    the pass that made the packed neighbour-max slower than int8 at T=64.
    Same
    `broadcasted_iota` construction, so it lowers inside Pallas kernel
    bodies (restricted to them by tools/ci_guards.py, like the int8 form).
    """
    W = packed.shape[-1]
    shifts = jax.lax.broadcasted_iota(
        jnp.uint32, packed.shape + (_BITS,), len(packed.shape)
    )
    bits = (packed[..., None] >> shifts) & jnp.uint32(1)
    full = bits.reshape(packed.shape[:-1] + (W * _BITS,))
    return full[..., : int(tile_size)] != 0


def dense_tiles(tiles: jnp.ndarray, tile_size: int) -> jnp.ndarray:
    """Storage dispatch for ORACLE paths (jnp engine ops, `kernels/ref.py`):
    packed uint32 tiles densify under jit, int8 tiles pass through.  The
    Pallas kernels must never call this — they unpack per-tile in VMEM so
    HBM only sees packed words (enforced by tools/ci_guards.py)."""
    if tiles.dtype == jnp.uint32:
        return unpack_tile_bits(tiles, tile_size)
    return tiles


def dense_tile_mask(tiles: jnp.ndarray, tile_size: int) -> jnp.ndarray:
    """`dense_tiles` counterpart yielding a bool edge MASK: packed uint32
    tiles bit-extract straight to bool (no int8 intermediate), int8 tiles
    compare against zero.  The jnp tile operators use this form; kernels
    never may (tools/ci_guards.py — it materialises (nt, T, T) in HBM)."""
    if tiles.dtype == jnp.uint32:
        return unpack_tile_mask(tiles, tile_size)
    return tiles != 0


def tiles_as_words(tiles: jnp.ndarray, tile_size: int) -> jnp.ndarray:
    """Tiles in the packed-word form, whatever the storage: bitpack tiles
    pass through, int8 tiles pack (jit-safe — the bitwise frontier path
    needs packed words even when the PLAN stores int8).  Packing is safe
    anywhere; it is the *unpack* direction the CI guards restrict."""
    if tiles.dtype == jnp.uint32:
        return tiles
    return pack_frontier_bits(tiles, tile_size)


def padded_tile_count(n_real: int, pad_tiles_to: int | None = None) -> int:
    """Stored tile count for `n_real` real tiles: floor 1 (an empty graph
    still stores one zero tile), optional caller floor, aligned up to 8
    for sharding.  THE single definition of the tile-list pad convention —
    `build_block_tiles` and the delta path (`repro.dyngraph.retile`) must
    agree on it, or patched tilings stop being bit-exact with rebuilds."""
    stored = max(int(n_real), 1)
    target = max(pad_tiles_to or stored, stored)
    return ((target + 7) // 8) * 8


def next_pow2(x: int) -> int:
    """Smallest power of two ≥ x (≥ 1) — the shape-bucket quantiser shared by
    the serving batcher and the bucketed validator (one definition, or their
    bucket shapes drift apart)."""
    return 1 << max(int(x) - 1, 0).bit_length()


# one (8, 128) int32 tile: the COO tail's capacity quantum
TAIL_QUANTUM = 1024


def tail_capacity(n: int) -> int:
    """Padded length of a COO tail of `n` real entries: `n` rounded up to
    a multiple of `TAIL_QUANTUM` (under 0.1% of padding above 1 M
    entries), or the next power of two ≥ 8 below one quantum.  THE single
    definition — both partition routes and the delta path must agree on
    it, or their tails stop being bit-identical."""
    n = int(n)
    if n < TAIL_QUANTUM:
        return next_pow2(max(n, 8))
    return -(-n // TAIL_QUANTUM) * TAIL_QUANTUM


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BlockTiledGraph:
    """BSR adjacency: only non-empty T×T tiles, row-major block order.

    Attributes:
      tiles:      (n_tiles_pad, T, T) int8 or (n_tiles_pad, T, W) uint32 —
                  0/1 dense tiles per `storage` (padding = zeros).
      tile_rows:  (n_tiles_pad,) int32 — block-row of each tile (padding tiles
                  carry the *last real* block-row so revisit-accumulation
                  stays monotone and adds zero).
      tile_cols:  (n_tiles_pad,) int32 — block-column of each tile.
      row_starts: (n_block_rows+1,) int32 — CSR-style pointer into the tile
                  list per block-row (host metadata for partitioning).
      n_tiles:    static — number of real tiles.
      n_nodes:    static — vertex count (pre-padding).
      tile_size:  static — T.
      n_block_rows / n_block_cols: static — ceil(n_nodes / T).
      storage:    static — 'int8' | 'bitpack' (the tile dtype's declared
                  format; raw-array consumers detect it from the dtype).
      partition:  optional hybrid routing split (DESIGN.md §16): a
                  `TilePartition` whose compacted dense sub-tiling and
                  COO sparse tail the hybrid engines dispatch.  A
                  partitioned tiling holds NO full tile list: `tiles`,
                  `tile_rows`, `tile_cols` and `row_starts` are empty
                  (length 0) and `n_tiles` counts the real tiles of both
                  halves, so nothing of the full list reaches the device.
                  `tiling_cells` / `full_tiling` recover the full list
                  from the partition where a caller needs it.
    """
    tiles: jnp.ndarray
    tile_rows: jnp.ndarray
    tile_cols: jnp.ndarray
    row_starts: jnp.ndarray
    n_tiles: int = dataclasses.field(metadata=dict(static=True))
    n_nodes: int = dataclasses.field(metadata=dict(static=True))
    tile_size: int = dataclasses.field(metadata=dict(static=True))
    n_block_rows: int = dataclasses.field(metadata=dict(static=True))
    n_block_cols: int = dataclasses.field(metadata=dict(static=True))
    storage: str = dataclasses.field(default="int8", metadata=dict(static=True))
    partition: Optional["TilePartition"] = None

    @property
    def n_tiles_pad(self) -> int:
        return int(self.tiles.shape[0])

    @property
    def n_padded(self) -> int:
        """Vertex count rounded up to a whole number of tiles."""
        return self.n_block_rows * self.tile_size

    def nnz(self) -> int:
        """Edge count over stored tiles, computed ON DEVICE — only the
        scalar crosses to host (bitpack counts bits via popcount).  A
        partitioned tiling counts its dense sub-tiling and its tail."""
        if self.partition is not None:
            return self.partition.dense.nnz() + self.partition.sp_nnz
        if self.n_tiles == 0:
            return 0
        t = self.tiles[: self.n_tiles]
        if self.storage == "bitpack":
            count = jnp.sum(
                jax.lax.population_count(t).astype(jnp.int32), dtype=jnp.int32
            )
        else:
            count = jnp.count_nonzero(t)
        return int(count)

    def density(self) -> float:
        """Fraction of tile cells that are real edges (the paper's trade-off)."""
        cells = self.n_tiles * self.tile_size * self.tile_size
        return self.nnz() / max(cells, 1)

    def tile_payload_bytes(self) -> int:
        """Bytes of stored tile payload alone (the HBM/DMA term the storage
        axis shrinks 8×): a partitioned tiling stores only its dense
        sub-tiling's."""
        own = self.tiles.size * self.tiles.dtype.itemsize
        if self.partition is not None:
            own += self.partition.dense.tile_payload_bytes()
        return own

    def memory_bytes(self) -> int:
        """HBM footprint of the tiled representation: every device array
        it holds (payload + indices, and a partition's dense sub-tiling and
        COO tail)."""
        return sum(
            int(x.size) * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(self)
        )

    def to_storage(self, storage: str) -> "BlockTiledGraph":
        """Convert between tile storage formats (host-side, exact).  A
        partitioned tiling converts its dense sub-tiling; the tail holds
        vertex ids, not tiles, and the threshold stays as resolved."""
        if storage not in STORAGES:
            raise ValueError(
                f"unknown storage {storage!r}; valid: {STORAGES}"
            )
        if storage == self.storage:
            return self
        if self.partition is not None:
            part = self.partition
            dense = part.dense.to_storage(storage)
            return dataclasses.replace(
                self,
                tiles=jnp.zeros((0,) + dense.tiles.shape[1:], dense.tiles.dtype),
                storage=storage,
                partition=dataclasses.replace(part, dense=dense),
            )
        if storage == "bitpack":
            tiles = jnp.asarray(pack_tile_bits(np.asarray(self.tiles)))
        else:
            tiles = jnp.asarray(
                np.asarray(unpack_tile_bits(self.tiles, self.tile_size))
            )
        return dataclasses.replace(self, tiles=tiles, storage=storage)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TilePartition:
    """nnz-classified hybrid routing split of a tiled adjacency (§16).

    Built at plan time by `partition_tiles`: tiles at or above the density
    threshold form a COMPACTED dense sub-tiling (same block grid, same
    storage, own `row_starts` — all dense tile ops run on it unchanged,
    and the sparse/empty tiles vanish from its dispatch entirely); tiles
    below the threshold are lowered to COO edge lists executed through the
    `core/spmv.py` segment ops.  Empty tiles appear in NEITHER list.

    Attributes:
      dense:     compacted `BlockTiledGraph` over the dense tile set
                 (its own `partition` is always None).
      sp_rows:   (sp_pad,) int32 — GLOBAL padded output-vertex id per
                 sparse nnz (tile row axis: the SpMV scatter target).
      sp_cols:   (sp_pad,) int32 — GLOBAL padded input-vertex id per
                 sparse nnz (tile column axis: the gather source).
                 Both padded to `tail_capacity(sp_nnz)` with the
                 sentinel id `n_padded`; segment consumers use
                 `num_segments = n_padded + 1` and slice the sentinel row
                 off, exactly like the Graph sentinel-edge convention.
      threshold: static — nnz cut: dense iff nnz >= threshold.
      n_dense_tiles / n_sparse_tiles: static — real tiles per class.
      sp_nnz:    static — real (unpadded) sparse-tail edge count.
    """
    dense: BlockTiledGraph
    sp_rows: jnp.ndarray
    sp_cols: jnp.ndarray
    threshold: int = dataclasses.field(metadata=dict(static=True))
    n_dense_tiles: int = dataclasses.field(metadata=dict(static=True))
    n_sparse_tiles: int = dataclasses.field(metadata=dict(static=True))
    sp_nnz: int = dataclasses.field(metadata=dict(static=True))


def tile_nnz(tiled: BlockTiledGraph) -> np.ndarray:
    """Per-tile nnz over the stored tile list, computed ON DEVICE — one
    (n_tiles_pad,) int32 transfer (bitpack counts bits via popcount;
    padding tiles are all-zero so their entries read 0).  A partitioned
    tiling stores no full list: count `full_tiling(tiled)` instead."""
    t = tiled.tiles
    if tiled.storage == "bitpack":
        counts = jnp.sum(
            jax.lax.population_count(t).astype(jnp.int32),
            axis=(1, 2), dtype=jnp.int32,
        )
    else:
        counts = jnp.sum(
            (t != 0).astype(jnp.int32), axis=(1, 2), dtype=jnp.int32
        )
    return np.asarray(counts)


def _host_unpack_tile_bits(packed: np.ndarray, tile_size: int) -> np.ndarray:
    """Host-side (numpy) inverse of `pack_tile_bits` for the plan-time
    partition build — no device round-trip, no jit trace."""
    shifts = np.arange(_BITS, dtype=np.uint32)
    bits = (packed[..., None] >> shifts) & np.uint32(1)
    full = bits.reshape(packed.shape[:-1] + (packed.shape[-1] * _BITS,))
    return (full[..., : int(tile_size)] != 0).astype(np.int8)


# --------------------------------------------------------------------------
# the edge-sized build (DESIGN.md §16): everything derives from the sorted
# nonzero CELLS of the adjacency, one int64 key per half-edge, so the host
# work and memory are sized by entries; only tiles that are actually stored
# as tiles ever get a T×T (or T×W) payload.
# --------------------------------------------------------------------------


def cell_keys(rows, cols, tile_size: int, n_block_cols: int) -> np.ndarray:
    """int64 cell key of each (row, col) vertex pair: `(tile_key · T +
    row % T) · T + col % T`, `tile_key = (row // T) · nb + col // T`."""
    T = int(tile_size)
    r = np.asarray(rows).astype(np.int64)
    c = np.asarray(cols).astype(np.int64)
    return ((r // T) * n_block_cols + c // T) * (T * T) + (r % T) * T + c % T


@dataclasses.dataclass(frozen=True)
class TileCells:
    """The nonzero cells of a tiled adjacency, host-side, sized by entries.

    `cells` holds one sorted, unique int64 key per nonzero cell
    (`cell_keys`) — so the order is the BSR tile order, then row, then
    column, within each tile.  `first[i]` is the
    index of tile i's first cell; tiles with no cell do not exist here.
    """
    cells: np.ndarray
    first: np.ndarray
    tile_size: int
    n_block_cols: int

    @classmethod
    def from_cells(cls, cells: np.ndarray, tile_size: int,
                   n_block_cols: int) -> "TileCells":
        """From sorted unique cell keys."""
        T2 = int(tile_size) * int(tile_size)
        tk = cells // T2
        first = np.flatnonzero(np.concatenate([[True], tk[1:] != tk[:-1]])) \
            if cells.size else np.zeros(0, np.int64)
        return cls(cells, first, int(tile_size), int(n_block_cols))

    @classmethod
    def from_edges(cls, senders: np.ndarray, receivers: np.ndarray,
                   tile_size: int, n_block_cols: int) -> "TileCells":
        """Cells of half-edges (row = sender, column = receiver);
        duplicates collapse to one cell, as in a 0/1 tile."""
        keys = cell_keys(senders, receivers, tile_size, n_block_cols)
        return cls.from_cells(np.unique(keys), tile_size, n_block_cols)

    @property
    def n_tiles(self) -> int:
        return int(self.first.shape[0])

    @functools.cached_property
    def counts(self) -> np.ndarray:
        """(n_tiles,) int64 — nonzero cells per tile."""
        return np.diff(np.append(self.first, self.cells.shape[0]))

    @functools.cached_property
    def keys(self) -> np.ndarray:
        """(n_tiles,) int64 — tile keys `block_row · nb + block_col`."""
        T = self.tile_size
        return self.cells[self.first] // (T * T)


def _checked_build(tile_size: int, storage: str) -> int:
    T = int(tile_size)
    if T < 8 or (T & (T - 1)):
        raise ValueError(f"tile_size must be a power of two >= 8, got {T}")
    if storage not in STORAGES:
        raise ValueError(f"unknown storage {storage!r}; valid: {STORAGES}")
    return T


def _pack_cells(cells: np.ndarray, tidx: np.ndarray, n_out: int,
                tile_size: int, storage: str) -> np.ndarray:
    """Payload of `n_out` tiles from sorted unique cells, `tidx` the output
    tile of each cell (non-decreasing).  Bitpack words are OR-reduced
    straight from the cells: no (n, T, T) int8 intermediate."""
    T = int(tile_size)
    loc = cells % (T * T)
    rl, cl = loc // T, loc % T
    if storage == "int8":
        out = np.zeros((n_out, T, T), np.int8)
        out[tidx, rl, cl] = 1
        return out
    W = packed_words(T)
    out = np.zeros((n_out, T, W), np.uint32)
    if cells.size:
        # cells sorted ⇒ word indices non-decreasing; disjoint bits ⇒ OR
        word = (tidx * T + rl) * W + cl // _BITS
        bits = np.left_shift(np.uint32(1), (cl % _BITS).astype(np.uint32))
        starts = np.flatnonzero(np.concatenate([[True], word[1:] != word[:-1]]))
        out.reshape(-1)[word[starts]] = np.bitwise_or.reduceat(bits, starts)
    return out


def tiles_from_cells(
    tc: TileCells,
    n_nodes: int,
    storage: str,
    *,
    select: Optional[np.ndarray] = None,
    pad_tiles_to: Optional[int] = None,
) -> BlockTiledGraph:
    """A BSR tiling of the tiles `select`ed (bool per tile; None = all),
    in their BSR order, padded by `padded_tile_count` — the full build
    when `select` is None, the compacted dense sub-tiling of a partition
    otherwise."""
    T, nb = _checked_build(tc.tile_size, storage), tc.n_block_cols
    counts = tc.counts
    if select is None:
        keys, cells, sel_counts = tc.keys, tc.cells, counts
    else:
        keys = tc.keys[select]
        cells = tc.cells[np.repeat(select, counts)]
        sel_counts = counts[select]
    n = int(keys.shape[0])
    tidx = np.repeat(np.arange(n, dtype=np.int64), sel_counts)
    tiles = _pack_cells(cells, tidx, max(n, 1), T, storage)
    rows = (keys // nb).astype(np.int32)
    cols = (keys % nb).astype(np.int32)
    if n == 0:   # an empty tiling still stores one zero tile at (0, 0)
        rows = np.zeros(1, np.int32)
        cols = np.zeros(1, np.int32)
    row_starts = np.zeros(nb + 1, dtype=np.int32)
    np.cumsum(np.bincount(rows[:n], minlength=nb), out=row_starts[1:])

    # pad: zero tiles pinned to the last real block-row (monotone, no-op adds)
    stored = tiles.shape[0]
    target = padded_tile_count(n, pad_tiles_to)
    if target > stored:
        last_row = rows[n - 1] if n else np.int32(0)
        tiles = np.concatenate(
            [tiles, np.zeros((target - stored,) + tiles.shape[1:], tiles.dtype)]
        )
        rows = np.concatenate(
            [rows, np.full(target - stored, last_row, np.int32)])
        cols = np.concatenate([cols, np.zeros(target - stored, np.int32)])
    return BlockTiledGraph(
        tiles=jnp.asarray(tiles),
        tile_rows=jnp.asarray(rows),
        tile_cols=jnp.asarray(cols),
        row_starts=jnp.asarray(row_starts),
        n_tiles=n,
        n_nodes=int(n_nodes),
        tile_size=T,
        n_block_rows=nb,
        n_block_cols=nb,
        storage=storage,
    )


def coo_tail(
    tc: TileCells, dense: np.ndarray, n_padded: int
) -> Tuple[np.ndarray, np.ndarray, int]:
    """(sp_rows, sp_cols, sp_nnz): the cells of the tiles not `dense` as a
    COO list in GLOBAL padded vertex ids, in cell order (tile by tile, then
    row, then column), sentinel-padded (`n_padded`) to `tail_capacity`."""
    T, nb = tc.tile_size, tc.n_block_cols
    sp = tc.cells[~np.repeat(dense, tc.counts)]
    tk, loc = sp // (T * T), sp % (T * T)
    sp_nnz = int(sp.shape[0])
    cap = tail_capacity(sp_nnz)
    sp_rows = np.full(cap, n_padded, np.int32)
    sp_cols = np.full(cap, n_padded, np.int32)
    sp_rows[:sp_nnz] = (tk // nb) * T + loc // T
    sp_cols[:sp_nnz] = (tk % nb) * T + loc % T
    return sp_rows, sp_cols, sp_nnz


def partitioned_tiling(
    dense: BlockTiledGraph,
    tail: Tuple[np.ndarray, np.ndarray, int],
    threshold: int,
    n_sparse_tiles: int,
) -> BlockTiledGraph:
    """The partitioned tiling the hybrid engines take: `dense` sub-tiling
    and COO `tail` (`coo_tail`), around an empty full list."""
    sp_rows, sp_cols, sp_nnz = tail
    part = TilePartition(
        dense=dense,
        sp_rows=jnp.asarray(sp_rows),
        sp_cols=jnp.asarray(sp_cols),
        threshold=int(threshold),
        n_dense_tiles=dense.n_tiles,
        n_sparse_tiles=int(n_sparse_tiles),
        sp_nnz=int(sp_nnz),
    )
    return dataclasses.replace(
        dense,
        tiles=jnp.zeros((0,) + dense.tiles.shape[1:], dense.tiles.dtype),
        tile_rows=jnp.zeros(0, jnp.int32),
        tile_cols=jnp.zeros(0, jnp.int32),
        row_starts=jnp.zeros(0, jnp.int32),
        n_tiles=dense.n_tiles + int(n_sparse_tiles),
        partition=part,
    )


def partition_from_cells(
    tc: TileCells, threshold: int, n_nodes: int, storage: str
) -> BlockTiledGraph:
    """Partitioned tiling straight from cells: pack the tiles at or above
    `threshold`, lower the rest to the COO tail."""
    dense = tc.counts >= int(threshold)
    sub = tiles_from_cells(tc, n_nodes, storage, select=dense)
    tail = coo_tail(tc, dense, sub.n_padded)
    return partitioned_tiling(
        sub, tail, threshold, tc.n_tiles - sub.n_tiles)


def partition_pays(mode: str, n_tiles: int, n_sparse: int) -> bool:
    """The hybrid policy on tile counts: 'forced' always partitions, 'auto'
    iff there are ≥ HYBRID_AUTO_MIN_TILES non-empty tiles and the
    sub-threshold tail is ≥ HYBRID_AUTO_MIN_SPARSE_FRAC of them, 'off'
    never."""
    if mode not in ("auto", "off", "forced"):
        raise ValueError(f"unknown hybrid mode {mode!r}; valid: auto|off|forced")
    if mode != "auto":
        return mode == "forced"
    return (
        n_tiles >= HYBRID_AUTO_MIN_TILES
        and n_sparse > 0
        and n_sparse >= HYBRID_AUTO_MIN_SPARSE_FRAC * n_tiles
    )


def _stored_cells(tiled: BlockTiledGraph) -> np.ndarray:
    """Cell keys of an unpartitioned tiling's stored tiles (host unpack)."""
    T, nb = tiled.tile_size, tiled.n_block_cols
    tiles = np.asarray(tiled.tiles)[: tiled.n_tiles]
    if tiled.storage == "bitpack":
        tiles = _host_unpack_tile_bits(tiles, T)
    ti, rl, cl = np.nonzero(tiles)
    rows = np.asarray(tiled.tile_rows)[ti].astype(np.int64) * T + rl
    cols = np.asarray(tiled.tile_cols)[ti].astype(np.int64) * T + cl
    return cell_keys(rows, cols, T, nb)


def tiling_cells(tiled: BlockTiledGraph) -> TileCells:
    """The cells of any tiling: its stored tiles, or a partition's dense
    tiles and tail.  Unpacks only what is stored as tiles."""
    T, nb = tiled.tile_size, tiled.n_block_cols
    part = tiled.partition
    if part is None:
        cells = _stored_cells(tiled)
    else:
        tail = cell_keys(np.asarray(part.sp_rows)[: part.sp_nnz],
                         np.asarray(part.sp_cols)[: part.sp_nnz], T, nb)
        cells = np.concatenate([_stored_cells(part.dense), tail])
    return TileCells.from_cells(np.unique(cells), T, nb)


def full_tiling(tiled: BlockTiledGraph) -> BlockTiledGraph:
    """The full BSR tile list of a tiling — itself when unpartitioned,
    rebuilt from a partition's cells otherwise (for the routes that need
    every tile: the sharded loop, a mixed batch)."""
    if tiled.partition is None:
        return tiled
    return tiles_from_cells(tiling_cells(tiled), tiled.n_nodes, tiled.storage)


def partition_tiles(
    tiled: BlockTiledGraph,
    threshold: int,
    *,
    nnz: np.ndarray | None = None,
) -> TilePartition:
    """Classify a FULL tile list's tiles by nnz and build the hybrid split
    (host-side, numpy) — the tile-list route; plans build theirs from
    edges (`TileCells`, `partition_from_cells`), with the same result.

    Deterministic in (tiles, threshold): rebuilding after a delta or a
    storage conversion yields bit-identical partitions, which keeps the
    dyngraph rebuild oracle exact.  Dense tiles keep their row-major order
    so the compacted CSR stays kernel-legal; the sparse tail needs no
    ordering (segment ops scatter by id).
    """
    T = tiled.tile_size
    thr = int(threshold)
    if nnz is None:
        nnz = tile_nnz(tiled)
    real = np.asarray(nnz)[: tiled.n_tiles]
    dense_idx = np.nonzero(real >= thr)[0]
    sparse_idx = np.nonzero((real > 0) & (real < thr))[0]

    tiles_h = np.asarray(tiled.tiles)
    rows_h = np.asarray(tiled.tile_rows)
    cols_h = np.asarray(tiled.tile_cols)

    # -- dense subset: gather, recompute CSR, re-pad (empty tiles vanish) --
    n_dense = int(dense_idx.shape[0])
    d_tiles = tiles_h[dense_idx]
    d_rows = rows_h[dense_idx].astype(np.int32)
    d_cols = cols_h[dense_idx].astype(np.int32)
    counts = np.bincount(
        d_rows if n_dense else np.zeros(0, np.int64),
        minlength=tiled.n_block_rows,
    )
    row_starts = np.zeros(tiled.n_block_rows + 1, dtype=np.int32)
    np.cumsum(counts, out=row_starts[1:])
    target = padded_tile_count(n_dense)
    if target > n_dense:
        last_row = d_rows[-1] if n_dense else np.int32(0)
        pad_shape = (target - n_dense,) + tiles_h.shape[1:]
        d_tiles = np.concatenate(
            [d_tiles, np.zeros(pad_shape, tiles_h.dtype)], axis=0
        )
        d_rows = np.concatenate(
            [d_rows, np.full(target - n_dense, last_row, np.int32)]
        )
        d_cols = np.concatenate(
            [d_cols, np.zeros(target - n_dense, np.int32)]
        )
    dense = BlockTiledGraph(
        tiles=jnp.asarray(d_tiles),
        tile_rows=jnp.asarray(d_rows),
        tile_cols=jnp.asarray(d_cols),
        row_starts=jnp.asarray(row_starts),
        n_tiles=n_dense,
        n_nodes=tiled.n_nodes,
        tile_size=T,
        n_block_rows=tiled.n_block_rows,
        n_block_cols=tiled.n_block_cols,
        storage=tiled.storage,
    )

    # -- sparse tail: COO in GLOBAL padded vertex ids, sentinel-padded --
    sp_sub = tiles_h[sparse_idx]
    if tiled.storage == "bitpack":
        sp_sub = _host_unpack_tile_bits(sp_sub, T)
    t_i, r_i, c_i = np.nonzero(sp_sub)
    v = rows_h[sparse_idx][t_i].astype(np.int64) * T + r_i
    u = cols_h[sparse_idx][t_i].astype(np.int64) * T + c_i
    sp_nnz = int(v.shape[0])
    cap = tail_capacity(sp_nnz)
    sentinel = np.int32(tiled.n_padded)
    sp_rows = np.full(cap, sentinel, np.int32)
    sp_cols = np.full(cap, sentinel, np.int32)
    sp_rows[:sp_nnz] = v.astype(np.int32)
    sp_cols[:sp_nnz] = u.astype(np.int32)

    return TilePartition(
        dense=dense,
        sp_rows=jnp.asarray(sp_rows),
        sp_cols=jnp.asarray(sp_cols),
        threshold=thr,
        n_dense_tiles=n_dense,
        n_sparse_tiles=int(sparse_idx.shape[0]),
        sp_nnz=sp_nnz,
    )


def attach_partition(
    tiled: BlockTiledGraph,
    mode: str = "auto",
    threshold: int | None = None,
) -> BlockTiledGraph:
    """Hybrid-routing policy front door (the knob behind
    `SolveOptions.hybrid`): returns `tiled` with a partition attached,
    or partition-free when the policy says the split won't pay.

      off     never partition (drop any stale one).
      forced  always partition (tests force tiny graphs through hybrid).
      auto    partition iff there are ≥ HYBRID_AUTO_MIN_TILES non-empty
              tiles AND the sub-threshold tail is ≥
              HYBRID_AUTO_MIN_SPARSE_FRAC of them.

    `threshold` defaults to the roofline break-even
    (`repro.perf.hybrid_density_threshold`).  The result holds either the
    full tile list or a partition, never both; a partitioned input is
    re-decided from its full list (`full_tiling`).
    """
    if mode not in ("auto", "off", "forced"):
        raise ValueError(f"unknown hybrid mode {mode!r}; valid: auto|off|forced")
    tiled = full_tiling(tiled)
    if mode == "off":
        return tiled
    if threshold is None:
        from repro.perf.roofline import hybrid_density_threshold

        threshold = hybrid_density_threshold(tiled.tile_size, tiled.storage)
    thr = int(threshold)
    nnz = tile_nnz(tiled)
    real = nnz[: tiled.n_tiles]
    nonempty = int(np.count_nonzero(real))
    n_sparse = int(np.count_nonzero((real > 0) & (real < thr)))
    if not partition_pays(mode, nonempty, n_sparse):
        return tiled
    part = partition_tiles(tiled, thr, nnz=nnz)
    return partitioned_tiling(
        part.dense, (part.sp_rows, part.sp_cols, part.sp_nnz), thr,
        part.n_sparse_tiles,
    )


def rcm_ordering(g: Graph) -> np.ndarray:
    """Reverse Cuthill–McKee vertex permutation (beyond-paper, DESIGN.md §6).

    Locality reordering concentrates edges near the diagonal, raising
    intra-tile density and cutting the non-empty tile count — the lever that
    makes 128×128 MXU tiles viable on graphs the paper would tile at 16×16.
    Returns perm such that new_id = perm_inv[old_id].
    """
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import reverse_cuthill_mckee

    s = np.asarray(g.senders)[: g.n_edges]
    r = np.asarray(g.receivers)[: g.n_edges]
    adj = coo_matrix(
        (np.ones(len(s), np.int8), (s, r)), shape=(g.n_nodes, g.n_nodes)
    ).tocsr()
    return np.asarray(reverse_cuthill_mckee(adj, symmetric_mode=True))


def build_block_tiles(
    g: Graph,
    tile_size: int = 128,
    *,
    pad_tiles_to: int | None = None,
    reorder: str | None = None,   # None | 'rcm'
    storage: str = "int8",        # 'int8' | 'bitpack'
) -> BlockTiledGraph:
    """Tile ``g``'s adjacency matrix (host-side, numpy).

    Steps (mirrors the paper's Listing 1 preprocessing):
      1. (optional) RCM locality reordering — beyond-paper, see rcm_ordering,
      2. map each half-edge (u, v) to its cell key, tile key (u//T, v//T)
         first (`TileCells`), unique and sorted: row-major tile order,
      3. pack each tile's cells (int8 bytes, or uint32 words for
         storage='bitpack', straight from the cells),
      4. pad the tile list so shapes are static/shardable.

    NOTE with reorder='rcm' the returned tiling indexes PERMUTED vertex ids;
    callers must map priorities/results through the same permutation (the
    MIS solution set is permutation-equivariant, so validity is unaffected —
    tests/test_tiling.py::test_rcm_mis_roundtrip).
    """
    T = _checked_build(tile_size, storage)
    s = np.asarray(g.senders)[: g.n_edges].astype(np.int64)
    r = np.asarray(g.receivers)[: g.n_edges].astype(np.int64)
    if reorder == "rcm":
        perm = rcm_ordering(g)                 # perm[new_id] = old_id
        inv = np.empty_like(perm)
        inv[perm] = np.arange(g.n_nodes)
        s, r = inv[s], inv[r]
    nb = -(-g.n_nodes // T)  # ceil
    return tiles_from_cells(
        TileCells.from_edges(s, r, T, nb), g.n_nodes, storage,
        pad_tiles_to=pad_tiles_to,
    )


def pack_vertex_vector(x: jnp.ndarray, tiled: BlockTiledGraph) -> jnp.ndarray:
    """(n_nodes,) -> (n_padded,) zero-padded to whole tiles."""
    pad = tiled.n_padded - x.shape[0]
    return jnp.pad(x, (0, pad)) if pad else x


def unpack_vertex_vector(x: jnp.ndarray, tiled: BlockTiledGraph) -> jnp.ndarray:
    return x[: tiled.n_nodes]


# --------------------------------------------------------------------------
# bit-packed frontier vectors (DESIGN.md §13) — THE single site of the
# frontier packing contract.  `cand`/`alive`/`in_mis` ride the bitwise round
# body as (n_block_cols, W) uint32 words; `core.distributed` packs its
# all-gather frontiers through the same helpers.  Unpacking a frontier is
# restricted to kernel bodies / oracles / this module by tools/ci_guards.py.
# --------------------------------------------------------------------------

def pack_frontier_bits(bits: jnp.ndarray, tile_size: int) -> jnp.ndarray:
    """(..., T) truthy -> (..., W) uint32, bit j of word w = slot 32·w + j.

    The SAME bit layout as `pack_tile_bits` (so a packed tile row ANDs
    directly against a packed frontier word), but jit- and kernel-safe:
    `broadcasted_iota` only, no host numpy — the kernels use it to emit
    packed result bits and the engine uses it on candidate masks each round.
    """
    T = int(tile_size)
    W = packed_words(T)
    shape = bits.shape[:-1] + (W, T)
    c = jax.lax.broadcasted_iota(jnp.uint32, shape, len(shape) - 1)
    w = jax.lax.broadcasted_iota(jnp.uint32, shape, len(shape) - 2)
    weight = jnp.where(
        (c >> 5) == w, jnp.uint32(1) << (c & jnp.uint32(31)), jnp.uint32(0)
    )
    vals = jnp.where(bits[..., None, :] != 0, weight, jnp.uint32(0))
    # disjoint bit positions ⇒ the OR-reduce is an overflow-free sum
    return jnp.sum(vals, axis=-1, dtype=jnp.uint32)


def pack_frontier_column(bits: jnp.ndarray, tile_size: int) -> jnp.ndarray:
    """(T, 1) truthy column -> (1, W) uint32 word row, the layout of
    `pack_frontier_bits`.

    The kernel-body form: inside a Pallas block vertex v of a tile sits on
    sublane v, while its word sits on a lane.  Packing is a broadcast
    against a (T, W) weight matrix and an int32 sum down the sublanes, so
    the TPU compiler never sees the (T, 1) -> (1, T) transpose it refuses.
    Disjoint bit positions keep the wrapping int32 sum exact."""
    T = int(tile_size)
    W = packed_words(T)
    v = jax.lax.broadcasted_iota(jnp.int32, (T, W), 0)
    w = jax.lax.broadcasted_iota(jnp.int32, (T, W), 1)
    weight = jnp.where((v >> 5) == w, 1 << (v & 31), 0)
    vals = jnp.where(bits != 0, weight, 0)
    return jax.lax.bitcast_convert_type(
        jnp.sum(vals, axis=0, keepdims=True), jnp.uint32
    )


def unpack_frontier_bits(words: jnp.ndarray, tile_size: int) -> jnp.ndarray:
    """(..., W) uint32 -> (..., T) bool — inverse of `pack_frontier_bits`.

    A frontier DENSIFY: allowed only inside `*_kernel` bodies, `kernels/
    ref.py`, `*_oracle` functions, the extraction/collective sites named in
    tools/ci_guards.py, and this module (the packing substrate itself)."""
    T = int(tile_size)
    W = words.shape[-1]
    shifts = jax.lax.broadcasted_iota(
        jnp.uint32, words.shape + (_BITS,), len(words.shape)
    )
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(words.shape[:-1] + (W * _BITS,))[..., :T] != 0


def pack_frontier_words(x: jnp.ndarray, tile_size: int) -> jnp.ndarray:
    """(n_blocks·T,) truthy vertex vector -> (n_blocks, W) uint32 words."""
    return pack_frontier_bits(x.reshape(-1, int(tile_size)), tile_size)


def unpack_frontier_words(words: jnp.ndarray, tile_size: int) -> jnp.ndarray:
    """(n_blocks, W) uint32 -> (n_blocks·T,) bool (same guard as
    `unpack_frontier_bits` — this is the extraction-time densify)."""
    return unpack_frontier_bits(words, tile_size).reshape(-1)


def gather_frontier_bits(
    words: jnp.ndarray, ids: jnp.ndarray, tile_size: int
) -> jnp.ndarray:
    """Per-id bit extraction from standard-layout frontier words: for each
    GLOBAL padded vertex id, the bool at its (block, word, bit) slot.

    The hybrid sparse tail reads single frontier bits at its COO gather
    sites; this is a shift-and-mask per id — NOT a frontier densify, so it
    stays legal on hot paths (and lives here, in the packing substrate,
    like every other consumer of the bit layout).  Sentinel ids (= the
    padded vertex count) land out of range and clamp under jnp gather
    semantics; hybrid callers pair them with sentinel scatter rows, so the
    clamped garbage is always dropped.
    """
    T = int(tile_size)
    ids = ids.astype(jnp.int32)
    slot = ids % T
    word = words[ids // T, slot // _BITS]
    return ((word >> (slot % _BITS).astype(jnp.uint32)) & jnp.uint32(1)) != 0


# -- priority-sorted bit order (the bitwise neighbour-max substrate) --------
#
# The bitwise Max_Np is a priority-plane scan collapsed to one pass: sort
# each block-column's slots by descending priority ONCE per solve, pack the
# tiles in that slot order with the MSB-first layout below, and per round the
# scan "iterate planes high→low, AND, fold" degenerates to "index of the
# first set bit" — one AND + count-leading-zeros per word (DESIGN.md §13).

def sort_block_priorities(
    p: jnp.ndarray, tile_size: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(n_blocks·T,) int32 -> (order, p_sorted), both (n_blocks, T).

    `order[b, s]` is the in-block column index occupying descending-priority
    slot `s` of block `b`; `p_sorted` the priorities in slot order.  Exact
    for ANY int32 priorities (negative resolve keys included) — the sort
    carries the values, no bit-plane sign handling needed."""
    blocks = p.reshape(-1, int(tile_size))
    order = jnp.argsort(-blocks, axis=1).astype(jnp.int32)
    return order, jnp.take_along_axis(blocks, order, axis=1)


def pack_sorted_frontier_bits(
    bits_sorted: jnp.ndarray, tile_size: int
) -> jnp.ndarray:
    """(..., T) truthy in sorted-slot order -> (..., W) uint32 with slot s at
    bit 31 − (s mod 32) of word s // 32 — MSB-first, so `clz(word)` IS the
    first occupied slot within the word."""
    T = int(tile_size)
    W = packed_words(T)
    shape = bits_sorted.shape[:-1] + (W, T)
    s = jax.lax.broadcasted_iota(jnp.uint32, shape, len(shape) - 1)
    w = jax.lax.broadcasted_iota(jnp.uint32, shape, len(shape) - 2)
    weight = jnp.where(
        (s >> 5) == w,
        jnp.uint32(1) << (jnp.uint32(31) - (s & jnp.uint32(31))),
        jnp.uint32(0),
    )
    vals = jnp.where(bits_sorted[..., None, :] != 0, weight, jnp.uint32(0))
    return jnp.sum(vals, axis=-1, dtype=jnp.uint32)


def sorted_tile_bits(
    tiles: jnp.ndarray,
    tile_cols: jnp.ndarray,
    order: jnp.ndarray,
    tile_size: int,
) -> jnp.ndarray:
    """Tiles (either storage) column-permuted into each block-column's
    priority-slot order and packed MSB-first: (nt, T, W) uint32.

    Setup-time, once per solve (the order is static for a run's priorities);
    the transient dense mask lives only inside this jit scope."""
    mask = dense_tile_mask(tiles, tile_size)                 # (nt, T, T)
    g_order = order[tile_cols]                               # (nt, T)
    permuted = jnp.take_along_axis(mask, g_order[:, None, :], axis=2)
    return pack_sorted_frontier_bits(permuted, tile_size)


def sorted_frontier_words(
    words: jnp.ndarray, order: jnp.ndarray, tile_size: int
) -> jnp.ndarray:
    """Standard-layout frontier words -> sorted-slot words, per block column.

    The per-round word remap feeding the clz scan: an O(n/32 → n) bit
    permutation (lane shuffles on TPU, ~1/10 the cost of the scan itself).
    The bit-level round-trip lives HERE, in the packing substrate — hot-path
    modules never touch frontier bits (tools/ci_guards.py)."""
    bits = unpack_frontier_bits(words, tile_size)            # (nbc, T)
    bits_sorted = jnp.take_along_axis(bits, order, axis=1)
    return pack_sorted_frontier_bits(bits_sorted, tile_size)


def pack_priority_planes(
    p: jnp.ndarray, tile_size: int, n_bits: int, *, signed: bool = False
) -> jnp.ndarray:
    """(n_blocks·T,) int32 -> (n_bits, n_blocks, W) uint32 bit-planes in the
    STANDARD frontier layout — the Pallas plane-scan kernel's input
    (`kernels.tc_neighbor_max`).  `signed` applies the order-preserving
    bias (bitcast ^ 0x80000000) so two's-complement keys scan correctly;
    the kernel un-biases on output."""
    u = jax.lax.bitcast_convert_type(p.astype(jnp.int32), jnp.uint32)
    if signed:
        u = u ^ jnp.uint32(0x80000000)
    blocks = u.reshape(-1, int(tile_size))
    planes = [
        pack_frontier_bits((blocks >> b) & jnp.uint32(1), tile_size)
        for b in range(int(n_bits))
    ]
    return jnp.stack(planes)


def tile_stats(tiled: BlockTiledGraph) -> dict:
    """Stats for the memory-footprint benchmark (paper §3.2) and the hybrid
    classifier (§16).

    Per-tile nnz is computed on device (`tile_nnz` popcount) — ONE
    (n_tiles_pad,) transfer; the aggregate nnz and the histogram derive
    from it on host, so adding the distribution cost no extra traffic
    (the old aggregate-only scalar pull is gone)."""
    per_tile = tile_nnz(tiled)[: tiled.n_tiles]
    nnz = int(per_tile.sum())
    cells = tiled.n_tiles * tiled.tile_size * tiled.tile_size
    total_blocks = tiled.n_block_rows * tiled.n_block_cols
    # power-of-two-bucketed nnz histogram: bucket `u` counts stored tiles
    # with nnz in (u/2, u]; bucket 0 would be empty tiles (never stored by
    # the builder, but deltas can drain a tile in place).
    cap = tiled.tile_size * tiled.tile_size
    hist = {0: int(np.count_nonzero(per_tile == 0))}
    upper = 1
    while True:
        hist[upper] = int(
            np.count_nonzero((per_tile > upper // 2) & (per_tile <= upper))
        )
        if upper >= cap:
            break
        upper *= 2
    return dict(
        tile_size=tiled.tile_size,
        n_tiles=tiled.n_tiles,
        storage=tiled.storage,
        block_grid=total_blocks,
        block_occupancy=tiled.n_tiles / max(total_blocks, 1),
        intra_tile_density=nnz / max(cells, 1),
        tile_nnz=per_tile.tolist(),
        nnz_hist=hist,
        tile_payload_bytes=tiled.tile_payload_bytes(),
        bsr_bytes=tiled.memory_bytes(),
        csr_bytes=8 * nnz + 4 * (tiled.n_nodes + 1),  # int32 idx + int64-ish ptr
    )
