"""Tile-local retiling: apply an `EdgeDelta` without rebuilding the tiling.

The BSR build (`core.tiling.build_block_tiles`) scatters every half-edge of
the graph; at serving scale that full rebuild — not the solve — is the cost
of a mutating graph.  But a delta only touches the tiles its endpoints land
in: `apply_delta` edits exactly those, leaving every other tile's bytes (and
the device arrays behind them, on the no-structural-change fast path)
untouched.

Per storage format (DESIGN.md §11):

  int8      byte edits — `tiles[t, u%T, v%T] = 0|1`.
  bitpack   word-level bit edits on the packed uint32 words — OR in
            `1 << bit` to add, AND with the complement to remove.  The
            packed tiles are never densified: the delta path obeys the same
            packed-words-only discipline as the kernels (tools/ci_guards.py
            guards this module too).

Structural changes (an add landing in a block the tiling has no tile for,
or a remove draining a tile's last edge) insert/drop tiles in the row-major
tile list and recompute `row_starts` — an O(n_tiles) index shuffle, still
free of the O(E) edge scatter.  The result is BIT-EXACT with a from-scratch
`build_block_tiles` of the mutated graph — padding convention included —
which is both the correctness oracle of the test suite and what lets
patched plans share cache/bucket machinery with built ones.

A partitioned tiling (DESIGN.md §16) holds no full tile list, only its
dense sub-tiling and COO tail.  Its patch works on cells, the form sized
by entries (`core.tiling.TileCells`): the partition's cells, less the
removed half-edges, plus the added ones, reclassified at the same
threshold by `partition_from_cells` — the very build a plan of the mutated
graph runs, so the result is bit-exact with it.  Only the dense tiles
unpack; no sub-threshold tile is ever built.

`apply_graph_delta` is the edge-list twin: the mutated `Graph` re-enters
`from_edges` canonicalisation, so a patched graph is indistinguishable —
content hash included — from the same graph loaded fresh.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.tiling import (
    BlockTiledGraph,
    TileCells,
    cell_keys,
    packed_words,
    padded_tile_count,
    partition_from_cells,
    tiling_cells,
)
from repro.dyngraph.delta import EdgeDelta, _pair_keys
from repro.graphs.graph import Graph, from_edges

_BITS = 32


def _half_edges(pairs: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(m, 2) canonical pairs → both directed half-edges (2m,) + (2m,)."""
    lo, hi = pairs[:, 0], pairs[:, 1]
    return np.concatenate([lo, hi]), np.concatenate([hi, lo])


def apply_graph_delta(g: Graph, delta: EdgeDelta) -> Graph:
    """Mutate the edge list: strict set semantics, canonical result.

    Every `remove` edge must exist and every `add` edge must not — the
    strictness `EdgeDelta.inverse()` relies on.  The result goes back
    through `from_edges`, so it is bit-identical (edge order, padding,
    `graph_content_key`) to loading the mutated graph fresh.
    """
    delta.check_bounds(g.n_nodes)
    if delta.is_empty:
        return g
    n = g.n_nodes
    s = np.asarray(g.senders)[: g.n_edges].astype(np.int64)
    r = np.asarray(g.receivers)[: g.n_edges].astype(np.int64)
    und = np.unique(np.stack([np.minimum(s, r), np.maximum(s, r)], axis=1),
                    axis=0).reshape(-1, 2)
    keys = _pair_keys(und, n)

    rem_keys = _pair_keys(delta.remove, n)
    missing = ~np.isin(rem_keys, keys)
    if missing.any():
        u, v = delta.remove[missing.argmax()]
        raise ValueError(
            f"delta removes {int(missing.sum())} edge(s) not in the graph "
            f"(first: ({int(u)}, {int(v)})) — deltas are strict set mutations"
        )
    add_keys = _pair_keys(delta.add, n)
    present = np.isin(add_keys, keys)
    if present.any():
        u, v = delta.add[present.argmax()]
        raise ValueError(
            f"delta adds {int(present.sum())} edge(s) already in the graph "
            f"(first: ({int(u)}, {int(v)})) — deltas are strict set mutations"
        )

    kept = und[~np.isin(keys, rem_keys)]
    new = np.concatenate([kept, delta.add], axis=0)
    return from_edges(new[:, 0], new[:, 1], n)


def _edit_tiles(
    tiles: np.ndarray,
    tidx: np.ndarray,    # (k,) tile index per half-edge
    u: np.ndarray,       # (k,) row vertex ids
    v: np.ndarray,       # (k,) column vertex ids
    T: int,
    *,
    set_bit: bool,
) -> None:
    """In-place cell edits in either storage format (detected by dtype)."""
    rloc, cloc = u % T, v % T
    if tiles.dtype == np.uint32:   # bitpack: word-level bit edits
        word, bit = cloc // _BITS, (cloc % _BITS).astype(np.uint32)
        if set_bit:
            np.bitwise_or.at(tiles, (tidx, rloc, word), np.uint32(1) << bit)
        else:
            np.bitwise_and.at(tiles, (tidx, rloc, word), ~(np.uint32(1) << bit))
    else:
        tiles[tidx, rloc, cloc] = 1 if set_bit else 0


def _patch_partition(tiled: BlockTiledGraph, delta: EdgeDelta) -> BlockTiledGraph:
    """A partitioned tiling's patch, on its cells (see module docstring)."""
    T, nb = tiled.tile_size, tiled.n_block_cols
    cells = tiling_cells(tiled).cells
    rem = cell_keys(*_half_edges(delta.remove), T, nb)
    pos = np.searchsorted(cells, rem)
    hit = pos < cells.shape[0]
    hit[hit] = cells[pos[hit]] == rem[hit]
    cells = np.delete(cells, pos[hit])
    add = np.unique(cell_keys(*_half_edges(delta.add), T, nb))
    pos = np.searchsorted(cells, add)
    present = pos < cells.shape[0]
    present[present] = cells[pos[present]] == add[present]
    cells = np.insert(cells, pos[~present], add[~present])
    return partition_from_cells(
        TileCells.from_cells(cells, T, nb), tiled.partition.threshold,
        tiled.n_nodes, tiled.storage,
    )


def apply_delta(tiled: BlockTiledGraph, delta: EdgeDelta) -> BlockTiledGraph:
    """Repack only the touched tiles of a `BlockTiledGraph`.

    Fast path — the delta lands entirely in existing tiles and drains none:
    the tile payload is edited in place on a host copy and `tile_rows` /
    `tile_cols` / `row_starts` are REUSED (same device arrays, no re-upload).
    Structural path — tiles are inserted (new block touched) and/or dropped
    (last edge removed) in row-major order and `row_starts` is recomputed
    from the new tile rows.  Either way the result equals
    `build_block_tiles(apply_graph_delta(g, delta))` bit-for-bit.

    Trusts its delta (bounds + strictness are `apply_graph_delta`'s checks,
    run by `Plan.apply_delta` on the same batch); a remove aimed at an
    absent edge is a silent no-op bit-clear here, so callers composing the
    two must apply the SAME canonical delta to both representations.
    A partitioned tiling patches its partition (`_patch_partition`).
    """
    delta.check_bounds(tiled.n_nodes)
    if delta.is_empty:
        return tiled
    if tiled.partition is not None:
        return _patch_partition(tiled, delta)
    T = tiled.tile_size
    nbc = tiled.n_block_cols
    nt = tiled.n_tiles

    rows_np = np.asarray(tiled.tile_rows)[:nt]
    cols_np = np.asarray(tiled.tile_cols)[:nt]
    tile_keys = rows_np.astype(np.int64) * nbc + cols_np   # sorted (row-major)

    add_u, add_v = _half_edges(delta.add)
    rem_u, rem_v = _half_edges(delta.remove)
    add_keys = (add_u // T) * np.int64(nbc) + (add_v // T)
    rem_keys = (rem_u // T) * np.int64(nbc) + (rem_v // T)

    new_keys = np.setdiff1d(np.unique(add_keys), tile_keys)
    if new_keys.size == 0:
        # ---- fast path candidate: all edits hit existing tiles ----------
        stored = np.array(tiled.tiles)                     # host copy, pad incl.
        ridx = np.searchsorted(tile_keys, rem_keys)        # (may be empty)
        if rem_keys.size:
            _edit_tiles(stored, ridx, rem_u, rem_v, T, set_bit=False)
        if add_keys.size:
            aidx = np.searchsorted(tile_keys, add_keys)
            _edit_tiles(stored, aidx, add_u, add_v, T, set_bit=True)
        # drain check over exactly the tiles the removes edited
        touched = np.unique(ridx)
        drained = touched[~stored[touched].any(axis=(1, 2))] \
            if touched.size else touched
        if drained.size == 0:
            return dataclasses.replace(tiled, tiles=jnp.asarray(stored))
        keep = np.ones(nt, bool)
        keep[drained] = False
        return _rebuild_index(tiled, stored[:nt][keep], tile_keys[keep])

    # ---- structural path: merge new (zero) tiles into the sorted list ---
    merged_keys = np.union1d(tile_keys, new_keys)
    n_merged = int(merged_keys.shape[0])
    if tiled.storage == "bitpack":
        shape = (n_merged, T, packed_words(T))
        merged = np.zeros(shape, np.uint32)
    else:
        merged = np.zeros((n_merged, T, T), np.int8)
    old_pos = np.searchsorted(merged_keys, tile_keys)
    merged[old_pos] = np.asarray(tiled.tiles)[:nt]
    rem_idx = np.searchsorted(merged_keys, rem_keys)       # (may be empty)
    if rem_keys.size:
        _edit_tiles(merged, rem_idx, rem_u, rem_v, T, set_bit=False)
    _edit_tiles(merged, np.searchsorted(merged_keys, add_keys),
                add_u, add_v, T, set_bit=True)
    # drain check over exactly the tiles the removes edited
    touched = np.unique(rem_idx)
    drained = touched[~merged[touched].any(axis=(1, 2))] \
        if touched.size else touched
    if drained.size:
        keep = np.ones(n_merged, bool)
        keep[drained] = False
        merged, merged_keys = merged[keep], merged_keys[keep]
    return _rebuild_index(tiled, merged, merged_keys)


def _rebuild_index(
    tiled: BlockTiledGraph, tiles: np.ndarray, keys: np.ndarray
) -> BlockTiledGraph:
    """Re-derive rows/cols/row_starts/padding from a sorted real-tile list —
    the O(n_tiles) tail of the structural path (never an edge scatter)."""
    nbc = tiled.n_block_cols
    n_real = int(tiles.shape[0])
    rows = (keys // nbc).astype(np.int32)
    cols = (keys % nbc).astype(np.int32)
    if n_real == 0:
        # mirror build_block_tiles' empty-graph shape: one zero tile at (0,0)
        tiles = np.zeros((1,) + tiles.shape[1:], tiles.dtype)
        rows = np.zeros(1, np.int32)
        cols = np.zeros(1, np.int32)

    counts = np.bincount(rows[: max(n_real, 1)] if n_real else [],
                         minlength=tiled.n_block_rows)
    row_starts = np.zeros(tiled.n_block_rows + 1, dtype=np.int32)
    np.cumsum(counts, out=row_starts[1:])

    target = padded_tile_count(n_real)
    stored = tiles.shape[0]
    if target > stored:
        last_row = rows[-1] if n_real else np.int32(0)
        tiles = np.concatenate(
            [tiles, np.zeros((target - stored,) + tiles.shape[1:], tiles.dtype)]
        )
        rows = np.concatenate(
            [rows, np.full(target - stored, last_row, np.int32)])
        cols = np.concatenate([cols, np.zeros(target - stored, np.int32)])
    return dataclasses.replace(
        tiled,
        tiles=jnp.asarray(tiles),
        tile_rows=jnp.asarray(rows),
        tile_cols=jnp.asarray(cols),
        row_starts=jnp.asarray(row_starts),
        n_tiles=n_real,
    )
