"""Incremental MIS repair: re-enter the round engine from a warm state.

The frontier-driven TC line (BLEST, Graph Traversal on Tensor Cores) rests
on one observation: delta-shaped work is still SpMV-shaped.  The same holds
for MIS repair.  After an `EdgeDelta`, the prior solution is *almost* right
— only the delta endpoints and their neighbourhoods can be wrong — so
instead of a cold re-solve we seed `MISRoundState` with the prior solution
and hand the round engine a candidate set that is just the dirty frontier
(DESIGN.md §12):

  in_mis₀ = prior \\ dirty       dirty = delta endpoints.  Every NEW edge
                                 runs between dirty vertices, so the seed
                                 set is independent in the mutated graph
                                 by construction — eviction needs no
                                 conflict search.
  alive₀  = ~in_mis₀ & ~(A·in_mis₀ > 0)
                                 one SpMV pass over the PATCHED
                                 representation, on the configured
                                 engine's OWN phase-② substrate
                                 (`_covered`: Pallas kernel / segment ops /
                                 jnp oracle) — recovers exactly the
                                 vertices the seed set no longer
                                 dominates: evicted dirty vertices, their
                                 orphaned neighbours, and anything
                                 uncovered by a removed edge.

From there the unmodified engine round body (`engine.step` — any
registered engine) runs to convergence: candidates spread only through the
alive set, so a small delta converges in a handful of rounds while the
untouched bulk of the graph never re-enters phase ①.  Convergence yields a
full valid MIS of the mutated graph — maximality is global because alive₀
is computed globally, not guessed from a k-hop ball.

An EMPTY warm frontier runs zero rounds (`lax.while_loop` fails on entry),
which is what makes `repair="incremental"` on an empty delta bit-identical
to the prior (= cold) solution, per the Solver's repair contract.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import (
    SegmentEngine,
    TiledPallasEngine,
    _covered_rows,
    _covered_vertices,
    get_engine,
    resolve_frontier,
    tile_spmv,
    tile_spmv_bits,
)
from repro.core.heuristics import Priorities
from repro.core.luby import MISResult
from repro.core.tc_mis import _tc_mis_impl
from repro.core.tiling import (
    BlockTiledGraph,
    gather_frontier_bits,
    pack_frontier_words,
    pack_vertex_vector,
    tiles_as_words,
)
from repro.graphs.graph import Graph
from repro.obs import metrics as obs_metrics


def note_repair(mode: str, *, dirty_frac: float = 0.0) -> None:
    """Record one repair-mode decision in the process metrics registry
    (repro.obs).  EAGER-ONLY by contract: the Solver calls this where the
    mode is decided (before jit dispatch) — never from inside `repair_mis`
    or `warm_state`, which run under a trace and would count compiles, not
    repairs."""
    obs_metrics.counter(f"repair.{mode}").inc()
    obs_metrics.histogram("repair.dirty_frac").observe(dirty_frac)


def dirty_mask(n_nodes: int, touched: np.ndarray) -> np.ndarray:
    """(n_nodes,) bool host vector flagging the delta endpoints — the seed
    of the repair frontier (`EdgeDelta.touched()`, already in plan ids)."""
    mask = np.zeros(n_nodes, dtype=bool)
    if touched.size:
        mask[touched] = True
    return mask


def _covered(config, g: Graph, tiled: BlockTiledGraph, in_mis0) -> jnp.ndarray:
    """(n_nodes,) bool — which vertices the seed set dominates (A·S > 0),
    computed on the CONFIGURED engine's own phase-② substrate: the Pallas
    kernel for the `*_pallas` engines (packed tiles unpack in VMEM, never
    in HBM — the same discipline Guard 3 enforces on the rest of the delta
    path), the segment ops for the CC baseline (no tiles touched), the jnp
    oracle for `tiled_ref` and custom engines.  Counts are exact integers
    in every substrate, so the warm state is engine-independent."""
    n = g.n_nodes
    engine = get_engine(config.backend)
    if isinstance(engine, SegmentEngine):
        from repro.core.spmv import neighbor_any_segment

        return neighbor_any_segment(g, in_mis0[:n])
    part = tiled.partition
    if part is not None:
        # hybrid: the dense sub-tiling on the same substrate (its rows with
        # no tile masked off, as in the round body), OR the COO tail
        x = pack_vertex_vector(in_mis0[:n].astype(jnp.int32), tiled)
        hits = jax.ops.segment_max(
            x[part.sp_cols], part.sp_rows, num_segments=tiled.n_padded + 1
        )[:n] > 0
        dense = _covered(config, g, part.dense, in_mis0)
        return hits | (dense & _covered_vertices(part.dense)[:n])
    if isinstance(engine, TiledPallasEngine):   # incl. the fused subclass
        from repro.kernels.ops import tc_spmv

        rhs = jnp.zeros((tiled.n_padded, config.lanes), dtype=jnp.float32)
        rhs = rhs.at[:, 0].set(pack_vertex_vector(
            in_mis0.astype(jnp.float32), tiled
        ))
        return tc_spmv(tiled, rhs, skip_dma=config.skip_dma)[:n, 0] > 0
    rhs = pack_vertex_vector(in_mis0.astype(jnp.float32), tiled)[:, None]
    return tile_spmv(
        tiled.tiles, tiled.tile_rows, tiled.tile_cols, rhs,
        tiled.n_block_rows, tiled.tile_size,
    )[:n, 0] > 0


def _covered_bits(config, engine, tiled: BlockTiledGraph, in_mis_words) -> jnp.ndarray:
    """(nbc, W) uint32 — the packed form of `_covered`: hit words of the
    seed-set SpMV, on the engine's own bitwise phase-② substrate.  Only
    tile-schedule engines reach here (`resolve_frontier` never says bitwise
    for the segment engine)."""
    part = tiled.partition
    if part is not None:
        T = tiled.tile_size
        bit = gather_frontier_bits(in_mis_words, part.sp_cols, T)
        hit = jax.ops.segment_max(
            bit.astype(jnp.uint32), part.sp_rows,
            num_segments=tiled.n_padded + 1,
        )[:-1]
        dense = _covered_bits(config, engine, part.dense, in_mis_words)
        return pack_frontier_words(hit, T) | jnp.where(
            _covered_rows(part.dense)[:, None], dense, jnp.uint32(0))
    if isinstance(engine, TiledPallasEngine):   # incl. the fused subclass
        from repro.kernels.ops import tc_spmv_bits

        return tc_spmv_bits(
            tiled, in_mis_words,
            tiles_words=tiles_as_words(tiled.tiles, tiled.tile_size),
            skip_dma=config.skip_dma,
        )
    return tile_spmv_bits(
        tiles_as_words(tiled.tiles, tiled.tile_size),
        tiled.tile_rows, tiled.tile_cols, in_mis_words,
        tiled.n_block_rows, tiled.tile_size,
    )


def warm_state(
    g: Graph,
    tiled: BlockTiledGraph,
    config,
    prior_in_mis: jnp.ndarray,   # (n_nodes,) bool, plan ids, valid pre-delta MIS
    dirty: jnp.ndarray,          # (n_nodes,) bool — delta endpoints
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(alive₀, in_mis₀) for the warm re-entry.

    Dense runs get (n_nodes,) bool vectors; bitwise runs (the resolved
    frontier of this config × storage — same policy `_setup` applies) get
    (nbc, W) uint32 word pairs that `_tc_mis_impl` accepts pre-packed, so
    the warm state never round-trips through a dense frontier on its way
    into the packed round loop.

    Pure jnp/Pallas over the PATCHED representation, so the Solver jits it
    together with the convergence loop — warm-start construction costs one
    SpMV (`_covered`/`_covered_bits`, on the configured engine's substrate)
    inside the same compiled program.
    """
    n = tiled.n_nodes
    in_mis0 = prior_in_mis[:n].astype(bool) & ~dirty[:n].astype(bool)
    engine = get_engine(config.backend)
    if resolve_frontier(config, engine, storage=tiled.storage) == "bitwise":
        T = tiled.tile_size
        in_mis_w = pack_frontier_words(pack_vertex_vector(in_mis0, tiled), T)
        hit_w = _covered_bits(config, engine, tiled, in_mis_w)
        # ~in_mis_w/~hit_w set the PADDING bits too — mask with the real-
        # vertex words or dead padding slots would wake up as alive.
        real_w = pack_frontier_words(jnp.arange(tiled.n_padded) < n, T)
        alive_w = real_w & ~in_mis_w & ~hit_w
        return alive_w, in_mis_w
    alive0 = ~in_mis0 & ~_covered(config, g, tiled, in_mis0)
    return alive0, in_mis0


def repair_mis(
    g: Graph,                    # the PATCHED graph (plan ids)
    tiled: BlockTiledGraph,      # its patched tiling
    key: jax.Array,
    config,                      # SolveOptions (or any engine cfg bundle)
    prior_in_mis: jnp.ndarray,   # (n_nodes,) bool — pre-delta solution
    dirty: jnp.ndarray,          # (n_nodes,) bool — delta endpoints
    *,
    priorities: Optional[Priorities] = None,
) -> MISResult:
    """Warm-started solve of the mutated graph on the configured engine.

    `prior_in_mis` must be a valid MIS of the PRE-delta graph (the Solver
    passes its own last result); the repaired result is then a valid MIS of
    the patched graph for every registered engine and either storage.
    Priorities default to the same construction a cold solve of the patched
    graph would use (same heuristic, same key, the NEW degree vector), so
    an empty delta repairs to exactly the cold answer.  Jit-compatible with
    `config` static — the Solver wraps this whole call in one `jax.jit`.

    With `config.telemetry` the return is `_tc_mis_impl`'s `(result,
    telemetry_buffer)` pair — the round buffer passes through this seam
    untouched, so repaired solves carry per-round series exactly like cold
    ones (the warm loop's row 0 is the first REPAIR round).
    """
    alive0, in_mis0 = warm_state(g, tiled, config, prior_in_mis, dirty)
    return _tc_mis_impl(
        g, tiled, key, config,
        priorities=priorities, alive0=alive0, in_mis0=in_mis0,
    )
