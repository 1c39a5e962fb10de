"""Pallas TPU kernel: block-tiled SpMV (the paper's phase-② WMMA listing).

One grid step per stored BSR tile, in block-row-major order:

  HBM layout                          VMEM working set per step
  tiles      (nt, T, T)   int8   ->   (1, T, T)  one adjacency tile
  rhs        (nbc·T, L)   f32    ->   (T, L)     the tile's RHS slab
  out        (nbr·T, L)   f32    ->   (T, L)     resident accumulator

With the default T=128, L=128 the working set is 128·128·(1+4+4) ≈ 144 KiB —
comfortably inside a v5e core's ~128 KiB/slot double-buffered VMEM budget at
bf16 RHS (switch `rhs` to bf16 to halve it; accumulation stays f32 via
`preferred_element_type`).  Both matmul dims are 128-multiples, so every
`jnp.dot` is exactly one MXU pass — the TPU equivalent of the paper's one
`mma_sync` per WMMA fragment.

TPU-native replacements for the paper's GPU mechanics (DESIGN.md §2):
  * per-row-per-tile atomics  -> tiles sorted by block-row; consecutive grid
    steps hitting the same output block accumulate in VMEM; `@pl.when` on the
    row transition zero-initialises the accumulator.
  * warp-level wave scheduling -> Pallas pipelines the HBM→VMEM DMAs of step
    i+1 under the MXU work of step i (automatic double buffering).
  * empty-C tile skipping      -> `col_flags` scalar prefetch: tiles whose RHS
    slab is all-zero skip the MXU op (`@pl.when`).  The DMA itself is also
    skippable by pointing the index_map at the previous block — that variant
    is `skip_dma=True` (hill-climb knob; both validated against the oracle).

The per-tile schedule (block row, block column, column flag) is scalar-
prefetched into SMEM; `kernels.tile_grid.tile_pallas_call` runs a tile list
longer than SMEM holds as several launches, resuming a block row cut by a
launch edge.

Storage axis (DESIGN.md §11): tiles arrive either dense int8 (T, T) or
bit-packed uint32 (T, W) with W = max(T//32, 1).  Packed tiles are unpacked
IN VMEM inside the kernel body, right after the DMA — HBM only ever carries
the 8×-smaller packed words, and with `skip_dma` the skipped-or-not transfer
shrinks by the same factor.  The format is detected from the tile dtype, so
call sites are storage-polymorphic.

Bitwise frontier mode (DESIGN.md §13): `tc_spmv_bits_pallas` and the fused
`tc_spmv_fused_bits_pallas` keep BOTH operands packed — tile words AND the
candidate vector as (nbc, W) uint32 words.  The MXU contraction is replaced
by `popcount(tile_word & cand_word) != 0` per row (the paper's N_c > 0 test
without the f32 accumulator), and phase ③ becomes pure word logic in the
fused epilogue.  No dense vector crosses HBM in either direction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.tiling import pack_frontier_column, unpack_tile_mask
from repro.kernels.tile_grid import TILES_PER_LAUNCH, tile_pallas_call

_STATIC = ("n_block_rows", "interpret", "skip_dma", "tiles_per_launch")


def _spmv_kernel(rows_ref, cols_ref, flags_ref, tiles_ref, rhs_ref, *refs,
                 packed: bool, tile_size: int, resume: bool):
    out_ref = refs[-1]
    i = pl.program_id(0)
    prev, row = rows_ref[i], rows_ref[i + 1]

    @pl.when(prev != row)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    if resume:                                     # row cut by the launch edge
        @pl.when((i == 0) & (prev == row))
        def _resume():
            out_ref[...] = refs[0][...]

    @pl.when(flags_ref[i] != 0)
    def _mma():
        a = tiles_ref[0]                           # (T, T) i8 | (T, W) u32
        if packed:                                 # in-VMEM bit→f32, post-DMA
            a = unpack_tile_mask(a, tile_size).astype(jnp.float32)
        else:
            a = a.astype(jnp.float32)              # (T, T) 0/1 adjacency tile
        b = rhs_ref[...].astype(jnp.float32)       # (T, L) packed RHS lanes
        out_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=_STATIC)
def tc_spmv_pallas(
    tiles: jnp.ndarray,       # (nt, T, T) int8, block-row-major
    tile_rows: jnp.ndarray,   # (nt,) int32, non-decreasing
    tile_cols: jnp.ndarray,   # (nt,) int32
    rhs: jnp.ndarray,         # (nbc*T, L) float
    n_block_rows: int,
    *,
    col_flags: jnp.ndarray | None = None,  # (nbc,) int32; None = all active
    interpret: bool,
    skip_dma: bool = False,
    tiles_per_launch: int = TILES_PER_LAUNCH,
) -> jnp.ndarray:
    """N = A @ rhs over BSR tiles. Returns (n_block_rows*T, L) float32.

    `tiles` may be dense int8 (nt, T, T) or bit-packed uint32 (nt, T, W) —
    the packed form DMAs 8× fewer bytes and unpacks in VMEM."""
    T = tiles.shape[1]
    L = rhs.shape[-1]
    (out,) = tile_pallas_call(
        functools.partial(
            _spmv_kernel, packed=tiles.dtype == jnp.uint32, tile_size=T
        ),
        tiles=tiles, tile_rows=tile_rows, tile_cols=tile_cols,
        col_flags=col_flags,
        inputs=[(rhs, (T, L), "col")],
        outputs=[(jax.ShapeDtypeStruct((n_block_rows * T, L), jnp.float32),
                  (T, L))],
        carried=[0], interpret=interpret, skip_dma=skip_dma,
        tiles_per_launch=tiles_per_launch,
    )
    return out


# ---------------------------------------------------------------------------
# fused phase ②+③ variant (DESIGN.md §6.3): the state update is applied in
# the SpMV epilogue on the LAST visit to each output block, so N_c never
# round-trips through HBM — the kernel emits the new (alive, in_mis) masks
# directly.
# ---------------------------------------------------------------------------

def _spmv_fused_kernel(
    rows_ref, cols_ref, flags_ref, tiles_ref, rhs_ref, cand_ref, alive_ref,
    *refs, packed: bool, tile_size: int, resume: bool,
):
    nc_ref, alive_out_ref, mis_out_ref = refs[-3:]
    i = pl.program_id(0)
    prev, row, nxt = rows_ref[i], rows_ref[i + 1], rows_ref[i + 2]

    @pl.when(prev != row)
    def _init():
        nc_ref[...] = jnp.zeros_like(nc_ref)

    if resume:                                     # row cut by the launch edge
        @pl.when((i == 0) & (prev == row))
        def _resume():
            nc_ref[...] = refs[0][...]

    @pl.when(flags_ref[i] != 0)
    def _mma():
        a = tiles_ref[0]
        if packed:                                 # in-VMEM bit→f32, post-DMA
            a = unpack_tile_mask(a, tile_size).astype(jnp.float32)
        else:
            a = a.astype(jnp.float32)
        b = rhs_ref[...].astype(jnp.float32)
        nc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(nxt != row)
    def _epilogue():
        # phase ③, paper's three rules — lock-free: own row block only
        cand = cand_ref[...] != 0                      # (T, 1) lanes
        alive = alive_ref[...] != 0
        hit = nc_ref[..., 0:1] > 0
        mis_out_ref[...] = cand.astype(jnp.int8)
        alive_out_ref[...] = (alive & ~cand & ~hit).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=_STATIC)
def tc_spmv_fused_pallas(
    tiles: jnp.ndarray,
    tile_rows: jnp.ndarray,
    tile_cols: jnp.ndarray,
    rhs: jnp.ndarray,          # (nbc*T, L): lane 0 = C, lane 1 = alive
    cand: jnp.ndarray,         # (nbr*T,) int8 — candidate mask per row block
    alive: jnp.ndarray,        # (nbr*T,) int8
    n_block_rows: int,
    *,
    col_flags: jnp.ndarray | None = None,
    interpret: bool,
    skip_dma: bool = False,
    tiles_per_launch: int = TILES_PER_LAUNCH,
):
    """Fused phase ②+③: returns (n_c (nbr*T, L) f32, new_alive i8, mis_add i8).

    Storage-polymorphic like the split kernel: bit-packed uint32 tiles DMA
    8× fewer bytes and unpack in VMEM inside the kernel body."""
    T = tiles.shape[1]
    L = rhs.shape[-1]
    n = n_block_rows * T
    n_c, new_alive, mis_add = tile_pallas_call(
        functools.partial(
            _spmv_fused_kernel, packed=tiles.dtype == jnp.uint32, tile_size=T
        ),
        tiles=tiles, tile_rows=tile_rows, tile_cols=tile_cols,
        col_flags=col_flags,
        inputs=[(rhs, (T, L), "col"),
                (cand.reshape(-1, 1), (T, 1), "row"),
                (alive.reshape(-1, 1), (T, 1), "row")],
        outputs=[(jax.ShapeDtypeStruct((n, L), jnp.float32), (T, L)),
                 (jax.ShapeDtypeStruct((n, 1), jnp.int8), (T, 1)),
                 (jax.ShapeDtypeStruct((n, 1), jnp.int8), (T, 1))],
        carried=[0], interpret=interpret, skip_dma=skip_dma,
        tiles_per_launch=tiles_per_launch,
    )
    return n_c, new_alive[:, 0], mis_add[:, 0]


# ---------------------------------------------------------------------------
# bitwise frontier kernels (DESIGN.md §13): packed words on BOTH sides of the
# contraction.  Per grid step the DMA moves one (T, W) tile and one (1, W)
# candidate word row — 32× less RHS traffic than the lane-packed f32 slab —
# and the "matmul" is popcount(AND) != 0 folded straight to a result bit.
# Word vectors ride as (nb, 1, W): the TPU compiler takes a block whose last
# two dims are (8k, 128k) or the array's own, so a one-row block of words
# needs the unit middle axis.
# ---------------------------------------------------------------------------

def _word_blocks(words: jnp.ndarray) -> jnp.ndarray:
    """(nb, W) frontier words -> (nb, 1, W), blocked (1, 1, W)."""
    return words.reshape(words.shape[0], 1, words.shape[1])


def _spmv_bits_kernel(rows_ref, cols_ref, flags_ref, tiles_ref, rhs_ref,
                      *refs, tile_size: int, resume: bool):
    out_ref = refs[-1]
    i = pl.program_id(0)
    prev, row = rows_ref[i], rows_ref[i + 1]

    @pl.when(prev != row)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    if resume:                                     # row cut by the launch edge
        @pl.when((i == 0) & (prev == row))
        def _resume():
            out_ref[...] = refs[0][...]

    @pl.when(flags_ref[i] != 0)
    def _and():
        # row v of the (T, W) tile is hit iff any word of tile[v] & C is
        # nonzero; the test reduces along lanes to a (T, 1) column, which
        # `pack_frontier_column` folds into a (1, W) word row
        a = tiles_ref[0]                          # (T, W) u32: row v's words
        c = rhs_ref[0]                            # (1, W) candidate words
        hit = jnp.max(((a & c) != 0).astype(jnp.int32), axis=1, keepdims=True)
        out_ref[0] |= pack_frontier_column(hit, tile_size)


@functools.partial(jax.jit, static_argnames=_STATIC)
def tc_spmv_bits_pallas(
    tiles_words: jnp.ndarray,  # (nt, T, W) uint32, block-row-major
    tile_rows: jnp.ndarray,    # (nt,) int32, non-decreasing
    tile_cols: jnp.ndarray,    # (nt,) int32
    rhs_words: jnp.ndarray,    # (nbc, W) uint32 — packed candidate vector
    n_block_rows: int,
    *,
    col_flags: jnp.ndarray | None = None,
    interpret: bool,
    skip_dma: bool = False,
    tiles_per_launch: int = TILES_PER_LAUNCH,
) -> jnp.ndarray:
    """hit = (A @ C) > 0 on packed words.  Returns (n_block_rows, W) uint32.

    Requires packed uint32 tiles (the bitwise mode exists to avoid ever
    touching the dense form; use `tiling.tiles_as_words` to convert)."""
    if tiles_words.dtype != jnp.uint32:
        raise ValueError(
            f"tc_spmv_bits_pallas needs packed uint32 tiles, got "
            f"{tiles_words.dtype} (convert via tiling.tiles_as_words)"
        )
    _, T, W = tiles_words.shape
    (out,) = tile_pallas_call(
        functools.partial(_spmv_bits_kernel, tile_size=T),
        tiles=tiles_words, tile_rows=tile_rows, tile_cols=tile_cols,
        col_flags=col_flags,
        inputs=[(_word_blocks(rhs_words), (1, 1, W), "col")],
        outputs=[(jax.ShapeDtypeStruct((n_block_rows, 1, W), jnp.uint32),
                  (1, 1, W))],
        carried=[0], interpret=interpret, skip_dma=skip_dma,
        tiles_per_launch=tiles_per_launch,
    )
    return out.reshape(n_block_rows, W)


def _spmv_fused_bits_kernel(
    rows_ref, cols_ref, flags_ref, tiles_ref, rhs_ref, cand_ref, alive_ref,
    *refs, tile_size: int, resume: bool,
):
    hit_ref, alive_out_ref, mis_out_ref = refs[-3:]
    i = pl.program_id(0)
    prev, row, nxt = rows_ref[i], rows_ref[i + 1], rows_ref[i + 2]

    @pl.when(prev != row)
    def _init():
        hit_ref[...] = jnp.zeros_like(hit_ref)

    if resume:                                     # row cut by the launch edge
        @pl.when((i == 0) & (prev == row))
        def _resume():
            hit_ref[...] = refs[0][...]

    @pl.when(flags_ref[i] != 0)
    def _and():
        a = tiles_ref[0]
        c = rhs_ref[0]
        hit = jnp.max(((a & c) != 0).astype(jnp.int32), axis=1, keepdims=True)
        hit_ref[0] |= pack_frontier_column(hit, tile_size)

    @pl.when(nxt != row)
    def _epilogue():
        # phase ③ as word logic — 32 vertices per op, own row block only
        cand = cand_ref[0]                        # (1, W)
        alive = alive_ref[0]
        mis_out_ref[0] = cand
        alive_out_ref[0] = alive & ~cand & ~hit_ref[0]


@functools.partial(jax.jit, static_argnames=_STATIC)
def tc_spmv_fused_bits_pallas(
    tiles_words: jnp.ndarray,  # (nt, T, W) uint32
    tile_rows: jnp.ndarray,
    tile_cols: jnp.ndarray,
    cand_words: jnp.ndarray,   # (nbc, W) uint32 — C, the SpMV RHS
    alive_words: jnp.ndarray,  # (nbr, W) uint32
    n_block_rows: int,
    *,
    col_flags: jnp.ndarray | None = None,
    interpret: bool,
    skip_dma: bool = False,
    tiles_per_launch: int = TILES_PER_LAUNCH,
):
    """Fused ②+③ on packed words.

    Returns (hit_words, new_alive_words, mis_add_words), each
    (n_block_rows, W) uint32.  `cand_words` plays both roles: SpMV RHS
    (indexed by block column) and phase-③ own-state input (indexed by block
    row) — same array, two BlockSpecs."""
    if tiles_words.dtype != jnp.uint32:
        raise ValueError(
            f"tc_spmv_fused_bits_pallas needs packed uint32 tiles, got "
            f"{tiles_words.dtype} (convert via tiling.tiles_as_words)"
        )
    _, T, W = tiles_words.shape
    cand3 = _word_blocks(cand_words)
    words = (jax.ShapeDtypeStruct((n_block_rows, 1, W), jnp.uint32), (1, 1, W))
    outs = tile_pallas_call(
        functools.partial(_spmv_fused_bits_kernel, tile_size=T),
        tiles=tiles_words, tile_rows=tile_rows, tile_cols=tile_cols,
        col_flags=col_flags,
        inputs=[(cand3, (1, 1, W), "col"),      # C twice: RHS + own-row role
                (cand3, (1, 1, W), "row"),
                (_word_blocks(alive_words), (1, 1, W), "row")],
        outputs=[words] * 3,
        carried=[0], interpret=interpret, skip_dma=skip_dma,
        tiles_per_launch=tiles_per_launch,
    )
    hit, new_alive, mis_add = (o.reshape(n_block_rows, W) for o in outs)
    return hit, new_alive, mis_add
