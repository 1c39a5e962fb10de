"""Pallas TPU kernel: tiled masked neighbour-max (beyond-paper phase ①).

The paper leaves phase ① (`Max_Np(v) = max_{u∈N(v)∩A} P(u)`) on CUDA cores —
and its own profile shows that after phase ② is tensorised, phase ① dominates
(83.1 % of TC-MIS runtime on G3/H200).  This kernel moves phase ① onto the
*same* BSR schedule as the SpMV: one grid step per tile, masked max over the
tile's columns, max-accumulated into a resident (1, T) output block.

Max has no MXU form, so this is VPU work — but it reads the identical tile
stream as `tc_spmv`, so on TPU the two kernels are bandwidth-twins and the
whole MIS round becomes tile-regular (DESIGN.md §6.1).

Priorities are int32; "dead" columns are encoded by the caller as _NEG
(−2^30) *before* the call, which keeps the kernel a pure max-reduce.

Storage axis (DESIGN.md §11): bit-packed uint32 tiles are supported exactly
as in `tc_spmv` — the DMA carries packed words, the kernel body bit-extracts
the VMEM-resident block straight to the bool mask the masked max needs
(`unpack_tile_mask` — no int8 intermediate, the cast that made the packed
path lose to int8 pre-§13).

Bitwise frontier mode (DESIGN.md §13): `tc_neighbor_max_bits_pallas` is the
priority-plane scan — tiles stay packed words end-to-end, the mask arrives
as (nbc, W) uint32 words, and the max is reconstructed bit-by-bit from a
static stack of priority planes.  (The jnp engine path runs the same scan
collapsed into one clz pass over priority-sorted bit order; the plane form
is the TPU-native formulation — W-word vector ops per plane, no gathers.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.tiling import unpack_tile_mask
from repro.kernels.tile_grid import TILES_PER_LAUNCH, tile_pallas_call

_NEG = -(1 << 30)  # plain int: jnp scalars would be captured as kernel consts


def _nbr_max_kernel(rows_ref, cols_ref, flags_ref, tiles_ref, pm_ref, *refs,
                    packed: bool, tile_size: int, resume: bool):
    out_ref = refs[-1]
    i = pl.program_id(0)
    prev, row = rows_ref[i], rows_ref[i + 1]

    @pl.when(prev != row)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, _NEG)

    if resume:                                # row cut by the launch edge
        @pl.when((i == 0) & (prev == row))
        def _resume():
            out_ref[...] = refs[0][...]

    tile = tiles_ref[0]                       # (T, T): row v, col u
    if packed:                                # in-VMEM bit→bool, post-DMA
        mask = unpack_tile_mask(tile, tile_size)
    else:
        mask = tile.astype(jnp.int32) != 0
    pm = pm_ref[0]                            # (1, T) masked priorities
    vals = jnp.where(mask, pm, _NEG)          # broadcast over rows
    # the row max lands as a (T, 1) column — the output block's layout, so
    # no (T, 1) -> (1, T) transpose reaches the TPU compiler
    out_ref[...] = jnp.maximum(out_ref[...], vals.max(axis=1, keepdims=True))


@functools.partial(
    jax.jit, static_argnames=("n_block_rows", "interpret", "tiles_per_launch")
)
def tc_neighbor_max_pallas(
    tiles: jnp.ndarray,       # (nt, T, T) int8 | (nt, T, W) uint32, row-major
    tile_rows: jnp.ndarray,   # (nt,) int32, non-decreasing
    tile_cols: jnp.ndarray,   # (nt,) int32
    pm: jnp.ndarray,          # (nbc*T,) int32 — priorities, _NEG where masked
    n_block_rows: int,
    *,
    interpret: bool,
    tiles_per_launch: int = TILES_PER_LAUNCH,
) -> jnp.ndarray:
    """Max_Np over BSR tiles. Returns (n_block_rows*T,) int32 (_NEG = none).

    Priorities enter as (nbc, 1, T) lane rows and the max leaves as a
    (n_block_rows·T, 1) column: both blocks end in dims the TPU compiler
    accepts, (1, T) and (T, 1) equal to the array's own."""
    T = tiles.shape[1]
    (out,) = tile_pallas_call(
        functools.partial(
            _nbr_max_kernel, packed=tiles.dtype == jnp.uint32, tile_size=T
        ),
        tiles=tiles, tile_rows=tile_rows, tile_cols=tile_cols, col_flags=None,
        inputs=[(pm.reshape(-1, 1, T), (1, 1, T), "col")],
        outputs=[(jax.ShapeDtypeStruct((n_block_rows * T, 1), jnp.int32),
                  (T, 1))],
        carried=[0], interpret=interpret, tiles_per_launch=tiles_per_launch,
    )
    return out.reshape(n_block_rows * T)


def _nbr_max_bits_kernel(rows_ref, cols_ref, flags_ref, tiles_ref, planes_ref,
                         mask_ref, *refs, n_bits: int, signed: bool,
                         tile_size: int, resume: bool):
    """Priority-plane scan over one packed tile (DESIGN.md §13).

    `cur` tracks the surviving neighbour set per tile row; plane b (static
    unroll, high→low) intersects it with "columns whose priority has bit b".
    A nonempty intersection fixes bit b of the max and narrows `cur`; empty
    leaves both.  After all planes `maxv` IS the masked max — never any
    priority value materialised per column, only word AND/OR."""
    out_ref = refs[-1]
    i = pl.program_id(0)
    prev, row = rows_ref[i], rows_ref[i + 1]

    @pl.when(prev != row)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, _NEG)

    if resume:                                # row cut by the launch edge
        @pl.when((i == 0) & (prev == row))
        def _resume():
            out_ref[...] = refs[0][...]

    def any_word(x):                          # (T, W) -> (T, 1) bool
        return jnp.max((x != 0).astype(jnp.int32), axis=1, keepdims=True) > 0

    a = tiles_ref[0]                          # (T, W) uint32: row v's words
    planes = planes_ref[0]                    # (n_bits, W): this column's planes
    cur = a & mask_ref[0]                     # (T, W), mask (1, W) broadcast
    nonempty = any_word(cur)                  # (T, 1)
    maxv = jnp.zeros((tile_size, 1), jnp.int32)
    for b in range(n_bits - 1, -1, -1):
        inter = cur & planes[b:b + 1, :]      # (T, W) ∩ plane b's (1, W)
        has = any_word(inter)
        # bit b of the max; b = 31 lands in the sign bit, which is what the
        # bitcast of the biased uint32 max needs
        maxv = maxv | (has.astype(jnp.int32) << b)
        cur = jnp.where(has, inter, cur)
    if signed:
        # planes were sign-biased (int32 ^ 0x80000000) so bit-serial max is
        # order-correct for negative priorities; un-bias on the way out.
        vals = maxv ^ (-(1 << 31))
    else:
        vals = maxv
    vals = jnp.where(nonempty, vals, _NEG)
    out_ref[...] = jnp.maximum(out_ref[...], vals)


@functools.partial(
    jax.jit,
    static_argnames=("n_block_rows", "signed", "interpret", "tiles_per_launch"),
)
def tc_neighbor_max_bits_pallas(
    tiles_words: jnp.ndarray,  # (nt, T, W) uint32 — standard bit layout
    tile_rows: jnp.ndarray,    # (nt,) int32, non-decreasing
    tile_cols: jnp.ndarray,    # (nt,) int32
    planes: jnp.ndarray,       # (n_bits, nbc, W) uint32 — static per solve
    mask_words: jnp.ndarray,   # (nbc, W) uint32 — per-round packed mask
    n_block_rows: int,
    *,
    signed: bool = False,
    interpret: bool,
    tiles_per_launch: int = TILES_PER_LAUNCH,
) -> jnp.ndarray:
    """Bitwise Max_Np: plane-scan form.  Returns (n_block_rows*T,) int32.

    The priority stack is (n_bits, nbc, W) packed planes from
    `core.tiling.pack_priority_planes` (`signed=True` iff the planes were
    sign-biased there).  Per grid step the DMA moves one packed tile, the
    block-column's plane column and its mask word — all uint32 words; no
    dense frontier or priority vector ever crosses HBM.  The kernel reads
    the stack block-column-major, (nbc, n_bits, W), so that one column's
    planes form a block the TPU compiler accepts."""
    if tiles_words.dtype != jnp.uint32:
        raise ValueError(
            f"tc_neighbor_max_bits_pallas needs packed uint32 tiles, got "
            f"{tiles_words.dtype} (convert via tiling.tiles_as_words)"
        )
    _, T, W = tiles_words.shape
    n_bits = int(planes.shape[0])
    nbc = int(mask_words.shape[0])
    (out,) = tile_pallas_call(
        functools.partial(
            _nbr_max_bits_kernel, n_bits=n_bits, signed=signed, tile_size=T
        ),
        tiles=tiles_words, tile_rows=tile_rows, tile_cols=tile_cols,
        col_flags=None,
        inputs=[(jnp.transpose(planes, (1, 0, 2)), (1, n_bits, W), "col"),
                (mask_words.reshape(nbc, 1, W), (1, 1, W), "col")],
        outputs=[(jax.ShapeDtypeStruct((n_block_rows * T, 1), jnp.int32),
                  (T, 1))],
        carried=[0], interpret=interpret, tiles_per_launch=tiles_per_launch,
    )
    return out.reshape(n_block_rows * T)
