"""Pallas TPU kernel: embedding-bag (gather + weighted segment-reduce).

JAX has no native EmbeddingBag; the recsys path (DeepFM) builds it from
`jnp.take` + `segment_sum`.  This kernel is the TPU hot-path version: the
*index map itself* performs the gather — grid (B, K), and the table's
BlockSpec selects row `indices[b,k]` for step (b,k), so Pallas DMAs exactly
one (1, D) embedding row per step out of the HBM-resident table.  The output
block (1, D) stays VMEM-resident across the K inner steps (revisit
accumulation), giving the weighted bag-sum without any scatter.

This mirrors the BSR trick in tc_spmv: irregular access is pushed into
scalar-prefetched index maps, the compute stays dense and regular.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bag_kernel(idx_ref, tiles_row_ref, w_ref, out_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    row = tiles_row_ref[...].astype(jnp.float32)   # (1, D)
    w = w_ref[0, 0]
    out_ref[...] += w * row


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_bag_pallas(
    table: jnp.ndarray,     # (V, D) float
    indices: jnp.ndarray,   # (B, K) int32
    weights: jnp.ndarray,   # (B, K) float
    *,
    interpret: bool,
) -> jnp.ndarray:
    """Σ_k weights[b,k] · table[indices[b,k]]  ->  (B, D) float32."""
    B, K = indices.shape
    _, D = table.shape

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K),
        in_specs=[
            pl.BlockSpec((1, D), lambda b, k, idx: (idx[b * K + k], 0)),
            pl.BlockSpec((1, 1), lambda b, k, idx: (b, k)),
        ],
        out_specs=pl.BlockSpec((1, D), lambda b, k, idx: (b, 0)),
    )
    return pl.pallas_call(
        _bag_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.float32),
        interpret=interpret,
    )(indices.reshape(-1), table, weights.astype(jnp.float32))
