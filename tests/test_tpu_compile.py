"""Compile every main-path Pallas kernel for a TPU v5e that is described,
not attached: the TPU compiler refuses here what interpret mode would let
through (block shapes off the (8, 128) tiling, scalar memory, VMEM).

Shapes are those of the full-scale G2 roadNet-PA stand-in (1,090,980
vertices, natural order, no hybrid split): its block rows and stored tiles
at T = 16 (the auto-T choice) and at T = 128.  The T = 16 tile list is
longer than scalar memory holds, so it compiles as several launches
(`repro.kernels.tile_grid`).
The topology is described inside a module fixture only, so every xdist
worker collects the same tests and only the worker running this file loads
the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.tc_neighbor_max import (
    tc_neighbor_max_bits_pallas,
    tc_neighbor_max_pallas,
)
from repro.kernels.tc_spmv import (
    tc_spmv_bits_pallas,
    tc_spmv_fused_bits_pallas,
    tc_spmv_fused_pallas,
    tc_spmv_pallas,
)

G2_FULL_NODES = 1_090_980
G2_FULL_TILES = {16: 476_920, 128: 59_616}   # stored, padded to 8
LANES = 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # compiles for a described chip land in the persistent cache but cannot
    # be read back without one: keep the cache off for this file
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _lowerings(name, T, spec):
    """(jitted kernel, args, kwargs) for one kernel at G2 widths."""
    nbr = -(-G2_FULL_NODES // T)
    W = max(T // 32, 1)
    i32, u32 = jnp.int32, jnp.uint32
    nt = G2_FULL_TILES[T]
    rows, cols = spec((nt,), i32), spec((nt,), i32)
    flags = spec((nbr,), i32)
    int8_tiles = spec((nt, T, T), jnp.int8)
    word_tiles = spec((nt, T, W), u32)
    words = spec((nbr, W), u32)
    rhs = spec((nbr * T, LANES), jnp.float32)
    mask8 = spec((nbr * T,), jnp.int8)
    kw = dict(interpret=False)
    return {
        "spmv_int8": (tc_spmv_pallas,
                      (int8_tiles, rows, cols, rhs, nbr),
                      dict(kw, col_flags=flags)),
        "spmv_bitpack": (tc_spmv_pallas,
                         (word_tiles, rows, cols, rhs, nbr),
                         dict(kw, col_flags=flags)),
        "fused_int8": (tc_spmv_fused_pallas,
                       (int8_tiles, rows, cols, rhs, mask8, mask8, nbr),
                       dict(kw, col_flags=flags)),
        "fused_bitpack": (tc_spmv_fused_pallas,
                          (word_tiles, rows, cols, rhs, mask8, mask8, nbr),
                          dict(kw, col_flags=flags, skip_dma=True)),
        "spmv_bits": (tc_spmv_bits_pallas,
                      (word_tiles, rows, cols, words, nbr),
                      dict(kw, col_flags=flags)),
        "fused_bits": (tc_spmv_fused_bits_pallas,
                       (word_tiles, rows, cols, words, words, nbr),
                       dict(kw, col_flags=flags, skip_dma=True)),
        "nbr_max_int8": (tc_neighbor_max_pallas,
                         (int8_tiles, rows, cols, spec((nbr * T,), i32), nbr),
                         kw),
        "nbr_max_bitpack": (tc_neighbor_max_pallas,
                            (word_tiles, rows, cols, spec((nbr * T,), i32),
                             nbr),
                            kw),
        "plane_select": (tc_neighbor_max_bits_pallas,
                         (word_tiles, rows, cols, spec((31, nbr, W), u32),
                          words, nbr),
                         dict(kw, signed=False)),
        "plane_resolve": (tc_neighbor_max_bits_pallas,
                          (word_tiles, rows, cols, spec((32, nbr, W), u32),
                           words, nbr),
                          dict(kw, signed=True)),
    }[name]


@pytest.mark.parametrize("T", [16, 128])
@pytest.mark.parametrize("name", [
    "spmv_int8", "spmv_bitpack", "fused_int8", "fused_bitpack",
    "spmv_bits", "fused_bits", "nbr_max_int8", "nbr_max_bitpack",
    "plane_select", "plane_resolve",
])
def test_kernel_compiles_for_v5e(one_chip, name, T):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args, kwargs = _lowerings(name, T, spec)
    compiled = fn.lower(*args, **kwargs).compile()
    # a Mosaic kernel, not an interpreted loop of XLA ops
    assert "tpu_custom_call" in compiled.as_text()
