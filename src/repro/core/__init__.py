"""TC-MIS core: the paper's contribution as composable JAX modules."""
from repro.core.engine import (
    ENGINES,
    EngineContext,
    MISRoundState,
    RoundEngine,
    block_col_flags,
    engine_names,
    get_engine,
    register_engine,
)
from repro.core.heuristics import HEURISTICS, Priorities, make_priorities
from repro.core.luby import MISResult, luby_mis
from repro.core.ecl_mis import ecl_mis
from repro.core.tc_mis import TCMISConfig, tc_mis
from repro.core.tiling import (
    STORAGES,
    BlockTiledGraph,
    TilePartition,
    attach_partition,
    build_block_tiles,
    gather_frontier_bits,
    pack_tile_bits,
    pack_vertex_vector,
    packed_words,
    partition_tiles,
    tile_nnz,
    tile_stats,
    unpack_tile_bits,
    unpack_vertex_vector,
)
from repro.core.validate import (
    cardinality,
    is_independent,
    is_maximal,
    is_valid_mis,
    is_valid_mis_jit,
)
from repro.core.distributed import (
    DistConfig,
    ShardedTiledGraph,
    build_distributed_mis,
    shard_tiled,
)

__all__ = [
    "ENGINES", "EngineContext", "MISRoundState", "RoundEngine",
    "block_col_flags", "engine_names", "get_engine", "register_engine",
    "HEURISTICS", "Priorities", "make_priorities",
    "MISResult", "luby_mis", "ecl_mis",
    "TCMISConfig", "tc_mis",
    "STORAGES", "BlockTiledGraph", "TilePartition", "attach_partition",
    "build_block_tiles", "gather_frontier_bits", "pack_tile_bits",
    "pack_vertex_vector", "packed_words", "partition_tiles", "tile_nnz",
    "tile_stats", "unpack_tile_bits", "unpack_vertex_vector",
    "cardinality", "is_independent", "is_maximal", "is_valid_mis",
    "is_valid_mis_jit",
    "DistConfig", "ShardedTiledGraph", "build_distributed_mis", "shard_tiled",
]
