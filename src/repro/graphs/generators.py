"""Synthetic graph generators, structurally matched to the paper's suite.

SuiteSparse is not available offline, so each of the paper's eight graphs
(Table 1) gets a generator that reproduces its *structure class* — degree
distribution shape and |E|/|V| — at any scale:

  G1 amazon0302        co-purchase      -> preferential_attachment (m≈4)
  G2 roadNet-PA        road network     -> grid2d (avg deg ≈ 2.7)
  G3 delaunay_n19      planar mesh      -> delaunay_like (deg ≈ 5.7, regular)
  G4 wiki-Talk         power-law hubs   -> powerlaw (skewed, |E|/|V| ≈ 4.0)
  G5 web-Google        web crawl        -> web_like (clustered power-law)
  G6 web-BerkStan      dense web crawl  -> web_like (higher m)
  G7 soc-LiveJournal1  social           -> preferential_attachment (m≈7)
  G8 kron_g500-logn21  Kronecker        -> rmat (Graph500 a,b,c,d, edge
                                            factor 48)

Wall-clock benchmarks run the *reduced* scale (CPU-tractable).  The
vectorised generators (G2 `grid2d`, G3 `delaunay_like`, G8 `rmat`) also
build the *full* published |V| as host arrays — the chip runs solve them; the others
loop in Python per vertex (or go through networkx), so their full scale
exists only as dry-run shape specs.  Generators are numpy, deterministic in
``seed``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

import numpy as np

from repro.graphs.graph import Graph, from_edges


# --------------------------------------------------------------------------
# generators (all return Graph; all deterministic in seed)
# --------------------------------------------------------------------------

def grid2d(n_rows: int, n_cols: int, seed: int = 0, diag_frac: float = 0.05) -> Graph:
    """Road-network stand-in: 2-D lattice with a sprinkle of diagonal shortcuts.

    Average degree ≈ 2·(2 + diag_frac) / ... ≈ 2.7 for small diag_frac, matching
    roadNet-PA's |E|/|V| = 2.7 (counting undirected edges once).
    """
    n = n_rows * n_cols
    idx = np.arange(n).reshape(n_rows, n_cols)
    right = np.stack([idx[:, :-1].ravel(), idx[:, 1:].ravel()], axis=1)
    down = np.stack([idx[:-1, :].ravel(), idx[1:, :].ravel()], axis=1)
    edges = [right, down]
    if diag_frac > 0:
        rng = np.random.default_rng(seed)
        n_diag = int(diag_frac * n)
        rr = rng.integers(0, n_rows - 1, n_diag)
        cc = rng.integers(0, n_cols - 1, n_diag)
        edges.append(np.stack([idx[rr, cc], idx[rr + 1, cc + 1]], axis=1))
    e = np.concatenate(edges, axis=0)
    return from_edges(e[:, 0], e[:, 1], n)


def rmat(
    scale: int,
    edge_factor: int = 16,
    a: float = 0.57,
    b: float = 0.19,
    c: float = 0.19,
    seed: int = 0,
) -> Graph:
    """R-MAT / Kronecker generator with Graph500 defaults (kron_g500 stand-in).

    `edge_factor · 2^scale` samples, each bit level drawn from the
    a/b/c/d quadrant split; vertex ids then permuted, self-loops dropped
    and duplicates merged (`from_edges`), as the Graph500 generator's
    output is before SuiteSparse stored kron_g500-logn*.  Duplicates are
    common on the hubs, so the edge count lands well under the samples:
    the published kron_g500-logn21 (91,040,932 edges at scale 21) takes
    edge factor 48."""
    n = 1 << scale
    m = n * edge_factor
    rng = np.random.default_rng(seed)
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    for i in range(scale):
        bit = 1 << i
        r1 = rng.random(m)
        r2 = rng.random(m)
        src_bit = r1 > ab
        dst_bit = np.where(src_bit, r2 > c_norm, r2 > a_norm)
        src |= bit * src_bit
        dst |= bit * dst_bit
    # permute vertex ids so locality is not an artefact of generation order
    perm = rng.permutation(n)
    return from_edges(perm[src], perm[dst], n)


def powerlaw(n: int, avg_deg: float = 4.0, exponent: float = 2.1, seed: int = 0) -> Graph:
    """Configuration-model power-law graph (wiki-Talk stand-in: hubby, skewed)."""
    rng = np.random.default_rng(seed)
    # Zipf-ish degree sequence, clipped so the config model terminates.
    raw = rng.zipf(exponent, n).astype(np.float64)
    raw = np.minimum(raw, np.sqrt(n))
    deg = np.maximum(1, np.round(raw * (avg_deg * n) / raw.sum())).astype(np.int64)
    stubs = np.repeat(np.arange(n), deg)
    rng.shuffle(stubs)
    if stubs.shape[0] % 2:
        stubs = stubs[:-1]
    half = stubs.shape[0] // 2
    return from_edges(stubs[:half], stubs[half:], n)


def delaunay_like(n: int, seed: int = 0) -> Graph:
    """Planar Delaunay triangulation of uniform points (delaunay_n19 stand-in)."""
    from scipy.spatial import Delaunay

    rng = np.random.default_rng(seed)
    pts = rng.random((n, 2))
    tri = Delaunay(pts)
    simplices = tri.simplices
    e = np.concatenate(
        [simplices[:, [0, 1]], simplices[:, [1, 2]], simplices[:, [2, 0]]], axis=0
    )
    return from_edges(e[:, 0], e[:, 1], n)


def preferential_attachment(n: int, m: int = 4, seed: int = 0) -> Graph:
    """Barabási–Albert (amazon / LiveJournal stand-in), vectorised numpy."""
    rng = np.random.default_rng(seed)
    targets = np.arange(m, dtype=np.int64)
    src_all = np.empty((n - m) * m, dtype=np.int64)
    dst_all = np.empty((n - m) * m, dtype=np.int64)
    # repeated-nodes trick: sample targets from the flat endpoint history
    history = list(range(m))
    hist = np.empty(2 * (n - m) * m + m, dtype=np.int64)
    hist[: m] = np.arange(m)
    hlen = m
    k = 0
    for v in range(m, n):
        picks = hist[rng.integers(0, hlen, 2 * m)]
        picks = np.unique(picks)[:m]
        cnt = picks.shape[0]
        src_all[k : k + cnt] = v
        dst_all[k : k + cnt] = picks
        hist[hlen : hlen + cnt] = picks
        hist[hlen + cnt : hlen + 2 * cnt] = v
        hlen += 2 * cnt
        k += cnt
    return from_edges(src_all[:k], dst_all[:k], n)


def web_like(n: int, m: int = 8, p_triangle: float = 0.5, seed: int = 0) -> Graph:
    """Holme–Kim style clustered power-law (web-Google / web-BerkStan stand-in)."""
    import networkx as nx

    G = nx.powerlaw_cluster_graph(n, m, p_triangle, seed=seed)
    e = np.asarray(G.edges(), dtype=np.int64)
    if e.size == 0:
        e = np.zeros((0, 2), dtype=np.int64)
    return from_edges(e[:, 0], e[:, 1], n)


def random_regular(n: int, d: int = 6, seed: int = 0) -> Graph:
    """d-regular random graph (uniform-degree control case)."""
    rng = np.random.default_rng(seed)
    stubs = np.repeat(np.arange(n), d)
    rng.shuffle(stubs)
    half = stubs.shape[0] // 2
    return from_edges(stubs[:half], stubs[half : 2 * half], n)


def erdos_renyi(n: int, avg_deg: float = 8.0, seed: int = 0) -> Graph:
    """G(n, m) uniform random graph."""
    rng = np.random.default_rng(seed)
    m = int(n * avg_deg / 2)
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    return from_edges(src, dst, n)


# --------------------------------------------------------------------------
# the paper's suite, as specs
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GraphSpec:
    """One row of the paper's Table 1, plus how to synthesise it."""
    name: str
    paper_id: str          # G1..G8
    n_full: int            # |V| at paper scale
    e_full: int            # |E| at paper scale (undirected count)
    n_reduced: int         # CPU-tractable scale for wall-clock benches
    make: Callable[[int, int], Graph]  # (n, seed) -> Graph at requested n
    vectorised: bool = False   # builds n_full in seconds (no per-vertex loop)

    def reduced(self, seed: int = 0) -> Graph:
        return self.make(self.n_reduced, seed)

    def full(self, seed: int = 0) -> Graph:
        """The published |V| as host arrays — vectorised generators only."""
        if not self.vectorised:
            raise ValueError(
                f"{self.paper_id} ({self.name}) loops in Python per vertex; "
                f"its full scale is a dry-run spec, not arrays"
            )
        return self.make(self.n_full, seed)

    @property
    def e_over_v(self) -> float:
        return self.e_full / self.n_full


def _grid_maker(n: int, seed: int) -> Graph:
    """The smallest near-square lattice with at least `n` vertices whose
    row count is ⌊√n⌋: 200×200 at n = 40,000, 1044×1045 at roadNet-PA's
    published 1,090,920."""
    rows = int(np.sqrt(n))
    return grid2d(rows, -(-n // rows), seed=seed)


GRAPH_SUITE: Dict[str, GraphSpec] = {
    s.paper_id: s
    for s in [
        GraphSpec("amazon0302", "G1", 262_111, 1_234_877, 20_000,
                  lambda n, seed: preferential_attachment(n, m=4, seed=seed)),
        GraphSpec("roadNet-PA", "G2", 1_090_920, 1_541_898, 40_000, _grid_maker,
                  vectorised=True),
        GraphSpec("delaunay_n19", "G3", 524_288, 1_572_823, 32_768,
                  lambda n, seed: delaunay_like(n, seed=seed), vectorised=True),
        GraphSpec("wiki-Talk", "G4", 2_394_385, 4_659_565, 30_000,
                  lambda n, seed: powerlaw(n, avg_deg=4.0, seed=seed)),
        GraphSpec("web-Google", "G5", 916_428, 4_322_051, 20_000,
                  lambda n, seed: web_like(n, m=5, seed=seed)),
        GraphSpec("web-BerkStan", "G6", 685_230, 6_649_470, 16_000,
                  lambda n, seed: web_like(n, m=10, seed=seed)),
        GraphSpec("soc-LiveJournal1", "G7", 4_847_571, 42_851_237, 24_000,
                  lambda n, seed: preferential_attachment(n, m=7, seed=seed)),
        GraphSpec("kron_g500-logn21", "G8", 2_097_152, 91_040_932, 16_384,
                  lambda n, seed: rmat(int(np.log2(n)), edge_factor=48, seed=seed),
                  vectorised=True),
    ]
}


def generate(paper_id: str, *, scale: str = "reduced", seed: int = 0) -> Graph:
    """Materialise one of the paper's graphs.  ``scale`` is 'reduced' or
    'full'; 'full' builds the published |V| for the vectorised generators
    (G2, G3, G8) and refuses the ones that loop in Python."""
    spec = GRAPH_SUITE[paper_id]
    if scale == "reduced":
        return spec.reduced(seed)
    if scale == "full":
        return spec.full(seed)
    raise ValueError(f"unknown scale {scale!r}; valid: reduced | full")
