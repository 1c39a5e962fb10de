"""roadNet-PA stand-in: a lattice with diagonal shortcuts, thinned at random
to the published edge count.

The lattice is the program's G2 stand-in (`graphs.generators._grid_maker`
and `grid2d`), copied here: the smallest near-square lattice whose row
count is floor(sqrt(n)), vertices numbered row by row, right and down
neighbours joined, and `diag_frac * n` random down-right diagonals.  Here
the last row is cut short so |V| is the published count, and edges are then
kept at random, each equally likely, until |E| is the published count.
"""
from __future__ import annotations

import numpy as np


def lattice_edges(n: int, rng: np.random.Generator, diag_frac: float):
    """Unique undirected lattice edges (u < v) over vertices 0..n-1."""
    rows = int(np.sqrt(n))
    cols = -(-n // rows)
    idx = np.arange(rows * cols, dtype=np.int64).reshape(rows, cols)
    right = np.stack([idx[:, :-1].ravel(), idx[:, 1:].ravel()], axis=1)
    down = np.stack([idx[:-1, :].ravel(), idx[1:, :].ravel()], axis=1)
    n_diag = int(diag_frac * n)
    rr = rng.integers(0, rows - 1, n_diag)
    cc = rng.integers(0, cols - 1, n_diag)
    diag = np.stack([idx[rr, cc], idx[rr + 1, cc + 1]], axis=1)
    e = np.concatenate([right, down, diag])
    e = e[e.max(axis=1) < n]
    key = np.unique(e[:, 0] * n + e[:, 1])
    return key // n, key % n


def make(params: dict, seed: int):
    n, m = int(params["n_nodes"]), int(params["n_edges"])
    rng = np.random.default_rng(seed)
    u, v = lattice_edges(n, rng, float(params["diag_frac"]))
    if u.shape[0] < m:
        raise ValueError(f"lattice has {u.shape[0]} edges, fewer than {m}")
    keep = np.sort(rng.choice(u.shape[0], size=m, replace=False))
    return n, u[keep].astype(np.int32), v[keep].astype(np.int32)
