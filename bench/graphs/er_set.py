"""ER-[700-800]: a fixed set of Erdős–Rényi graphs, as one edge list.

The test set of the learned-MIS literature (Ahn, Seo and Shin, ICML 2020;
DIMES and DIFUSCO after them): `graphs` graphs G(n, p), each n drawn
uniformly from [n_min, n_max].  `make` returns their disjoint union,
member after member: member j holds the ids from the sum of the sizes
before it, and every edge joins two vertices of one member.  The sizes are
drawn first from the seed, then each member's edges, so `member_sizes`
gives the sizes without drawing the edges.
"""
from __future__ import annotations

import numpy as np


def _sizes(params: dict, rng: np.random.Generator) -> np.ndarray:
    return rng.integers(int(params["n_min"]), int(params["n_max"]) + 1,
                        size=int(params["graphs"]))


def member_sizes(params: dict, seed: int) -> np.ndarray:
    """The members' vertex counts, in order."""
    return _sizes(params, np.random.default_rng(seed))


def make(params: dict, seed: int):
    rng = np.random.default_rng(seed)
    p = float(params["p"])
    us, vs = [], []
    off = 0
    for n in _sizes(params, rng):
        n = int(n)
        iu, iv = np.triu_indices(n, 1)
        keep = rng.random(iu.shape[0]) < p
        us.append(iu[keep] + off)
        vs.append(iv[keep] + off)
        off += n
    return off, np.concatenate(us).astype(np.int32), np.concatenate(vs).astype(np.int32)
