"""Graph500 Kronecker graph (kron_g500-logn*): R-MAT samples, ids permuted.

The program's generator (`graphs.generators.rmat`), copied here: `edge_factor
· 2^scale` edge samples, each of the `scale` bit levels drawing its quadrant
from the initiator A/B/C/D (D = 1 - A - B - C), then vertex ids permuted at
random.  As in the Graph500 output SuiteSparse stores, self-loops are
dropped and duplicate edges merged, so |E| lands well under the samples.
"""
from __future__ import annotations

import numpy as np


def make(params: dict, seed: int):
    scale, ef = int(params["scale"]), int(params["edge_factor"])
    a, b, c = float(params["a"]), float(params["b"]), float(params["c"])
    n = 1 << scale
    m = n * ef
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
    perm = rng.permutation(n)
    s, r = perm[src], perm[dst]
    keep = s != r
    lo = np.minimum(s[keep], r[keep])
    hi = np.maximum(s[keep], r[keep])
    key = np.unique(lo * n + hi)
    return n, (key // n).astype(np.int32), (key % n).astype(np.int32)
