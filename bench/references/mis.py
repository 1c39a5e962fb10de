"""Plain reference for a maximal independent set, in numpy, independent of
the program: it reads only the benchmark's own edge list.

`check` judges one answer by what it says: an independent set has no edge
with both ends in it, and a maximal one leaves no vertex outside it without
a neighbour inside it.  Any one vertex flipped in a valid answer breaks one
of the two, so a count of 0 for both is exact.  It also gives the answer's
size, which a configuration may hold to a floor.

`solve` is Luby's algorithm with random priorities: each round every alive
vertex whose priority beats all its alive neighbours' joins the set, and it
and its neighbours leave.  `rounds_short=1` stops it one round before it
converges, which leaves vertices neither in the set nor covered: the control
that breaks the maximality the configurations state.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def check(n: int, u: np.ndarray, v: np.ndarray, answer) -> Dict[str, int]:
    """Counts of what is wrong with `answer`, a (n,) bool vector over the
    original vertex ids, and its size."""
    a = np.asarray(answer) if answer is not None else None
    if a is None or a.shape != (n,):
        return {"bad_shape": 1, "both_in": 0, "uncovered": 0, "mis_size": 0}
    m = a.astype(bool)
    both_in = int(np.count_nonzero(m[u] & m[v]))
    covered = m.copy()
    covered[u[m[v]]] = True
    covered[v[m[u]]] = True
    return {"bad_shape": 0, "both_in": both_in,
            "uncovered": int(n - np.count_nonzero(covered)),
            "mis_size": int(np.count_nonzero(m))}


def solve(n: int, u: np.ndarray, v: np.ndarray, seed: int,
          rounds_short: int = 0) -> np.ndarray:
    """Luby's MIS; with `rounds_short` > 0 it stops that many rounds early."""
    rng = np.random.default_rng(seed)
    pri = rng.permutation(n).astype(np.int64)
    s = np.concatenate([u, v])
    r = np.concatenate([v, u])
    alive = np.ones(n, dtype=bool)
    in_mis = np.zeros(n, dtype=bool)
    history = []
    while alive.any():
        live = alive[s] & alive[r]
        best = np.full(n, -1, dtype=np.int64)
        np.maximum.at(best, r[live], pri[s[live]])
        pick = alive & (pri > best)
        history.append(pick)
        in_mis |= pick
        gone = pick.copy()
        gone[r[pick[s]]] = True
        alive &= ~gone
    for pick in history[len(history) - rounds_short:] if rounds_short else ():
        in_mis &= ~pick
    return in_mis
