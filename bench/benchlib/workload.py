"""A cell's inputs: its graph, fixed by the configuration, and the priority
keys of its requests, ordered by `--seed`.

The configuration names a generator under `bench/graphs/`, its parameters
and the seed of its graph (`graph.seed`): the deployment's graph is the
same in every run, so every run asks for the same shapes and finds every
program in the compile cache after the first.

The keys (`keys`: `seed`, `block`, `blocks`) are a pool of `block * blocks`
keys drawn from the configuration's key seed, cut into blocks.  Request `i`
solves under a key of block `i // block`, in an order within the block
drawn from `--seed`; after the last block the pool starts again.  So no key
repeats until the whole pool is spent, and every seed asks for the same
work, in another order, for each whole block: the rounds a solve takes
depend on its key, and a window of fresh keys would change the work from
seed to seed.  Two more keys of the same draw warm the program up.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from benchlib.spec import Cell, load_module

EdgeList = Tuple[int, np.ndarray, np.ndarray]   # (n, u, v) with u < v, unique


def seed_sequence(seed: int, *stream: int) -> np.random.SeedSequence:
    """Seed sequence of one stream; any whole `seed` works, negative too."""
    return np.random.SeedSequence(seed % (1 << 64), spawn_key=stream)


class Workload:
    def __init__(self, cell: Cell, seed: int):
        self.params = cell.config["graph"]
        self.gen = load_module(cell.bench / "graphs" / f"{self.params['generator']}.py")
        keys = cell.config["keys"]
        block, blocks = int(keys["block"]), int(keys["blocks"])
        drawn = [int(k >> 1) for k in
                 seed_sequence(int(keys["seed"])).generate_state(block * blocks + 2)]
        self.pool, self._warm = drawn[:-2], drawn[-2:]
        rng = np.random.default_rng(seed_sequence(seed, 0))
        self._order = np.concatenate(
            [b * block + rng.permutation(block) for b in range(blocks)])
        self._graph: EdgeList = None

    def graph(self, i: int = 0) -> EdgeList:
        """The graph request `i` solves (every request solves the one graph)."""
        if self._graph is None:
            self._graph = self.gen.make(self.params, int(self.params["seed"]))
        return self._graph

    def key(self, i: int) -> int:
        """Priority key of request `i`, below 2**31."""
        return self.pool[self._order[i % len(self._order)]]

    def warm_keys(self):
        """Two keys for warm-up solves, outside the pool."""
        return list(self._warm)


def half_edges(g: EdgeList) -> Tuple[np.ndarray, np.ndarray]:
    """Both directions of every edge, sorted by sender then receiver."""
    n, u, v = g
    s = np.concatenate([u, v]).astype(np.int64)
    r = np.concatenate([v, u]).astype(np.int64)
    order = np.argsort(s * n + r, kind="stable")
    return s[order].astype(np.int32), r[order].astype(np.int32)
