"""The least work a round of MIS must do, whatever implements it.

A round of a Luby-style MIS reads, for every vertex, whether each neighbour
is still a candidate and how its priority compares.  Whatever the layout
(dense tiles, bit-packed tiles, a COO tail, a CSR walk), the adjacency has
to be read at least once per round, and it cannot be held in less than one
bit per stored half-edge.  Each vertex's 32-bit priority is read, and its
alive flag is read and written (one bit each way).  So the bytes below are
a floor: no layout moves fewer, and a share of the roofline computed from
them cannot pass 100% unless the time leaves out part of the work.

The operations (one compare per half-edge) take far less time than the
bytes at the chip's peaks, so the bound is the memory one.
"""
from __future__ import annotations

from benchlib.peaks import Peaks

PRIORITY_BYTES = 4          # one int32 priority per vertex
ALIVE_BITS = 2              # alive flag read and written, one bit each


def round_bytes(n_nodes: int, n_half_edges: int) -> float:
    """Floor on the bytes one MIS round moves."""
    return n_half_edges / 8 + n_nodes * (PRIORITY_BYTES + ALIVE_BITS / 8)


def round_ops(n_half_edges: int) -> float:
    """Floor on the operations of one round: a compare per half-edge."""
    return float(n_half_edges)


def least_time_s(rounds: int, n_nodes: int, n_half_edges: int,
                 peaks: Peaks) -> float:
    """The least time the chip could take for `rounds` rounds: the larger
    of bytes over bandwidth and operations over the int8 peak."""
    by_bytes = rounds * round_bytes(n_nodes, n_half_edges) / peaks.hbm_bw
    by_ops = rounds * round_ops(n_half_edges) / peaks.ops_int8
    return max(by_bytes, by_ops)
