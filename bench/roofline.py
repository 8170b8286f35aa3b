"""Bytes the fleet sweep has to move, counted from the problem's shapes.

The least time of one sweep call is ``sweep_bytes / HBM bytes per s``.
Counted once each, at the dtype the program declares for them: the
feature table (13 float64 words a node), the edge arrays (source and
destination int64, words float64), the source/sink/node/edge masks (one
byte each), the cut batch (one byte a grouping and edge), the hardware rows
(11 float64 words a point) and the 4 area constants; plus the answer the
call returns: per graph the picked row (its 4 metric words, hardware index
and grouping index, 8 bytes each).  A call that asks for a Pareto front
returns its rows too; no cell that reads this count asks for one.

Padding is not counted, nor any per-candidate output: a program may write
the (point x grouping) plane or reduce it on the device, and neither is
required by the problem.  So this is a floor that no valid implementation
can go under, whichever way it finds the pick.
"""
from __future__ import annotations

F64 = 8
N_FEATURES = 13
N_HW_FIELDS = 11
# An answer row: 4 metric words, the hardware index and the grouping index.
ANSWER_WORDS = 4 + 2


def sweep_bytes(n_nodes: int, n_edges: int, n_hw: int, n_cuts: int,
                n_graphs: int = 1) -> int:
    """Bytes one fleet-sweep call without a Pareto front moves at least,
    per the module docstring.  Node, edge and cut counts are the real
    (unpadded) ones of one graph."""
    per_graph = (
        n_nodes * N_FEATURES * F64  # feature table
        + n_edges * 3 * F64  # edge src, dst, words
        + 2 * n_nodes + n_nodes + n_edges  # src/sink, node, edge masks
        + n_cuts * n_edges  # cut batch, bool
        + ANSWER_WORDS * F64  # the picked row
    )
    shared = n_hw * N_HW_FIELDS * F64 + 4 * F64
    return n_graphs * per_graph + shared
