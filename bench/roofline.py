"""Bytes the fleet sweep has to move, counted from the problem's shapes.

The least time of one sweep call is ``sweep_bytes / HBM bytes per s``.
Counted once each, at the dtype the program declares for them: the
feature table (13 float64 words a node), the edge arrays (source and
destination int64, words float64), the source/sink/node/edge masks (one
byte each), the cut batch (one byte a grouping and edge), the hardware rows
(11 float64 words a point) and the 4 area constants; plus the row every
candidate has to yield for selection, 4 metric words of 8 bytes.

Padding is not counted, nor the fifth raw column the program also emits:
they are the program's choice, not the problem's.  A program that reduces
on the device and never materialises the per-candidate row needs this
count revised, in a change to the benchmark.
"""
from __future__ import annotations

F64 = 8
N_FEATURES = 13
N_HW_FIELDS = 11
METRIC_WORDS = 4


def sweep_bytes(n_nodes: int, n_edges: int, n_hw: int, n_cuts: int,
                n_graphs: int = 1) -> int:
    """Bytes one fleet-sweep call moves at least, per the module docstring.
    Node, edge and cut counts are the real (unpadded) ones of one graph."""
    per_graph = (
        n_nodes * N_FEATURES * F64  # feature table
        + n_edges * 3 * F64  # edge src, dst, words
        + 2 * n_nodes + n_nodes + n_edges  # src/sink, node, edge masks
        + n_cuts * n_edges  # cut batch, bool
    )
    shared = n_hw * N_HW_FIELDS * F64 + 4 * F64
    rows = n_graphs * n_hw * n_cuts * METRIC_WORDS * F64
    return n_graphs * per_graph + shared + rows
