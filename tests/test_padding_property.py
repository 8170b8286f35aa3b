"""Hypothesis property: padding invariance of the evaluator on random DAGs.

Random DAG, random cuts, random pad amounts: padded/masked evaluation is
bit-identical to the unpadded batch path and the scalar ``*_ref`` oracles
for all four metrics and the SRAM feasibility mask.  Deterministic
per-workload locks live in tests/test_padding.py (this module is skipped
entirely when hypothesis is absent, per suite convention).
"""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import fusion, metrics as M
from repro.core.arch import PAPER_OPTIMAL_CONFIG as HW
from repro.core.ir import pad_cuts_batch, pad_graph
from test_graph_ir import random_dag


@given(
    seed=st.integers(0, 2**31 - 1),
    n=st.integers(3, 8),
    node_pad=st.integers(0, 5),
    edge_pad=st.integers(0, 6),
    row_pad=st.integers(0, 3),
)
@settings(max_examples=40, deadline=None)
def test_padding_invariance_property(seed, n, node_pad, edge_pad, row_pad):
    """Uses the eager kernel (``M._evaluate_batch_graph``) so one hypothesis
    run does not pay an XLA compile per drawn shape; the jitted path's
    padded==unpadded==oracle lock is in tests/test_padding.py."""
    rng = np.random.default_rng(seed)
    g = random_dag(rng, n)
    C = int(rng.integers(1, 4))
    cuts = rng.random((C, g.n_edges)) < 0.5
    hw_rows = np.stack([HW.as_row()])
    ac = M.area_consts_of(HW)

    feat = g.node_features()
    esrc, edst, ewords = g.edge_arrays()
    with M.enable_x64(True):
        ref = M.compose_metrics(M._evaluate_batch_graph(
            feat, esrc, edst, ewords, g.source_mask, g.sink_mask, cuts,
            hw_rows, ac,
        ), hw_rows)
        pg = pad_graph(
            g, n_nodes=g.n_nodes + node_pad, n_edges=g.n_edges + edge_pad
        )
        pc = pad_cuts_batch(cuts, pg.n_edges_padded, C + row_pad)
        pad = M.compose_metrics(M._evaluate_batch_graph(
            pg.feat, pg.esrc, pg.edst, pg.ewords, pg.src_mask, pg.sink_mask,
            pc, hw_rows, ac, pg.node_mask, pg.edge_mask,
        ), hw_rows)[:, :C]
    assert np.array_equal(ref, pad)  # padded == unpadded, bit-identical
    m = M.evaluate_ref(g, cuts[0], HW)  # == the scalar oracles
    assert pad[0, 0, 0] == m.bandwidth_words
    assert pad[0, 0, 1] == m.latency_cycles
    assert pad[0, 0, 2] == m.energy_nj
    assert pad[0, 0, 3] == m.area_um2

    max_int = fusion.padded_max_intermediate_batch(pg, pc)[:C]
    assert np.array_equal(
        max_int, fusion.graph_max_intermediate_batch(g, cuts)
    )
    assert max_int[0] == fusion.graph_max_intermediate(g, cuts[0])
    budget = float(np.median(max_int))
    assert np.array_equal(
        fusion.padded_feasible_mask_batch(pg, pc, budget)[:C],
        fusion.graph_feasible_mask_batch(g, cuts, budget),
    )
