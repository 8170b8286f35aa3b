"""Hypothesis properties over the PR 8 frontend lowerings (config zoo).

Fuzz the model shapes the new lowerings depend on — ``seq_len`` for the
attention actmul pair, ``d_state``/chunking for the SSM scan node,
``top_k``/``n_experts`` for the MoE expert fan-out — and assert, on every
traced graph:

* the vectorised batch evaluator is **bit-identical** to the scalar
  ``*_ref`` oracle on random cut vectors (the lock-step contract extended
  to graphs carrying ``state_words``);
* padded/masked evaluation is bit-identical to unpadded (padded rows are
  inert in the new feature column too);
* the structural claims of docs/OP_COVERAGE.md hold (scan nodes carry
  ``d_inner x d_state`` words, MoE expands to ``n_experts`` branches).

Skipped entirely when hypothesis is absent, per suite convention.
"""
import dataclasses

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import frontend as F, fusion, metrics as M
from repro.core.arch import PAPER_OPTIMAL_CONFIG as HW
from repro.core.ir import pad_cuts_batch, pad_graph
from repro.configs import REGISTRY, scaled_down


def _assert_lockstep_and_padding_inert(g, seed: int) -> None:
    """Batched == oracle (bit-identical, all four metrics + feasibility)
    and padded == unpadded, on random cuts of ``g``."""
    rng = np.random.default_rng(seed)
    C = 3
    cuts = rng.random((C, g.n_edges)) < 0.5
    hw_rows = np.stack([HW.as_row()])
    ac = M.area_consts_of(HW)
    feat = g.node_features()
    esrc, edst, ewords = g.edge_arrays()
    with M.enable_x64(True):
        batch = M.compose_metrics(M._evaluate_batch_graph(
            feat, esrc, edst, ewords, g.source_mask, g.sink_mask, cuts,
            hw_rows, ac,
        ), hw_rows)
        pg = pad_graph(g, n_nodes=g.n_nodes + 3, n_edges=g.n_edges + 5)
        pc = pad_cuts_batch(cuts, pg.n_edges_padded, C + 2)
        padded = M.compose_metrics(M._evaluate_batch_graph(
            pg.feat, pg.esrc, pg.edst, pg.ewords, pg.src_mask, pg.sink_mask,
            pc, hw_rows, ac, pg.node_mask, pg.edge_mask,
        ), hw_rows)[:, :C]
    assert np.array_equal(batch, padded)
    for c in range(C):
        m = M.evaluate_ref(g, cuts[c], HW)
        assert batch[0, c, 0] == m.bandwidth_words
        assert batch[0, c, 1] == m.latency_cycles
        assert batch[0, c, 2] == m.energy_nj
        assert batch[0, c, 3] == m.area_um2
    # Feasibility: batched graph mask == scalar oracle, at a budget that
    # actually bites (the median intermediate), so state_words is load-
    # bearing on both sides of the comparison.
    budget = float(np.median([
        fusion.graph_max_intermediate(g, cuts[c]) for c in range(C)
    ])) or 1.0
    mask = fusion.graph_feasible_mask_batch(g, cuts, budget)
    for c in range(C):
        assert mask[c] == (
            fusion.graph_max_intermediate(g, cuts[c]) <= budget
        )


@given(
    seed=st.integers(0, 2**31 - 1),
    seq_pow=st.integers(4, 7),  # seq_len in {16, 32, 64, 128}
)
@settings(max_examples=8, deadline=None)
def test_attention_actmul_lockstep(seed, seq_pow):
    """The QK^T/PV actmul pair at fuzzed seq_len: O(S^2) edge present,
    evaluator lock-step holds."""
    cfg = scaled_down(REGISTRY["qwen3-0.6b"])
    S = 2 ** seq_pow
    g = F.transformer_graph(cfg, seq_len=S, n_sublayers=1)
    actmuls = [n for n in g.nodes if n.kind == "actmul"]
    assert len(actmuls) == 2  # QK^T and PV
    score_words = cfg.n_heads * S * S
    assert any(e.words == score_words for e in g.edges)  # the S^2 matrix
    _assert_lockstep_and_padding_inert(g, seed)


@given(
    seed=st.integers(0, 2**31 - 1),
    d_state=st.sampled_from([2, 4, 8]),
    chunks=st.sampled_from([1, 2, 4]),
)
@settings(max_examples=8, deadline=None)
def test_mamba_scan_lockstep(seed, d_state, chunks):
    """The scan node at fuzzed d_state/chunking: state_words is exactly
    the carry size, one scan node per chunk, lock-step holds."""
    cfg = dataclasses.replace(
        scaled_down(REGISTRY["falcon-mamba-7b"]), ssm_state=d_state
    )
    g = F.mamba_graph(cfg, seq_len=64, chunks=chunks)
    scans = [n for n in g.nodes if n.kind == "scan"]
    assert len(scans) == chunks
    for n in scans:
        assert n.state_words == cfg.d_inner * d_state
        assert n.macs == 0  # weightless recurrent node
    if chunks > 1:
        # The carry hand-off between consecutive chunks is a real edge.
        ids = [i for i, n in enumerate(g.nodes) if n.kind == "scan"]
        carry = {(e.src, e.dst): e.words for e in g.edges}
        for a, b in zip(ids, ids[1:]):
            assert carry[(a, b)] == cfg.d_inner * d_state
    _assert_lockstep_and_padding_inert(g, seed)


@given(
    seed=st.integers(0, 2**31 - 1),
    n_experts=st.sampled_from([2, 4]),
    top_k=st.integers(1, 2),
)
@settings(max_examples=8, deadline=None)
def test_moe_fanout_lockstep(seed, n_experts, top_k):
    """The expert fan-out at fuzzed top_k/n_experts: E w1-branches with
    routed-capacity edge words, lock-step holds."""
    from repro.models.moe import _capacity

    cfg = dataclasses.replace(
        scaled_down(REGISTRY["mixtral-8x7b"]),
        n_experts=n_experts, top_k=min(top_k, n_experts),
    )
    S = 32
    g = F.moe_block_graph(cfg, seq_len=S)
    # w1 + w3 (swiglu) + w2 stacks each expand to n_experts branches.
    matmuls = [n for n in g.nodes if n.kind in ("matmul", "fc")]
    assert len(matmuls) == 1 + 3 * n_experts  # router + 3 stacks
    # Routed-capacity edge words: the dispatch actmul fans out
    # G*C*d words per expert branch (C = capacity_factor-scaled slots).
    G = S // min(cfg.moe_group_size, S)
    C = _capacity(cfg, min(cfg.moe_group_size, S))
    branch_words = G * C * cfg.d_model
    fanout = [e.words for e in g.edges if e.words == branch_words]
    assert len(fanout) >= 2 * n_experts  # into each expert's w1 and w3
    _assert_lockstep_and_padding_inert(g, seed)


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=6, deadline=None)
def test_zero_state_graph_unchanged_by_state_column(seed):
    """state_words == 0 everywhere => zeroing the F_STATE column is a
    no-op: the new feature is exactly inert on pre-scan workloads."""
    cfg = scaled_down(REGISTRY["qwen3-0.6b"])
    g = F.transformer_graph(cfg, seq_len=32, n_sublayers=1)
    feat = g.node_features()
    assert np.all(feat[:, M.F_STATE] == 0.0)
    rng = np.random.default_rng(seed)
    cuts = rng.random((2, g.n_edges)) < 0.5
    hw_rows = np.stack([HW.as_row()])
    ac = M.area_consts_of(HW)
    esrc, edst, ewords = g.edge_arrays()
    zeroed = feat.copy()
    zeroed[:, M.F_STATE] = 0.0
    with M.enable_x64(True):
        a = M._evaluate_batch_graph(
            feat, esrc, edst, ewords, g.source_mask, g.sink_mask, cuts,
            hw_rows, ac,
        )
        b = M._evaluate_batch_graph(
            zeroed, esrc, edst, ewords, g.source_mask, g.sink_mask, cuts,
            hw_rows, ac,
        )
    assert np.array_equal(np.asarray(a), np.asarray(b))
