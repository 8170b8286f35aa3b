"""Deterministic locks on the PR 8 lowerings behind the config zoo.

These are the no-hypothesis counterparts of tests/test_zoo_property.py:
exact structural claims about what :func:`frontend.transformer_graph`,
:func:`frontend.mamba_graph`, and :func:`frontend.moe_block_graph` emit —
the attention actmul pair, the recurrent ``scan`` node and its
``state_words``, the chunk-boundary carry/conv-tail edges, and the MoE
router + expert fan-out — plus an all-registry trace smoke at scaled-down
shapes.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import frontend as F, metrics as M
from repro.core.arch import PAPER_OPTIMAL_CONFIG as HW
from repro.configs import REGISTRY, scaled_down
from repro.models.moe import _capacity


def _lockstep(g):
    """Batched evaluator == scalar oracle on a handful of fixed cuts."""
    rng = np.random.default_rng(0)
    cuts = rng.random((3, g.n_edges)) < 0.5
    hw_rows = np.stack([HW.as_row()])
    ac = M.area_consts_of(HW)
    feat = g.node_features()
    esrc, edst, ewords = g.edge_arrays()
    with M.enable_x64(True):
        batch = M.compose_metrics(M._evaluate_batch_graph(
            feat, esrc, edst, ewords, g.source_mask, g.sink_mask, cuts,
            hw_rows, ac,
        ), hw_rows)
    for c in range(cuts.shape[0]):
        m = M.evaluate_ref(g, cuts[c], HW)
        assert batch[0, c, 0] == m.bandwidth_words
        assert batch[0, c, 1] == m.latency_cycles
        assert batch[0, c, 2] == m.energy_nj
        assert batch[0, c, 3] == m.area_um2


def test_attention_lowering_actmul_pair():
    """One attention sublayer = QK^T and PV actmuls with the O(S^2)
    score matrix as an explicit n_heads*S*S edge between them."""
    cfg = scaled_down(REGISTRY["qwen3-0.6b"])
    S = 64
    g = F.transformer_graph(cfg, seq_len=S, n_sublayers=1)
    actmuls = [i for i, n in enumerate(g.nodes) if n.kind == "actmul"]
    assert len(actmuls) == 2
    qk, pv = actmuls
    score = [e for e in g.edges if e.src == qk and e.dst == pv]
    # Softmax folds into the QK^T producer, so the pair is directly
    # connected and the score matrix words are the full S^2 spill.
    assert any(e.words == cfg.n_heads * S * S for e in score)
    assert all(n.state_words == 0 for n in g.nodes)  # attn carries none
    _lockstep(g)


def test_mamba_scan_state_words():
    """The selective scan lowers to a weightless ``scan`` node whose
    state_words is exactly the (d_inner, d_state) carry."""
    cfg = scaled_down(REGISTRY["falcon-mamba-7b"])
    g = F.mamba_graph(cfg, seq_len=64, chunks=1)
    scans = [n for n in g.nodes if n.kind == "scan"]
    assert len(scans) == 1
    (scan,) = scans
    assert scan.state_words == cfg.d_inner * cfg.ssm_state
    assert scan.macs == 0
    assert M.F_STATE == 12  # the 13th feature column, doc'd in OP_COVERAGE
    feat = g.node_features()
    assert feat[:, M.F_STATE].sum() == scan.state_words
    _lockstep(g)


def test_mamba_chunked_carry_and_conv_tail_edges():
    """chunks=2 threads the SSM cache between the calls: the
    (d_inner, d_state) carry and the (conv-1)-token convolution tail
    both surface as real cut-point edges."""
    cfg = scaled_down(REGISTRY["falcon-mamba-7b"])
    g = F.mamba_graph(cfg, seq_len=64, chunks=2)
    scans = [i for i, n in enumerate(g.nodes) if n.kind == "scan"]
    assert len(scans) == 2
    a, b = scans
    carry = [e for e in g.edges if e.src == a and e.dst == b]
    assert [e.words for e in carry] == [cfg.d_inner * cfg.ssm_state]
    tail_words = (cfg.ssm_conv - 1) * cfg.d_inner
    assert any(e.words == tail_words for e in g.edges)
    _lockstep(g)


def test_moe_lowering_router_and_fanout():
    """MoE FFN = router matmul + 3 stacks of E expert branches (swiglu
    w1/w3 + w2), dispatch edges carrying the routed capacity words."""
    cfg = dataclasses.replace(
        scaled_down(REGISTRY["mixtral-8x7b"]), n_experts=4, top_k=2
    )
    S = 32
    g = F.moe_block_graph(cfg, seq_len=S)
    matmuls = [n for n in g.nodes if n.kind in ("matmul", "fc")]
    assert len(matmuls) == 1 + 3 * cfg.n_experts
    groups = S // min(cfg.moe_group_size, S)
    cap = _capacity(cfg, min(cfg.moe_group_size, S))
    branch_words = groups * cap * cfg.d_model
    fanout = [e for e in g.edges if e.words == branch_words]
    # Dispatch feeds each expert's w1 AND w3 (swiglu): >= 2E such edges.
    assert len(fanout) >= 2 * cfg.n_experts
    _lockstep(g)


def test_moe_capacity_scales_with_top_k():
    """Doubling top_k doubles the routed capacity and hence the words
    on every dispatch edge (capacity_factor held fixed)."""
    base = scaled_down(REGISTRY["mixtral-8x7b"])
    words = {}
    for tk in (1, 2):
        cfg = dataclasses.replace(base, n_experts=4, top_k=tk)
        sg = min(cfg.moe_group_size, 32)
        g = F.moe_block_graph(cfg, seq_len=32)
        w = (32 // sg) * _capacity(cfg, sg) * cfg.d_model
        assert any(e.words == w for e in g.edges)
        words[tk] = w
    assert words[2] == 2 * words[1]


@pytest.mark.parametrize("name", sorted(REGISTRY))
def test_every_registry_config_traces_scaled_down(name):
    """The whole zoo lowers at scaled-down shapes: one pattern period per
    config traces to a validated GraphIR with > 0 compute."""
    cfg = scaled_down(REGISTRY[name])
    g = F.transformer_graph(cfg, seq_len=64)
    assert g.n_nodes > 0 and g.n_edges > 0
    assert g.total_macs > 0
    kinds = {n.kind for n in g.nodes}
    if "mamba" in cfg.layer_pattern:
        assert "scan" in kinds
    if cfg.n_experts > 1:
        assert "actmul" in kinds  # dispatch/combine appear
