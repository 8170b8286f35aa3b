"""Frontier-state DP: exact beyond the 2^E enumeration wall.

Deterministic coverage (this module must run WITHOUT hypothesis — the
random-DAG cross-checks here use numpy seeds; the hypothesis variants live
in ``test_frontier_dp_property.py`` behind an importorskip):

* bit-identical minimum group cost vs ``brute_force_min_bw`` on random
  valid DAGs (with and without SRAM budgets) and on the in-repo builders;
* deterministic cost locks on ``residual_block_ir`` / ``encoder_decoder_ir``
  and the ResNet-18 exact-optimum-at-most-beam guarantee (38 edges — a
  space flat enumeration can never certify);
* the ``optimal_cuts`` dispatch chain (chain DP -> frontier DP ->
  exhaustive for small-but-wide DAGs -> beam) with ``engine`` provenance,
  including the flow integration;
* the elimination-order / frontier-width utilities in ``repro.core.ir``;
* the small-graph enumeration threshold (scalar filter under
  ``SMALL_ENUM_PATTERNS``, identical output, memo intact).
"""
import numpy as np
import pytest

from repro.core import fusion, metrics as M
from repro.core.arch import Constraints, PAPER_OPTIMAL_CONFIG
from repro.core.flow import run_flow
from repro.core.ir import (
    EdgeSpec,
    GraphIR,
    LayerSpec,
    as_graph,
    encoder_decoder_ir,
    min_width_topo_order,
    residual_block_ir,
    resnet18_ir,
    topo_frontier_sets,
    topo_frontier_width,
    vgg16_ir,
)
from test_graph_ir import random_dag

RELAXED = Constraints(max_bandwidth_words=1e12, max_latency_cycles=1e12,
                      max_energy_nj=1e12, max_area_um2=1e12)


def _assert_exact_match(g, dp, bf, sram):
    """The DP contract vs brute force: bit-identical minimum cost; the DP's
    cuts must themselves be valid, feasible, and achieve that cost (ties
    may resolve to a different optimal vector than brute force's
    first-pattern rule)."""
    assert dp.group_cost_words == bf.group_cost_words
    assert dp.engine == "frontier_dp" and dp.exact
    assert fusion.is_valid_cuts(g, dp.cuts)
    assert fusion.graph_max_intermediate(g, dp.cuts) <= sram
    assert fusion._graph_cost(g, dp.cuts) == dp.group_cost_words
    labels = fusion.cut_group_labels(g, dp.cuts)
    assert dp.n_groups == int(labels.max()) + 1


# ---------------------------------------------------------------------------
# Bit-identical minimum cost vs brute force
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(12))
def test_dp_bit_identical_cost_random_dags(seed):
    rng = np.random.default_rng(4200 + seed)
    g = random_dag(rng, int(rng.integers(3, 11)))
    feat = g.node_features()
    budget = float(np.median(feat[:, M.F_OUT_PRE]))
    for sram in (float("inf"), budget):
        bf = fusion.brute_force_min_bw(g, sram_budget_words=sram)
        dp = fusion.frontier_dp_min_bw(
            g, sram_budget_words=sram, max_width=None, max_states=1 << 22
        )
        _assert_exact_match(g, dp, bf, sram)


@pytest.mark.parametrize("sram", [float("inf"), 150_000.0])
def test_dp_bit_identical_residual_block(sram):
    rb = residual_block_ir()
    bf = fusion.brute_force_min_bw(rb, sram_budget_words=sram)
    dp = fusion.frontier_dp_min_bw(rb, sram_budget_words=sram)
    _assert_exact_match(rb, dp, bf, sram)


def test_dp_bit_identical_encoder_decoder_vs_enumeration():
    """The acceptance case: 21 edges = 2^21 flat patterns; the DP must agree
    with the full enumeration bit-for-bit on the minimum."""
    ed = encoder_decoder_ir()
    bf = fusion.brute_force_min_bw(ed)
    dp = fusion.frontier_dp_min_bw(ed)
    _assert_exact_match(ed, dp, bf, float("inf"))


# ---------------------------------------------------------------------------
# Deterministic locks + ResNet-18 exactness
# ---------------------------------------------------------------------------


def test_dp_locked_optima():
    """Geometry-derived optima of the in-repo builders — any change to the
    DP, the cost model, or the builders must consciously update these."""
    rb = residual_block_ir()
    ed = encoder_decoder_ir()
    assert fusion.frontier_dp_min_bw(rb).group_cost_words == 200704.0
    assert fusion.frontier_dp_min_bw(
        rb, sram_budget_words=150_000.0
    ).group_cost_words == 501760.0
    assert fusion.frontier_dp_min_bw(ed).group_cost_words == 720896.0
    assert fusion.frontier_dp_min_bw(
        ed, sram_budget_words=300_000.0
    ).group_cost_words == 11206656.0


@pytest.mark.parametrize("sram", [float("inf"), 200_000.0])
def test_resnet18_exact_at_most_beam(sram):
    """ResNet-18 (38 edges) was heuristic-only before the frontier DP; the
    certified exact optimum can only match or beat the beam answer."""
    g = resnet18_ir()
    dp = fusion.frontier_dp_min_bw(g, sram_budget_words=sram)
    beam = fusion.beam_merge_cuts(g, sram_budget_words=sram)
    assert dp.group_cost_words <= beam.group_cost_words
    assert fusion.is_valid_cuts(g, dp.cuts)
    assert fusion.graph_max_intermediate(g, dp.cuts) <= sram
    assert fusion._graph_cost(g, dp.cuts) == dp.group_cost_words


# ---------------------------------------------------------------------------
# Dispatch, provenance, caps
# ---------------------------------------------------------------------------


def _wide_dag(n_mid: int) -> GraphIR:
    """source -> n_mid parallel convs -> sink join: every topological order
    holds all middles on the frontier at once, so width == n_mid."""
    nodes = [LayerSpec("src", "conv", 4, 4, 8, 8, 3, 3, 1)]
    for i in range(n_mid):
        nodes.append(LayerSpec(f"m{i}", "conv", 4, 4, 8, 8, 3, 3, 1))
    nodes.append(LayerSpec("join", "elementwise", 4, 4, 8, 8))
    edges = [EdgeSpec(0, i + 1, nodes[0].out_words) for i in range(n_mid)]
    edges += [
        EdgeSpec(i + 1, n_mid + 1, nodes[i + 1].out_words)
        for i in range(n_mid)
    ]
    return GraphIR("wide", tuple(nodes), tuple(edges))


def _dense_wide_dag(rng, n_mid: int) -> GraphIR:
    """source -> n_mid middles of distinct sizes -> sink join, with random
    edges among the middles: width n_mid, no separator, no two isomorphic
    branches."""
    nodes = [LayerSpec("src", "conv", 4, 4, 8, 8, 3, 3, 1)]
    for i in range(n_mid):
        nodes.append(LayerSpec(f"m{i}", "conv", 4, 4 + i, 8, 8, 3, 3, 1))
    nodes.append(LayerSpec("join", "elementwise", 4, 4, 8, 8))
    edges = [EdgeSpec(0, i + 1, 256) for i in range(n_mid)]
    edges += [EdgeSpec(i + 1, n_mid + 1, nodes[i + 1].out_words)
              for i in range(n_mid)]
    edges += [EdgeSpec(i + 1, j + 1, 64) for i in range(n_mid)
              for j in range(i + 1, n_mid) if rng.random() < 0.3]
    return GraphIR("dense", tuple(nodes), tuple(edges))


def _wide_fanin_dag(n_src: int) -> GraphIR:
    """n_src parallel sources feeding one join: width n_src, n_src edges —
    wide for the DP but small enough to enumerate."""
    nodes = [
        LayerSpec(f"s{i}", "conv", 4, 4, 8, 8, 3, 3, 1) for i in range(n_src)
    ] + [LayerSpec("join", "elementwise", 4, 4, 8, 8)]
    edges = [EdgeSpec(i, n_src, nodes[i].out_words) for i in range(n_src)]
    return GraphIR("fanin", tuple(nodes), tuple(edges))


def test_dispatch_engines():
    assert fusion.optimal_cuts(vgg16_ir()).engine == "chain_dp"
    assert fusion.optimal_cuts(residual_block_ir()).engine == "frontier_dp"
    assert fusion.optimal_cuts(resnet18_ir()).engine == "frontier_dp"
    # a DAG wider than the cap but within the 2^E wall keeps a CERTIFIED
    # optimum via exhaustive enumeration (the pre-DP dispatch guarantee)
    fanin = _wide_fanin_dag(fusion.FRONTIER_DP_MAX_WIDTH + 1)
    assert fanin.n_edges <= fusion.MAX_EXHAUSTIVE_EDGES
    res = fusion.optimal_cuts(fanin)
    assert res.engine == "exhaustive" and res.exact
    # wide AND beyond the enumeration wall, but its middles are isomorphic
    # branches between one source and one join: the segment DP certifies
    # the optimum (at no budget, one fused group)
    wide = _wide_dag(fusion.FRONTIER_DP_MAX_WIDTH + 1)
    assert wide.n_edges > fusion.MAX_EXHAUSTIVE_EDGES
    res = fusion.optimal_cuts(wide)
    assert res.engine == "segment_dp" and res.exact
    assert res.group_cost_words == fusion._graph_cost(
        wide, np.zeros(wide.n_edges, bool))
    with pytest.raises(fusion.FrontierTooWide):
        fusion.frontier_dp_min_bw(wide)
    # wide, beyond the wall, and dense with distinct middles: no separator
    # and no bundle, so beam, with provenance saying so
    dense = _dense_wide_dag(np.random.default_rng(7),
                            fusion.FRONTIER_DP_MAX_WIDTH + 1)
    assert dense.n_edges > fusion.MAX_EXHAUSTIVE_EDGES
    res = fusion.optimal_cuts(dense)
    assert res.engine == "beam" and not res.exact
    with pytest.raises(fusion.SearchDeclined):
        fusion.segment_dp_min_bw(dense)


def test_state_cap_raises_frontier_too_wide():
    # On the in-repo builders dominance + branch-and-bound collapse the DP
    # to a single live state per step (the greedy incumbent is already
    # optimal there), so the cap needs a graph whose incumbent is loose: a
    # budgeted random DAG where greedy overpays keeps competing states.
    rng = np.random.default_rng(4200)
    g = random_dag(rng, int(rng.integers(3, 11)))
    budget = float(np.median(g.node_features()[:, M.F_OUT_PRE]))
    with pytest.raises(fusion.FrontierTooWide):
        fusion.frontier_dp_min_bw(
            g, sram_budget_words=budget, max_width=None, max_states=1
        )


def test_optimal_cuts_returns_fresh_cuts():
    """The dispatch memo must hand every caller an independent cut vector —
    mutating one result cannot poison later searches."""
    g = residual_block_ir()
    a = fusion.optimal_cuts(g)
    a.cuts[:] = True
    b = fusion.optimal_cuts(g)
    assert not b.cuts.all()
    assert b.group_cost_words == 200704.0


def test_run_flow_search_provenance_and_exact_optimum():
    g = resnet18_ir()
    res = run_flow(g, config_space=[PAPER_OPTIMAL_CONFIG],
                   constraints=RELAXED, groupings="search")
    assert res.search_engine == "frontier_dp"
    dp = fusion.frontier_dp_min_bw(g)
    assert res.best_metrics.bandwidth_words == M.bandwidth_ref(g, dp.cuts)
    # chain fast path + exhaustive provenance strings
    res_chain = run_flow(vgg16_ir(), config_space=[PAPER_OPTIMAL_CONFIG],
                         constraints=RELAXED, groupings="search")
    assert res_chain.search_engine == "chain_dp"
    res_ex = run_flow(residual_block_ir(),
                      config_space=[PAPER_OPTIMAL_CONFIG],
                      constraints=RELAXED, groupings="exhaustive")
    assert res_ex.search_engine == "exhaustive"


# ---------------------------------------------------------------------------
# Elimination-order / frontier-width utilities
# ---------------------------------------------------------------------------


def test_frontier_width_known_graphs():
    assert topo_frontier_width(residual_block_ir()) == 2
    assert topo_frontier_width(as_graph(encoder_decoder_ir())) == 3
    assert topo_frontier_width(resnet18_ir()) == 2
    assert topo_frontier_width(_wide_dag(5)) == 5


def test_frontier_sets_cover_pending_edges():
    rng = np.random.default_rng(7)
    g = random_dag(rng, 9)
    sets = topo_frontier_sets(g)
    assert sets[-1] == []
    for t, frontier in enumerate(sets):
        want = sorted(
            {e.src for e in g.edges if e.src <= t < e.dst}
        )
        assert frontier == want


def test_min_width_order_is_topological_and_no_wider():
    for seed in range(6):
        rng = np.random.default_rng(90 + seed)
        g = random_dag(rng, int(rng.integers(4, 12)))
        order = min_width_topo_order(g)
        assert sorted(order) == list(range(len(g.nodes)))
        pos = {v: t for t, v in enumerate(order)}
        assert all(pos[e.src] < pos[e.dst] for e in g.edges)
        # any-order DP invariance: the optimum is order-independent
        dp_nat = fusion.frontier_dp_min_bw(g, max_width=None)
        dp_alt = fusion.frontier_dp_min_bw(g, max_width=None, order=order)
        assert dp_nat.group_cost_words == dp_alt.group_cost_words


def test_frontier_sets_reject_non_topological_order():
    g = residual_block_ir()
    with pytest.raises(ValueError):
        topo_frontier_sets(g, [3, 2, 1, 0])
    with pytest.raises(ValueError):
        topo_frontier_sets(g, [0, 0, 1, 2])


# ---------------------------------------------------------------------------
# Small-graph enumeration threshold (cold-path satellite)
# ---------------------------------------------------------------------------


def test_small_graph_enumeration_uses_scalar_filter_identically():
    """Below SMALL_ENUM_PATTERNS the memoised enumeration runs the scalar
    per-pattern filter — output, ordering, caching, and read-only-ness all
    unchanged."""
    rb = residual_block_ir()
    assert (1 << rb.n_edges) <= fusion.SMALL_ENUM_PATTERNS
    fusion.enumerate_valid_edge_cuts.cache_clear()
    out = fusion.enumerate_valid_edge_cuts(rb)
    np.testing.assert_array_equal(
        out, fusion._enumerate_valid_edge_cuts_scalar(rb)
    )
    assert fusion.enumerate_valid_edge_cuts(rb) is out  # still memoised
    assert not out.flags.writeable
    rng = np.random.default_rng(11)
    g = random_dag(rng, 5)
    if (1 << g.n_edges) <= fusion.SMALL_ENUM_PATTERNS:
        np.testing.assert_array_equal(
            fusion.enumerate_valid_edge_cuts(g),
            fusion._enumerate_valid_edge_cuts_scalar(g),
        )
