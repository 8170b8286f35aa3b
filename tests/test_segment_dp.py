"""Segment DP: exact grouping search past the frontier-width cap.

The engine splits a DAG at single-node separators and solves each segment
by the frontier DP or, where a bundle of isomorphic branches makes it too
wide, by counts over the bundle.  Its minima must equal brute force and the
frontier DP wherever those run, at infinite and finite SRAM budgets.
"""
import math

import numpy as np
import pytest

from repro.configs import REGISTRY
from repro.core import frontend, fusion, metrics as M, spans
from repro.core.arch import Constraints, config_space_grid
from repro.core.errors import InfeasibleBudgetError
from repro.core.ir import EdgeSpec, GraphIR, LayerSpec, resnet18_ir
from repro.core.service import PlanRequest, PlanningService


def _spec(rng, name):
    c, h = int(rng.choice([2, 4, 8])), int(rng.choice([2, 4, 8]))
    kind = str(rng.choice(["conv", "matmul", "elementwise"]))
    if kind == "conv":
        return LayerSpec(name, "conv", c, int(rng.choice([2, 4, 8])), h, h,
                         3, 3, 1)
    n_out = c if kind == "elementwise" else int(rng.choice([2, 4, 8]))
    return LayerSpec(name, kind, c, n_out, h, 1)


def bundle_dag(rng, max_edges=16) -> GraphIR:
    """A series of chain links and bundles: a bundle is an optional router
    R, a node D, 2 to 6 isomorphic branches (one node, a chain of two, or a
    gated pair) each with one edge into C, and an optional skip join; the
    branches' nodes are numbered layer by layer, as a trace numbers
    experts."""
    nodes, edges = [_spec(rng, "n")], []
    tail = 0

    def add(spec):
        nodes.append(spec)
        return len(nodes) - 1

    def words():
        return int(rng.integers(1, 200))

    while len(edges) < max_edges - 1:
        room = max_edges - len(edges)
        shape = int(rng.integers(0, 3))  # one node, chain of two, gated pair
        per = (2, 3, 5)[shape]  # edges a branch takes, D and C included
        router, skip = bool(rng.integers(0, 2)), bool(rng.integers(0, 2))
        fixed = 1 + 3 * router + 2 * skip
        k_max = min(6, (room - fixed) // per)
        if rng.integers(0, 3) == 0 or k_max < 2:
            v = add(_spec(rng, "n"))
            edges.append((tail, v, words()))
            tail = v
            continue
        k = int(rng.integers(2, k_max + 1))
        start = tail
        if router:
            r = add(_spec(rng, "r"))
            edges.append((start, r, words()))
        d = add(_spec(rng, "d"))
        edges.append((start, d, words()))
        if router:
            edges.append((r, d, words()))
        kinds = [_spec(rng, "b") for _ in range(3)]
        w = [words() for _ in range(5)]
        layer = [[add(kinds[i]) for _ in range(k)] for i in range(shape + 1)]
        c_in = []
        for b in range(k):
            x = [layer[i][b] for i in range(shape + 1)]
            if shape == 0:
                edges.append((d, x[0], w[0]))
            elif shape == 1:
                edges += [(d, x[0], w[0]), (x[0], x[1], w[1])]
            else:
                edges += [(d, x[0], w[0]), (d, x[1], w[1]),
                          (x[0], x[2], w[2]), (x[1], x[2], w[3])]
            c_in.append((x[-1], w[4]))
        c = add(_spec(rng, "c"))
        edges += [(x, c, ww) for x, ww in c_in]
        if router:
            edges.append((r, c, words()))
        tail = c
        if skip:
            j = add(_spec(rng, "j"))
            edges += [(start, j, words()), (c, j, words())]
            tail = j
    nodes = [LayerSpec(f"{n.name}{i}", n.kind, n.n_in, n.n_out, n.h_in,
                       n.w_in, n.kh, n.kw, n.stride)
             for i, n in enumerate(nodes)]
    return GraphIR("bundles", tuple(nodes),
                   tuple(EdgeSpec(s, t, w) for s, t, w in edges))


def _budgets(g):
    f = g.node_features()
    terms = np.concatenate([f[:, M.F_OUT_PRE], [e.words for e in g.edges]])
    return [math.inf] + sorted(set(np.quantile(terms, [0.3, 0.6, 0.9])))


def _check_against_brute_force(g, budget):
    try:
        want = fusion.brute_force_min_bw(g, sram_budget_words=budget)
    except InfeasibleBudgetError:
        with pytest.raises(InfeasibleBudgetError):
            fusion.segment_dp_min_bw(g, sram_budget_words=budget)
        return
    got = fusion.segment_dp_min_bw(g, sram_budget_words=budget)
    assert got.engine == "segment_dp" and got.exact
    assert got.group_cost_words == want.group_cost_words
    assert fusion.is_valid_cuts(g, got.cuts)
    assert fusion.graph_max_intermediate(g, got.cuts) <= budget
    assert fusion._graph_cost(g, got.cuts) == got.group_cost_words


@pytest.fixture
def fresh_memo():
    fusion._SEGMENT_MEMO.clear()
    yield
    fusion._SEGMENT_MEMO.clear()


@pytest.mark.parametrize("seed", range(12))
def test_random_bundle_dags_match_brute_force(seed, fresh_memo):
    g = bundle_dag(np.random.default_rng(seed))
    assert g.n_edges <= fusion.MAX_EXHAUSTIVE_EDGES
    for budget in _budgets(g):
        _check_against_brute_force(g, budget)


@pytest.mark.parametrize("seed", range(12, 24))
def test_bundles_solved_by_counts_match_brute_force(seed, monkeypatch,
                                                    fresh_memo):
    """With the frontier DP held to width 1, every segment that holds a
    bundle is solved by counts over its branches."""
    monkeypatch.setattr(fusion, "FRONTIER_DP_MAX_WIDTH", 1)
    solved = []
    real = fusion._bundle_min_bw
    monkeypatch.setattr(fusion, "_bundle_min_bw",
                        lambda g, b, budget: solved.append(b) or
                        real(g, b, budget))
    g = bundle_dag(np.random.default_rng(seed))
    for budget in _budgets(g):
        fusion._SEGMENT_MEMO.clear()
        _check_against_brute_force(g, budget)
    assert solved


def _mixtral():
    return frontend.transformer_graph(REGISTRY["mixtral-8x7b"], seq_len=512)


def _jamba(seq_len=512):
    return frontend.transformer_graph(REGISTRY["jamba2-mini"],
                                      seq_len=seq_len)


@pytest.mark.parametrize("graph", [_mixtral, resnet18_ir])
@pytest.mark.parametrize("sram", [math.inf, 0.5])
def test_same_minimum_as_frontier_dp(graph, sram, fresh_memo):
    """Within the width cap the dispatch keeps the frontier DP; the segment
    DP finds the same minimum.  A finite budget is taken as a share of the
    largest node term, so some groupings are infeasible."""
    g = graph()
    if math.isfinite(sram):
        sram = sram * float(g.node_features()[:, M.F_OUT_PRE].max())
    dp = fusion.frontier_dp_min_bw(g, sram_budget_words=sram)
    res = fusion.optimal_cuts(g, sram_budget_words=sram)
    assert res.engine == "frontier_dp"
    assert res.group_cost_words == dp.group_cost_words
    seg = fusion.segment_dp_min_bw(g, sram_budget_words=sram)
    assert seg.group_cost_words == dp.group_cost_words
    assert fusion.graph_max_intermediate(g, seg.cuts) <= sram


def test_jamba_period_is_certified_and_fully_fused(fresh_memo):
    g = _jamba()
    assert fusion.topo_frontier_width(g, fusion.min_width_topo_order(g)) > \
        fusion.FRONTIER_DP_MAX_WIDTH
    res = fusion.optimal_cuts(g)
    assert res.engine == "segment_dp" and res.exact
    fused = fusion._graph_cost(g, np.zeros(g.n_edges, bool))
    assert res.group_cost_words == fused
    # a finite budget: certified, and within the budget
    budget = float(2 ** 29)
    res = fusion.optimal_cuts(g, sram_budget_words=budget)
    assert res.engine == "segment_dp" and res.exact
    assert fusion.is_valid_cuts(g, res.cuts)
    assert fusion.graph_max_intermediate(g, res.cuts) <= budget


def test_jamba_period_splits_at_its_residual_adds():
    g = _jamba()
    seps = fusion.single_node_separators(g)
    assert seps[0] == 0 and seps[-1] == g.n_nodes - 1
    # the residual adds are the only joins at the model width
    adds = [v for v, n in enumerate(g.nodes) if n.kind == "elementwise"
            and n.n_in == REGISTRY["jamba2-mini"].d_model]
    assert len(adds) == 16 and set(adds) <= set(seps)
    assert adds[-1] == g.n_nodes - 1


def test_search_spans_and_segment_memo(fresh_memo):
    g = _jamba()
    t0 = spans.records()[-1].t1 if spans.records() else -math.inf
    fusion.optimal_cuts(g)
    fusion.optimal_cuts(g)
    searches = [r for r in spans.records("fusion.search") if r.t0 > t0]
    assert len(searches) == 2
    per = spans.per_call("fusion.search", "fusion.segment", t0)
    assert len(per) == 2
    assert per[0][1] > 0  # DP states kept on the cold search
    assert per[1][1] == 0  # every segment a memo hit
    assert searches[0].work == searches[1].work > 1
    splits = [r for r in spans.records("fusion.split") if r.t0 > t0]
    assert [r.work for r in splits] == [searches[0].work] * 2


def test_dense_wide_dag_declines_to_beam():
    rng = np.random.default_rng(3)
    n = fusion.FRONTIER_DP_MAX_WIDTH + 2
    nodes = [LayerSpec("src", "conv", 4, 4, 8, 8, 3, 3, 1)]
    nodes += [LayerSpec(f"m{i}", "conv", 4, 4 + i, 8, 8, 3, 3, 1)
              for i in range(n)]
    nodes.append(LayerSpec("join", "elementwise", 4, 4, 8, 8))
    edges = [EdgeSpec(0, i + 1, 256) for i in range(n)]
    edges += [EdgeSpec(i + 1, n + 1, nodes[i + 1].out_words)
              for i in range(n)]
    edges += [EdgeSpec(i + 1, j + 1, 64) for i in range(n)
              for j in range(i + 1, n) if rng.random() < 0.4]
    g = GraphIR("dense", tuple(nodes), tuple(edges))
    for _ in range(2):  # declined, then the memo's decline
        with pytest.raises(fusion.SearchDeclined):
            fusion.segment_dp_min_bw(g)
    res = fusion.optimal_cuts(g)
    assert res.engine == "beam" and not res.exact


def test_planning_service_plans_the_jamba_period_exactly():
    g = _jamba(4096)
    grid = config_space_grid(f1s=(2, 4), f2s=(2, 4), f3s=(2,), f4s=(2,),
                             bus_widths=(4,), sram_splits=("unified",))
    loose = Constraints(math.inf, math.inf, math.inf, math.inf)
    resp = PlanningService(config_space=grid).plan(
        PlanRequest(graph=g, constraints=loose))
    assert resp.ok and not resp.degraded, resp.error
    assert resp.exact and resp.engine == "segment_dp"
