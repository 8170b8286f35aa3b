"""Shape-bucketed (padded/masked) evaluation and the fleet sweep.

Locks the PR's core claim: zero-padding a graph's evaluator arrays to a
shape bucket and evaluating through the masked kernels is **bit-identical**
to the unpadded batch path and the scalar ``*_ref`` oracles, for all four
metrics and the SRAM feasibility mask — so the bucketed `run_fleet` (and
`run_flow`, its one-graph form) changes compile economics, never results.
"""
import numpy as np
import pytest

from repro.core import flow, fusion, metrics as M
from repro.core.arch import Constraints, PAPER_OPTIMAL_CONFIG
from repro.core.frontend import mlp_block_graph, mobilenet_graph
from repro.core.ir import (
    LayerSpec,
    NetworkIR,
    as_graph,
    bucket_size,
    encoder_decoder_ir,
    pad_cuts_batch,
    pad_graph,
    resnet18_ir,
    vgg16_ir,
)

RELAXED = Constraints(*[1e15] * 4)
HW = PAPER_OPTIMAL_CONFIG


def _workloads():
    return {
        "vgg16": as_graph(vgg16_ir(pool_mode="separate")),
        "resnet18": resnet18_ir(),
        "mobilenet": mobilenet_graph(),
        "mlp_block": as_graph(mlp_block_graph()),
        "encoder_decoder": encoder_decoder_ir(),
    }


def _rng_cuts(g, rng, C=5):
    return rng.random((C, g.n_edges)) < 0.5


def _eval_unpadded(g, cuts, hw_rows, ac):
    feat = g.node_features()
    esrc, edst, ewords = g.edge_arrays()
    return np.asarray(M.evaluate_batch_graph(
        feat, esrc, edst, ewords, g.source_mask, g.sink_mask, cuts,
        hw_rows, ac,
    ))


def _eval_padded(g, cuts, hw_rows, ac, *, n_nodes=32, n_edges=64, n_rows=8):
    pg = pad_graph(g, n_nodes=n_nodes, n_edges=n_edges)
    pc = pad_cuts_batch(cuts, n_edges, n_rows)
    out = np.asarray(M.evaluate_batch_graph(
        pg.feat, pg.esrc, pg.edst, pg.ewords, pg.src_mask, pg.sink_mask,
        pc, hw_rows, ac, pg.node_mask, pg.edge_mask,
    ))
    return out[:, : cuts.shape[0]]


# ---------------------------------------------------------------------------
# Padding helpers
# ---------------------------------------------------------------------------


def test_bucket_size():
    assert [bucket_size(n) for n in (1, 2, 3, 4, 5, 17, 64)] == [
        1, 2, 4, 4, 8, 32, 64,
    ]
    assert bucket_size(3, floor=32) == 32
    assert bucket_size(33, floor=32) == 64


def test_pad_graph_shapes_and_masks():
    g = resnet18_ir()
    pg = pad_graph(g, n_nodes=32, n_edges=64)
    assert pg.feat.shape == (32, g.node_features().shape[1])
    assert pg.n_nodes == g.n_nodes and pg.n_edges == g.n_edges
    assert pg.node_mask.sum() == g.n_nodes and pg.edge_mask.sum() == g.n_edges
    assert not pg.node_mask[g.n_nodes :].any()
    assert not pg.src_mask[g.n_nodes :].any()
    assert not pg.sink_mask[g.n_nodes :].any()
    assert (pg.feat[g.n_nodes :] == 0).all()
    assert (pg.ewords[g.n_edges :] == 0).all()
    with pytest.raises(ValueError):
        pad_graph(g, n_nodes=8, n_edges=64)
    with pytest.raises(ValueError):
        pad_cuts_batch(np.zeros((3, 5), dtype=bool), 5, 2)


# ---------------------------------------------------------------------------
# Padding invariance — every in-repo workload, all four metrics + SRAM mask
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,g", _workloads().items(), ids=_workloads())
def test_padded_bit_identical_on_workloads(name, g):
    """Acceptance: padded/bucketed == unpadded batch == scalar oracles."""
    rng = np.random.default_rng(7)
    cuts = np.concatenate([
        flow.groupings_batch(g, "pool"), _rng_cuts(g, rng, C=3)
    ])
    hw_rows = np.stack([HW.as_row()])
    ac = M.area_consts_of(HW)
    ref = _eval_unpadded(g, cuts, hw_rows, ac)
    pad = _eval_padded(g, cuts, hw_rows, ac)
    assert np.array_equal(ref, pad)  # bit-identical, not approx
    for i in range(cuts.shape[0]):  # and both == the scalar oracles
        m = M.evaluate_ref(g, cuts[i], HW)
        assert pad[0, i, 0] == m.bandwidth_words
        assert pad[0, i, 1] == m.latency_cycles
        assert pad[0, i, 2] == m.energy_nj
        assert pad[0, i, 3] == m.area_um2

    # SRAM feasibility through the padded prefilter kernel.
    pg = pad_graph(g, n_nodes=32, n_edges=64)
    pc = pad_cuts_batch(cuts, 64)
    max_int = fusion.padded_max_intermediate_batch(pg, pc)
    assert np.array_equal(
        max_int, fusion.graph_max_intermediate_batch(g, cuts)
    )
    assert max_int[0] == fusion.graph_max_intermediate(g, cuts[0])
    budget = float(np.median(max_int))
    assert np.array_equal(
        fusion.padded_feasible_mask_batch(pg, pc, budget),
        fusion.graph_feasible_mask_batch(g, cuts, budget),
    )


def test_run_flow_bucketed_equals_unbucketed():
    # run_flow sweeps the bucketed (padded) program; its pick is the
    # argmin of the exact-shape sweep over the same cut batch: least
    # energy, ties to the least (bandwidth, latency, area, cut index).
    for g in (resnet18_ir(), as_graph(mlp_block_graph())):
        b = flow.run_flow(g, config_space=[HW], constraints=RELAXED,
                          groupings="search")
        cuts = flow.groupings_batch(g, "search")
        u = _eval_unpadded(g, cuts, HW.as_row()[None],
                           M.area_consts_of_space([HW]))[0]  # (C, 4)
        c = np.lexsort((u[:, 3], u[:, 1], u[:, 0], u[:, 2]))[0]
        assert b.best_metrics == M.Metrics(*map(float, u[c]))
        assert np.array_equal(b.best_cuts, cuts[c])
        assert b.n_candidates == u.shape[0]  # padded rows not counted


# ---------------------------------------------------------------------------
# Fleet sweep
# ---------------------------------------------------------------------------


def test_run_fleet_matches_run_flow_with_one_compile():
    works = _workloads()
    del works["encoder_decoder"]  # keep the search cheap; 4 graphs >= 4
    flow.clear_sweep_cache()
    fl = flow.run_fleet(list(works.values()), config_space=[HW],
                        constraints=RELAXED, groupings="search")
    stats = flow.sweep_cache_stats()
    assert stats["misses"] == 1  # ONE executable for the whole fleet
    assert fl.compile_seconds > 0.0
    assert fl.n_graphs == len(works)
    assert fl.n_candidates == sum(r.n_candidates for r in fl.results)
    for g, r in zip(works.values(), fl.results):
        solo = flow.run_flow(g, config_space=[HW], constraints=RELAXED,
                             groupings="search")
        assert r.best_metrics == solo.best_metrics
        assert np.array_equal(r.best_cuts, solo.best_cuts)
        assert r.best_cuts.shape == (g.n_edges,)
    # the per-graph bucketed flows above shared one more executable
    assert flow.sweep_cache_stats()["misses"] == 2


def test_run_fleet_sram_prefilter_and_errors():
    rb = _workloads()["mlp_block"]
    with pytest.raises(ValueError):
        flow.run_fleet([])
    budget = 1.0  # nothing fits: lbl grouping survives (no intermediates)
    fl = flow.run_fleet([rb], config_space=[HW], constraints=RELAXED,
                        groupings="search", sram_budget_words=budget)
    assert fl.results[0].n_pruned > 0
    assert fusion.graph_max_intermediate(rb, fl.results[0].best_cuts) <= budget


# ---------------------------------------------------------------------------
# Satellites: LRU sweep cache, pool dedupe, planner memo
# ---------------------------------------------------------------------------


def test_sweep_cache_is_lru_not_clear(monkeypatch):
    monkeypatch.setattr(flow, "SWEEP_CACHE_CAPACITY", 2)
    monkeypatch.setattr(flow, "_COMPILED_SWEEPS", type(flow._COMPILED_SWEEPS)())
    monkeypatch.setattr(
        flow, "_SWEEP_CACHE_STATS", {"hits": 0, "misses": 0, "evictions": 0}
    )
    flow._sweep_cache_put(("a",), "exe_a")
    flow._sweep_cache_put(("b",), "exe_b")
    assert flow._sweep_cache_get(("a",)) == "exe_a"  # refreshes a's recency
    flow._sweep_cache_put(("c",), "exe_c")  # evicts b (LRU), NOT everything
    assert flow._sweep_cache_get(("b",)) is None
    assert flow._sweep_cache_get(("a",)) == "exe_a"  # hot entry survived
    assert flow._sweep_cache_get(("c",)) == "exe_c"
    stats = flow.sweep_cache_stats()
    assert stats["evictions"] == 1 and stats["size"] == 2


def test_groupings_batch_pool_dedupes_degenerate_policy():
    # Every producer ends a pooling stage -> pool policy == layer-by-layer;
    # the duplicate row must not be scored twice.
    layers = tuple(
        LayerSpec(f"l{i}", "conv", 8, 8, 16, 16, 3, 3, 1, pool_after=2)
        for i in range(4)
    )
    g = as_graph(NetworkIR("allpool", layers))
    cuts = flow.groupings_batch(g, "pool")
    assert cuts.shape[0] == 1
    assert cuts.all()
    # VGG-16 keeps both distinct rows.
    assert flow.groupings_batch(as_graph(vgg16_ir()), "pool").shape[0] == 2


def test_plan_model_memoises_block_evaluation():
    from repro.configs import REGISTRY
    from repro.core import planner

    cfg = REGISTRY[sorted(REGISTRY)[0]]
    planner._block_bandwidths.cache_clear()
    p1 = planner.plan_model(cfg, 4096)
    info = planner._block_bandwidths.cache_info()
    assert info.misses == 1
    p2 = planner.plan_model(cfg, 4096)
    info = planner._block_bandwidths.cache_info()
    assert info.hits == 1 and info.misses == 1
    assert p1 == p2


# The hypothesis property test for padding invariance on random DAGs lives
# in tests/test_padding_property.py: the suite convention puts
# pytest.importorskip("hypothesis") at module top, which skips the WHOLE
# module when hypothesis is absent — the deterministic locks above must
# still run in that environment.
