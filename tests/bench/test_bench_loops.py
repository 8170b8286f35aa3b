"""The three loops on the CPU against a 12-point design space: rates are
the work over the whole window, the slice merge keeps ``run_fleet``'s tie
order, a sound run is correct, and every cell of the real
``BENCHMARK.json`` reports the metrics that file names for it."""
import json
import time

import numpy as np
import pytest

import benchkit
import drivers
import reference as R
from harness import load_metric, load_spec, run_cell

REAL = json.loads((benchkit.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", ["vgg16.exhaustive", "mixtral-8x7b.search",
                                  "mixtral-8x7b.serve"])
def test_sound_run_is_correct(tmp_path, cell):
    res = benchkit.run_small(tmp_path, cell, seconds=1.5)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", [w["name"] for w in REAL["workloads"]])
def test_every_benchmark_cell_reports_its_end_to_end_metrics(tmp_path, cell):
    """The real BENCHMARK.json's entry of ``cell`` run at CPU size: its loop
    reports every end-to-end metric the file applies to the cell, and every
    per-layer metric it names for the cell has a reader and moves one of
    those."""
    real = benchkit.ROOT / "BENCHMARK.json"
    root = benchkit.small_root(tmp_path)
    res = run_cell(cell, 11, 1.5, False, t_start=time.perf_counter(),
                   bench_json=real, root=root, require_tpu=False,
                   compile_cache=False)
    spec = load_spec(real, cell)
    ends = {m["name"] for m in spec.end_to_end}
    assert "setup_s" in ends and len(ends) >= 2
    assert set(res["metrics"]) == ends
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["correct"] is True, res["checks"]
    assert spec.per_layer
    for m in spec.per_layer:
        assert m["moves"] in ends, m["name"]
        assert callable(load_metric(m["name"], root).read)


def test_rate_is_all_work_over_whole_window(tmp_path):
    from harness import Spans, load_config, load_traffic

    root = benchkit.small_root(tmp_path)
    cfg, mod = load_config("vgg16", root)
    d = drivers.SliceLoop(seed=5, config=cfg, config_mod=mod,
                          traffic=load_traffic("exhaustive", root),
                          spans=Spans(), out=None)
    d.setup()
    t0 = time.perf_counter()
    e2e = d.run(1.0)
    wall = time.perf_counter() - t0
    c = d.counters
    H, C = c["shape"][2], c["shape"][3]
    assert c["candidates"] == c["attempted"] * H * C
    assert c["window_s"] >= 1.0 and c["window_s"] <= wall
    assert e2e["sweep_cand_per_s"] == c["candidates"] / c["window_s"]


def test_slice_merge_keeps_run_fleet_tie_order():
    from repro.core import arch, flow, fusion
    from repro.core.ir import as_graph, vgg16_ir

    g = as_graph(vgg16_ir(pool_mode="separate"))
    grid = arch.config_space_grid(f1s=(2, 4), f2s=(2, 4), f3s=(2,),
                                  f4s=(2,), bus_widths=(4,),
                                  sram_splits=("unified",))
    hw_index = {c: i for i, c in enumerate(grid)}
    cuts = np.asarray(fusion.enumerate_valid_edge_cuts(g))
    rng = np.random.default_rng(0)
    base = cuts[rng.permutation(len(cuts))[:96]]
    # every grouping twice, so equal metric rows tie across slices
    batch = np.concatenate([base, base[::-1]])
    loose = arch.Constraints(*[float("inf")] * 4)
    C = 64
    won = []
    for s in range(len(batch) // C):
        sl = batch[s * C:(s + 1) * C]
        r = flow.run_fleet([g], config_space=grid, constraints=loose,
                           groupings=[sl]).results[0]
        c = drivers.index_of_row(sl, r.best_cuts)
        m = drivers.metrics_row(r.best_metrics)
        won.append((R.key(m, hw_index[r.best_hw], s * C + c), m))
    best = drivers.merge(won)
    one = flow.run_fleet([g], config_space=grid, constraints=loose,
                         groupings=[batch]).results[0]
    assert best[0][4] == hw_index[one.best_hw]
    assert best[0][5] == drivers.index_of_row(batch, one.best_cuts)
    assert best[1] == drivers.metrics_row(one.best_metrics)
