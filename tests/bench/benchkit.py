"""Builds a small copy of the benchmark's data for CPU tests: the same
configurations and mixes with a 12-point design space and short slices,
found by the same harness code."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

SMALL_SPACE = {"f1": [2, 4], "f2": [2, 4], "f3": [2, 4], "f4": [2],
               "bus_widths": [4], "sram_splits": [["unified", 0.1]]}
SMALL_TRAFFIC = {
    "exhaustive": {"c_slice": 16384, "sample_cells_per_call": 16,
                   "warmup_calls": 1},
    "exhaustive_4chip": {"c_slice": 16384, "sample_cells_per_call": 16,
                         "warmup_calls": 1},
    "search": {"seq_lens": [512, 1024], "sample_cells_per_call": 16},
    "serve": {"seq_lens": [512, 1024], "rate_per_s": 4.0, "n_caps": 4,
              "grace_s": 30.0, "service": {"max_batch": 2}},
}


# The cells the loops are tested on, whether or not BENCHMARK.json holds
# them today: (cell, config, traffic, chips, end-to-end metrics, per-layer).
CELLS = [
    ("vgg16.exhaustive", "vgg16", "exhaustive", 1, ["sweep_cand_per_s"],
     ["sweep.device_ns_per_kcand", "fleet_sweep_roofline",
      "fleet.host_ms_per_call", "device.idle_pct.sweep"]),
    ("vgg16.exhaustive.4chip", "vgg16", "exhaustive_4chip", 4,
     ["sweep_cand_per_s"], ["fleet.host_ms_per_call"]),
    ("mixtral-8x7b.search", "mixtral-8x7b", "search", 1, ["plans_per_s"],
     ["frontend.trace_ms_per_plan", "fusion.search_ms_per_plan",
      "device.idle_pct.plans"]),
    ("mixtral-8x7b.serve", "mixtral-8x7b", "serve", 1,
     ["plan_p95_ms", "plan_goodput_per_s"],
     ["service.cache_hit_pct", "service.degraded_pct", "client.late_ms_p95",
      "device.idle_pct.serve"]),
]


def bench_json() -> dict:
    """A BENCHMARK.json naming :data:`CELLS`, each metric limited to the
    cells that report it."""
    e2e, per = {"setup_s": None}, {}
    for cell, _c, _t, _n, ends, layers in CELLS:
        for m in ends:
            e2e.setdefault(m, []).append(cell)
        for m in layers:
            per.setdefault(m, []).append(cell)
    metric = lambda n, ws: dict(  # noqa: E731
        {"name": n, "unit": "u", "better": "lower", "source": "host_clock"},
        **({} if ws is None else {"workloads": ws}))
    return {
        "workloads": [{"name": c, "config": cfg, "traffic": t, "chips": n,
                       "why": "test"} for c, cfg, t, n, _e, _l in CELLS],
        "end_to_end": [dict(metric(n, ws), bound=0.1)
                       for n, ws in e2e.items()],
        "per_layer": [dict(metric(n, ws), layer="test", moves="setup_s")
                      for n, ws in per.items()],
    }


def small_root(tmp: pathlib.Path) -> pathlib.Path:
    """A bench directory under ``tmp`` holding the real configurations and
    mixes, cut to CPU size, the real metric readers and limits, and a
    ``BENCHMARK.json`` naming the cells of :data:`CELLS`."""
    root = tmp / "bench"
    for sub in ("configs", "traffic"):
        (root / sub).mkdir(parents=True)
    shutil.copytree(BENCH / "metrics", root / "metrics")
    shutil.copy(BENCH / "limits.json", root / "limits.json")
    for js in (BENCH / "configs").glob("*.json"):
        cfg = json.loads(js.read_text())
        cfg["design_space"].update(SMALL_SPACE)
        (root / "configs" / js.name).write_text(json.dumps(cfg))
        shutil.copy(js.with_suffix(".py"), root / "configs" / f"{js.stem}.py")
    for js in (BENCH / "traffic").glob("*.json"):
        t = json.loads(js.read_text())
        t.update(SMALL_TRAFFIC.get(js.stem, {}))
        (root / "traffic" / js.name).write_text(json.dumps(t))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench_json()))
    return root


def run_small(tmp, cell, seed=7, seconds=2.0, trace=False, **kw):
    """One run of ``cell`` on the CPU against :func:`small_root`."""
    import time

    from harness import run_cell

    root = small_root(pathlib.Path(tmp))
    return run_cell(cell, seed, seconds, trace, t_start=time.perf_counter(),
                    bench_json=root.parent / "BENCHMARK.json", root=root,
                    require_tpu=False, compile_cache=False, **kw)
