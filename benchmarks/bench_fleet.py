"""Fleet benchmark — the XLA recompile tax vs shape buckets + run_fleet.

``BENCH_search.json`` showed the steady-state sweep is nearly free while the
*cold* path is dominated by per-shape XLA compilation: every distinct graph
signature pays its own compile.  This benchmark times a multi-model sweep
over every in-repo workload three ways, each in a **fresh subprocess** (cold
caches, the honest serving-system number):

* ``sequential`` — per-graph :func:`repro.core.flow.run_flow` with
  ``bucket=False`` (the pre-bucketing behaviour: one XLA compile per
  distinct graph shape);
* ``bucketed``   — per-graph ``run_flow`` with shape buckets (default): all
  workloads share one ``(L, E, C)`` bucket, so the fleet pays ONE compile;
* ``fleet``      — :func:`repro.core.flow.run_fleet`: all graphs stacked
  and evaluated as a single vmapped XLA program (one compile, one dispatch).

Each child re-runs the loop a second time for the steady-state split, and
reports the per-graph best metrics so the parent can assert all three modes
agree bit-for-bit — plus the executable-cache accounting (``bucketed`` and
``fleet`` must compile exactly once).  Groupings use the paper's pool-
boundary policy so the timed section isolates the evaluator cold path
rather than the (mode-independent) grouping search.

Writes ``BENCH_fleet.json`` at the repo root.

Usage: ``python benchmarks/bench_fleet.py [--smoke]`` (``--smoke`` = the
six-workload subset, for the CI smoke job).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
OUT = ROOT / "BENCH_fleet.json"

try:  # running from a checkout without `pip install -e .`
    import repro  # noqa: F401
except ImportError:  # pragma: no cover
    sys.path.insert(0, str(ROOT / "src"))

from machine_meta import machine_metadata


def _workloads(smoke: bool):
    """name -> GraphIR for every in-repo workload (distinct shapes)."""
    from repro.core.frontend import mlp_block_graph, mobilenet_graph
    from repro.core.ir import (
        as_graph,
        encoder_decoder_ir,
        lm_ir,
        residual_block_ir,
        resnet18_ir,
        transformer_block_ir,
        vgg16_ir,
    )

    works = {
        "vgg16": as_graph(vgg16_ir(pool_mode="separate")),
        "resnet18": resnet18_ir(),
        "mobilenet": mobilenet_graph(),
        "mlp_block": as_graph(mlp_block_graph()),
        "encoder_decoder": encoder_decoder_ir(),
        "residual_block": residual_block_ir(),
    }
    if not smoke:
        works["vgg16_absorbed"] = as_graph(vgg16_ir(pool_mode="absorbed"))
        works["transformer_block"] = as_graph(transformer_block_ir(
            name="tb", d_model=1024, n_heads=16, n_kv_heads=16, d_ff=4096,
            seq_len=2048,
        ))
        works["lm_2block"] = as_graph(lm_ir(
            name="lm", n_layers=24, d_model=1024, n_heads=16, n_kv_heads=16,
            d_ff=4096, seq_len=2048, repeat=2,
        ))
        works["lm_3block"] = as_graph(lm_ir(
            name="lm3", n_layers=24, d_model=2048, n_heads=16, n_kv_heads=4,
            d_ff=8192, seq_len=1024, repeat=3,
        ))
    return works


def _metrics_rows(results) -> dict:
    return {
        name: [
            r.best_metrics.bandwidth_words,
            r.best_metrics.latency_cycles,
            r.best_metrics.energy_nj,
            r.best_metrics.area_um2,
        ]
        for name, r in results.items()
    }


def run_child(mode: str, smoke: bool) -> None:
    """One cold measurement in this (fresh) process; JSON on the last line,
    stamped with this process's own machine metadata.  The persistent
    compilation cache stays off: the compile tax is what this measures."""
    from repro.core import flow
    from repro.core.arch import Constraints

    loose = Constraints(*[float("inf")] * 4)
    works = _workloads(smoke)

    def sweep():
        if mode == "fleet":
            fl = flow.run_fleet(
                list(works.values()), groupings="pool", constraints=loose
            )
            results = dict(zip(works, fl.results))
            return results, fl.compile_seconds, fl.sweep_seconds
        bucket = mode == "bucketed"
        results = {
            name: flow.run_flow(
                g, groupings="pool", constraints=loose, bucket=bucket
            )
            for name, g in works.items()
        }
        compile_s = sum(r.compile_seconds for r in results.values())
        sweep_s = sum(r.sweep_seconds for r in results.values())
        return results, compile_s, sweep_s

    t0 = time.perf_counter()
    results, compile_s, sweep_s = sweep()
    cold_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    results2, _, steady_sweep = sweep()
    steady_wall = time.perf_counter() - t0

    stats = flow.sweep_cache_stats()
    expect = len(works) if mode == "sequential" else 1
    assert stats["misses"] == expect, (
        f"{mode}: expected {expect} compiled executable(s), "
        f"cache reports {stats}"
    )
    assert _metrics_rows(results) == _metrics_rows(results2)
    print(json.dumps({
        "machine": machine_metadata(),
        "mode": mode,
        "n_workloads": len(works),
        "cold_wall_s": round(cold_wall, 6),
        "steady_wall_s": round(steady_wall, 6),
        "compile_s": round(compile_s, 6),
        "sweep_s": round(sweep_s, 6),
        "steady_sweep_s": round(steady_sweep, 6),
        "executables_compiled": stats["misses"],
        "cache": stats,
        "best_metrics": _metrics_rows(results),
    }))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="six-workload subset (CI)")
    ap.add_argument("--mode", choices=["sequential", "bucketed", "fleet"],
                    help="(internal) run one cold measurement in-process")
    args = ap.parse_args()
    if args.mode:
        run_child(args.mode, args.smoke)
        return

    rows: dict[str, dict] = {}
    for mode in ("sequential", "bucketed", "fleet"):
        cmd = [sys.executable, str(pathlib.Path(__file__).resolve()),
               "--mode", mode]
        if args.smoke:
            cmd.append("--smoke")
        proc = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT)
        if proc.returncode != 0:  # surface the child's traceback in CI logs
            sys.stderr.write(proc.stdout)
            sys.stderr.write(proc.stderr)
            raise SystemExit(f"bench_fleet child --mode {mode} failed")
        rows[mode] = json.loads(proc.stdout.strip().splitlines()[-1])
        r = rows[mode]
        print(
            f"{mode:10s} cold {r['cold_wall_s']*1e3:8.0f} ms "
            f"(compile {r['compile_s']*1e3:7.0f} ms, "
            f"{r['executables_compiled']} executables)  "
            f"steady {r['steady_wall_s']*1e3:7.1f} ms"
        )

    # All three modes must agree bit-for-bit on every workload's best point.
    assert rows["sequential"]["best_metrics"] == rows["bucketed"]["best_metrics"]
    assert rows["sequential"]["best_metrics"] == rows["fleet"]["best_metrics"]

    seq, fleet = rows["sequential"], rows["fleet"]
    speedup_fleet = seq["cold_wall_s"] / fleet["cold_wall_s"]
    speedup_bucketed = seq["cold_wall_s"] / rows["bucketed"]["cold_wall_s"]
    record = {
        "bench": "fleet",
        "smoke": args.smoke,
        "machine": rows["fleet"]["machine"],
        "metric_note": (
            "cold_wall_s = first multi-model sweep in a fresh process "
            "(includes XLA compilation); steady_wall_s = the same sweep "
            "re-run with warm executable caches.  sequential compiles one "
            "executable per distinct graph shape; bucketed and fleet "
            "compile exactly one for the whole fleet (asserted via the "
            "sweep-cache accounting).  All modes are asserted bit-identical "
            "on every workload's best metrics."
        ),
        "n_workloads": seq["n_workloads"],
        "modes": rows,
        "cold_speedup_fleet_vs_sequential": round(speedup_fleet, 2),
        "cold_speedup_bucketed_vs_sequential": round(speedup_bucketed, 2),
    }
    OUT.write_text(json.dumps(record, indent=2) + "\n")
    print(f"\n[bench_fleet] {len(rows)} modes x {seq['n_workloads']} "
          f"workloads -> {OUT}")
    print(f"[bench_fleet] cold-path speedup: fleet {speedup_fleet:.1f}x, "
          f"bucketed run_flow {speedup_bucketed:.1f}x vs sequential")


if __name__ == "__main__":
    main()
