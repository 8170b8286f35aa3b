"""Config-zoo benchmark — every registry architecture through ONE fleet sweep.

PR 8's frontend extensions (batched ``dot_general`` -> attention actmuls,
``scan`` -> SSM state nodes, expert-branch expansion -> MoE fan-out) mean
every model in ``repro.configs.REGISTRY`` now traces to a GraphIR.  This
benchmark exercises that end to end: one superblock graph per architecture
(:func:`repro.core.frontend.transformer_graph`, the real model forward at
``seq_len=512``), each paired with an explicit cut batch —

* ``lbl``    — layer-by-layer (every edge cut; the paper's baseline);
* ``fused``  — fully fused (no cuts; infinite-SRAM upper bound);
* ``search`` — :func:`repro.core.fusion.optimal_cuts` optimum, for graphs
  with at most ``SEARCH_EDGE_CAP`` edges (the frontier DP certifies the
  small/medium zoo; the three widest graphs — jamba / arctic / llama4,
  569-1600 edges — skip the search row and this is recorded per config
  rather than silently dropped).

All graphs + batches go through a **single** :func:`repro.core.flow.run_fleet`
call (PR 4 shape buckets, PR 6 Pareto fronts), so the whole zoo pays one XLA
compile.  Per config the record carries the best hardware point, the winning
cuts, the Pareto front size, and the fused-vs-layer-by-layer bandwidth /
latency / energy reductions from :func:`repro.core.flow.compare_fusion`
evaluated at that best hardware point.

Writes ``BENCH_zoo.json`` at the repo root.

Usage: ``python benchmarks/bench_zoo.py [--smoke]`` (``--smoke`` = the
two small configs qwen3-0.6b + phi3-mini-3.8b, for the CI core lane).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
OUT = ROOT / "BENCH_zoo.json"

try:  # running from a checkout without `pip install -e .`
    import repro  # noqa: F401
except ImportError:  # pragma: no cover
    sys.path.insert(0, str(ROOT / "src"))

from machine_meta import machine_metadata

SEQ_LEN = 512
#: Exact search is run only for graphs at or below this edge count.  The
#: frontier DP certifies everything in the zoo up to gemma3 (98 edges) in
#: well under a second; the expert-fan-out giants (jamba 569, arctic 787,
#: llama4 1600 edges) fall to beam merge which takes minutes, so their
#: batches carry lbl + fused only and ``search_skipped`` marks them.
SEARCH_EDGE_CAP = 128
SMOKE_ARCHS = ("qwen3_0_6b", "phi3_mini_3_8b")


def _zoo_graphs(smoke: bool):
    """name -> GraphIR for the (sub)zoo, traced from the real modules."""
    from repro.configs import REGISTRY, resolve
    from repro.core.frontend import transformer_graph

    names = [resolve(a).name for a in SMOKE_ARCHS] if smoke else sorted(REGISTRY)
    return {n: transformer_graph(REGISTRY[n], seq_len=SEQ_LEN) for n in names}


def _cut_batches(graphs):
    """Per-graph explicit (C_i, E_i) cut batches + per-config search notes."""
    import numpy as np

    from repro.core import fusion

    batches, notes = [], {}
    for name, g in graphs.items():
        lbl = np.asarray(fusion.layer_by_layer_cuts(g), bool).reshape(-1)
        rows = [lbl, np.zeros_like(lbl)]
        if g.n_edges <= SEARCH_EDGE_CAP:
            res = fusion.optimal_cuts(g)
            rows.append(np.asarray(res.cuts, bool).reshape(-1))
            notes[name] = {"search_skipped": False, "engine": res.engine,
                           "exact": bool(res.exact)}
        else:
            notes[name] = {"search_skipped": True, "engine": None,
                           "exact": False}
        batches.append(np.stack(rows))
    return batches, notes


def run_child(smoke: bool) -> None:
    """The cold measurement in this (fresh) process; JSON on the last line,
    stamped with this process's own machine metadata."""
    from repro.compile_cache import enable_compile_cache
    from repro.core import flow
    from repro.core.arch import Constraints

    enable_compile_cache(ROOT)

    loose = Constraints(*[float("inf")] * 4)

    t0 = time.perf_counter()
    graphs = _zoo_graphs(smoke)
    trace_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    batches, notes = _cut_batches(graphs)
    search_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    fl = flow.run_fleet(
        list(graphs.values()), groupings=batches, constraints=loose,
        pareto=True,
    )
    fleet_wall = time.perf_counter() - t0

    configs = {}
    for (name, g), r in zip(graphs.items(), fl.results):
        comp = flow.compare_fusion(g, r.best_hw, r.best_cuts)
        # The batch always contains the lbl row, so the winner can only
        # improve on (or tie) layer-by-layer.
        assert comp.bw_reduction >= -1e-9, (name, comp.bw_reduction)
        configs[name] = {
            **notes[name],
            "n_nodes": len(g.nodes),
            "n_edges": int(g.n_edges),
            "n_feasible": int(r.n_feasible),
            "best_hw": dataclasses.asdict(r.best_hw),
            "best_cuts": [int(c) for c in r.best_cuts],
            "n_groups": len(r.group_sizes),
            "best_metrics": {
                "bandwidth_words": float(r.best_metrics.bandwidth_words),
                "latency_cycles": float(r.best_metrics.latency_cycles),
                "energy_nj": float(r.best_metrics.energy_nj),
                "area_um2": float(r.best_metrics.area_um2),
            },
            "pareto_points": int(r.pareto.metrics.shape[0]),
            "bw_reduction_vs_lbl": round(float(comp.bw_reduction), 6),
            "latency_reduction_vs_lbl": round(
                float(comp.latency_reduction), 6),
            "energy_reduction_vs_lbl": round(float(comp.energy_reduction), 6),
        }

    print(json.dumps({
        "machine": machine_metadata(),
        "n_configs": len(graphs),
        "trace_s": round(trace_s, 6),
        "search_s": round(search_s, 6),
        "fleet_wall_s": round(fleet_wall, 6),
        "compile_s": round(fl.compile_seconds, 6),
        "sweep_s": round(fl.sweep_seconds, 6),
        "n_candidates": int(fl.n_candidates),
        "candidates_per_second": round(fl.candidates_per_second, 1),
        "configs": configs,
    }))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="two-config subset (CI core lane)")
    ap.add_argument("--child", action="store_true",
                    help="(internal) run the cold measurement in-process")
    args = ap.parse_args()
    if args.child:
        run_child(args.smoke)
        return

    cmd = [sys.executable, str(pathlib.Path(__file__).resolve()), "--child"]
    if args.smoke:
        cmd.append("--smoke")
    # The child inherits the environment unchanged, so it sweeps on the
    # backend this command was given; the parent never touches JAX.
    proc = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT)
    if proc.returncode != 0:  # surface the child's traceback in CI logs
        sys.stderr.write(proc.stdout)
        sys.stderr.write(proc.stderr)
        raise SystemExit("bench_zoo child failed")
    row = json.loads(proc.stdout.strip().splitlines()[-1])

    record = {
        "bench": "zoo",
        "smoke": args.smoke,
        "metric_note": (
            "One run_fleet program over the whole config zoo: each "
            "architecture's real forward pass traced to GraphIR at "
            f"seq_len={SEQ_LEN}, swept over the default hardware space with "
            "an explicit per-graph cut batch (layer-by-layer / fully-fused "
            "/ optimal_cuts optimum for graphs <= "
            f"{SEARCH_EDGE_CAP} edges — wider graphs record "
            "search_skipped=true).  bw_reduction_vs_lbl is compare_fusion's "
            "fused-vs-layer-by-layer DRAM-traffic reduction at the "
            "per-config best hardware point; candidates_per_second counts "
            "(hw x cut) evaluations in the single compiled sweep."
        ),
        **row,
    }
    OUT.write_text(json.dumps(record, indent=2) + "\n")
    skipped = [n for n, c in row["configs"].items() if c["search_skipped"]]
    print(f"\n[bench_zoo] {row['n_configs']} configs, "
          f"{row['n_candidates']} candidates "
          f"({row['candidates_per_second']:.0f}/s) -> {OUT}")
    for name, c in row["configs"].items():
        print(f"  {name:28s} L={c['n_nodes']:4d} E={c['n_edges']:4d} "
              f"bw_red {100 * c['bw_reduction_vs_lbl']:5.1f}%  "
              f"pareto {c['pareto_points']:3d}")
    if skipped:
        print(f"[bench_zoo] exact search skipped (edges > {SEARCH_EDGE_CAP}):"
              f" {', '.join(skipped)}")


if __name__ == "__main__":
    main()
