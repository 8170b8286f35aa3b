"""Search-engine benchmark — scalar path vs batched engine vs frontier DP.

Times enumeration, brute force, greedy/beam merge search, and the exact
frontier-state DP on the three DAG builders (residual block,
encoder-decoder, ResNet-18) and writes ``BENCH_search.json`` at the repo
root with candidates/s and the speedup vs each case's baseline (the
preserved ``fusion._*_scalar`` implementations, or — for the DP cases —
the 2^E flat enumeration / beam search it supersedes).  Cases where the
baseline is intractable (2^21 patterns through a per-pattern Python
filter) report batched-only throughput.

Whenever both paths run, the benchmark also asserts the agreed-on
invariant — bit-identical cut vectors, bit-identical minimum cost
(frontier DP vs brute force), or exact-at-most-heuristic (frontier DP vs
beam) — a free regression check in CI.

Usage: ``python benchmarks/bench_search.py [--smoke]`` (``--smoke`` = one
measured rep per case, for the CI smoke job).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import numpy as np

from machine_meta import machine_metadata
from repro.compile_cache import enable_compile_cache
from repro.core import fusion, metrics as M
from repro.core.ir import encoder_decoder_ir, residual_block_ir, resnet18_ir

ROOT = pathlib.Path(__file__).resolve().parents[1]
OUT = ROOT / "BENCH_search.json"


def _clear_engine_caches() -> None:
    fusion.enumerate_valid_edge_cuts.cache_clear()
    fusion._exhaustive_tables.cache_clear()
    fusion._frontier_dp_cached.cache_clear()


def _bench(fn, reps: int):
    """(result, best_seconds, cold_seconds) — cold includes one-time cache
    builds; best is the steady state the flow sees on repeated searches."""
    times = []
    out = None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        times.append(time.perf_counter() - t0)
    return out, min(times), times[0]


class Bench:
    def __init__(self, reps: int):
        self.reps = reps
        self.cases: list[dict] = []

    def case(
        self,
        name: str,
        *,
        batched,
        scalar=None,
        n_candidates: int | None = None,
        compare: str | None = "cuts",
        scalar_reps: int = 1,
    ) -> None:
        """``compare``: the invariant asserted between the two paths —
        "cuts" (bit-identical vectors), "cost" (bit-identical minimum
        group cost: the frontier-DP-vs-enumeration contract, ties may pick
        different optimal cuts), "cost_le" (exact at most heuristic), or
        None."""
        _clear_engine_caches()
        b_res, b_best, b_cold = _bench(batched, max(self.reps, 2))
        s_best = s_res = None
        if scalar is not None:
            s_res, s_best, _ = _bench(scalar, scalar_reps)
            if compare == "cuts":
                assert np.array_equal(
                    np.asarray(b_res.cuts), np.asarray(s_res.cuts)
                ), f"{name}: batched cuts differ from scalar"
            elif compare == "cost":
                assert b_res.group_cost_words == s_res.group_cost_words, (
                    f"{name}: {b_res.group_cost_words} != "
                    f"{s_res.group_cost_words}"
                )
            elif compare == "cost_le":
                assert b_res.group_cost_words <= s_res.group_cost_words, (
                    f"{name}: exact {b_res.group_cost_words} worse than "
                    f"heuristic {s_res.group_cost_words}"
                )
        row = {
            "name": name,
            "n_candidates": n_candidates,
            # batched_s: steady state (warm per-graph caches — what the flow
            # sees on repeated searches); batched_cold_s: first call, full
            # pipeline.  candidates_per_s is computed from the cold time so
            # it reports pipeline throughput, not a cache hit.
            "batched_s": round(b_best, 6),
            "batched_cold_s": round(b_cold, 6),
            "scalar_s": round(s_best, 6) if s_best is not None else None,
            # speedup: steady state; speedup_cold: first call incl. building
            # the per-graph memos (for beam there is no memo, so they agree).
            "speedup": round(s_best / b_best, 2) if s_best is not None else None,
            "speedup_cold": (
                round(s_best / b_cold, 2) if s_best is not None else None
            ),
            "candidates_per_s": (
                round(n_candidates / b_cold) if n_candidates else None
            ),
        }
        self.cases.append(row)
        sp = f"{row['speedup']}x" if row["speedup"] is not None else "n/a"
        print(
            f"{name:42s} batched {b_best*1e3:9.3f} ms  "
            f"scalar {s_best*1e3 if s_best else float('nan'):9.3f} ms  "
            f"speedup {sp}"
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="one measured rep per case (CI)")
    args = ap.parse_args()
    enable_compile_cache(ROOT)
    reps = 2 if args.smoke else 5

    rb = residual_block_ir()
    ed = encoder_decoder_ir()
    rn = resnet18_ir()
    bench = Bench(reps)

    # -- enumeration -------------------------------------------------------
    bench.case(
        "enumerate.residual_block",
        batched=lambda: fusion.enumerate_valid_edge_cuts(rb),
        scalar=lambda: fusion._enumerate_valid_edge_cuts_scalar(rb),
        n_candidates=2**rb.n_edges,
        compare=None,
        scalar_reps=reps,
    )
    bench.case(
        "enumerate.encoder_decoder",  # 2^21 patterns: scalar path intractable
        batched=lambda: fusion.enumerate_valid_edge_cuts(ed),
        n_candidates=2**ed.n_edges,
    )

    # -- brute force (acceptance case: >= 10x on the residual block) ------
    bench.case(
        "brute_force.residual_block",
        batched=lambda: fusion.brute_force_min_bw(rb),
        scalar=lambda: fusion._brute_force_min_bw_scalar(rb),
        n_candidates=2**rb.n_edges,
        scalar_reps=reps,
    )
    budget_rb = 150_000.0
    bench.case(
        "brute_force.residual_block_sram_budget",
        batched=lambda: fusion.brute_force_min_bw(
            rb, sram_budget_words=budget_rb
        ),
        scalar=lambda: fusion._brute_force_min_bw_scalar(
            rb, sram_budget_words=budget_rb
        ),
        n_candidates=2**rb.n_edges,
        scalar_reps=reps,
    )
    bench.case(
        "brute_force.encoder_decoder",
        batched=lambda: fusion.brute_force_min_bw(ed),
        n_candidates=2**ed.n_edges,
    )

    # -- merge search (acceptance case: >= 10x on the ResNet-18 beam) -----
    bench.case(
        "greedy.resnet18",
        batched=lambda: fusion.greedy_merge_cuts(rn),
        scalar=lambda: fusion._greedy_merge_cuts_scalar(rn),
    )
    bench.case(
        "beam.resnet18",
        batched=lambda: fusion.beam_merge_cuts(rn),
        scalar=lambda: fusion._beam_merge_cuts_scalar(rn),
    )
    budget_rn = 200_000.0
    bench.case(
        "beam.resnet18_sram_budget",
        batched=lambda: fusion.beam_merge_cuts(
            rn, sram_budget_words=budget_rn
        ),
        scalar=lambda: fusion._beam_merge_cuts_scalar(
            rn, sram_budget_words=budget_rn
        ),
    )
    bench.case(
        "beam.encoder_decoder",
        batched=lambda: fusion.beam_merge_cuts(ed),
        scalar=lambda: fusion._beam_merge_cuts_scalar(ed),
    )

    # -- frontier DP (exact beyond the 2^E enumeration wall) --------------
    # Encoder-decoder: the DP's answer must be bit-identical in cost to the
    # 2^21 flat enumeration it supersedes (ties may differ in cuts), and the
    # acceptance bar is beating its cold wall clock outright.
    bench.case(
        "frontier_dp.encoder_decoder",
        batched=lambda: fusion.frontier_dp_min_bw(ed),
        scalar=lambda: fusion.brute_force_min_bw(ed),
        n_candidates=2**ed.n_edges,
        compare="cost",
    )
    # ResNet-18 (38 edges, 2^38 patterns): previously heuristic-only; the
    # exact DP optimum can only match or beat the beam answer.
    bench.case(
        "frontier_dp.resnet18",
        batched=lambda: fusion.frontier_dp_min_bw(rn),
        scalar=lambda: fusion.beam_merge_cuts(rn),
        compare="cost_le",
    )
    bench.case(
        "frontier_dp.resnet18_sram_budget",
        batched=lambda: fusion.frontier_dp_min_bw(
            rn, sram_budget_words=budget_rn
        ),
        scalar=lambda: fusion.beam_merge_cuts(
            rn, sram_budget_words=budget_rn
        ),
        compare="cost_le",
    )

    record = {
        "bench": "search",
        "smoke": args.smoke,
        "machine": machine_metadata(),
        "metric_note": (
            "speedup = scalar_s / batched_s (steady state: warm per-graph "
            "memos, what repeated searches in a flow pay); speedup_cold = "
            "scalar_s / batched_cold_s (first call, full pipeline incl. "
            "memo build — the honest number for one-shot use; the merge "
            "searches have no memo, so for them the two agree).  The "
            "frontier_dp.* cases baseline against what they supersede: "
            "the 2^E flat enumeration (encoder_decoder, cost asserted "
            "bit-identical) or beam search (resnet18, exact asserted <= "
            "heuristic)"
        ),
        "graphs": {
            "residual_block": {"nodes": len(rb.nodes), "edges": rb.n_edges},
            "encoder_decoder": {"nodes": len(ed.nodes), "edges": ed.n_edges},
            "resnet18": {"nodes": len(rn.nodes), "edges": rn.n_edges},
        },
        "cases": bench.cases,
    }
    OUT.write_text(json.dumps(record, indent=2) + "\n")
    print(f"\n[bench_search] {len(bench.cases)} cases -> {OUT}")

    acceptance = {
        c["name"]: f"{c['speedup']}x steady-state / {c['speedup_cold']}x cold"
        for c in bench.cases
        if c["name"] in (
            "brute_force.residual_block",
            "beam.resnet18",
            "frontier_dp.encoder_decoder",
        )
    }
    dp_ed = next(
        c for c in bench.cases if c["name"] == "frontier_dp.encoder_decoder"
    )
    assert dp_ed["speedup_cold"] > 1.0, (
        "frontier DP must beat cold 2^21 enumeration wall-clock"
    )
    print(f"[bench_search] acceptance speedups: {acceptance}")


if __name__ == "__main__":
    main()
