"""Serving benchmark — latency and degradation of the planning service.

Drives a paced request stream (deterministic inter-arrival at an offered
QPS) through :class:`repro.core.service.PlanningService` at several load
levels, with and without active fault injection
(:class:`repro.testing.faults.FaultInjector`: recurring transient sweep
failures + executable-cache eviction storms).  Each (load, fault-mode)
measurement runs in a **fresh subprocess** — cold process, one warmup plan
to populate the executable cache (serving steady state), then the timed
stream.

Reported per level: p50/p99 response latency, achieved QPS, degradation
rate (responses answered below the exact rung by the deadline ladder),
plan-cache hit rate, and the response taxonomy split.  The parent asserts
the service contract before writing anything: every request — faults or
not — got exactly one typed response.

With ``--async`` three more measurement families run (PR 9):

* the same paced stream through :class:`AsyncPlanningService`
  (submit-to-future-resolution latency, i.e. transport included);
* **cancellation latency** — chunk-stalled sweeps cancelled mid-flight,
  measuring cancel-to-response time (the chunk-boundary guarantee);
* **recovery replay** — a journaled run killed mid-stream, then
  ``PlanningService.recover`` timed: WAL replay cost and the re-run cost
  for the requests the crash left in flight.

Writes ``BENCH_serve.json`` at the repo root.

Usage: ``python benchmarks/bench_serve.py [--smoke] [--async]``
(``--smoke`` = one load level, fewer requests, for the CI smoke job).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
OUT = ROOT / "BENCH_serve.json"

try:  # running from a checkout without `pip install -e .`
    import repro  # noqa: F401
except ImportError:  # pragma: no cover
    sys.path.insert(0, str(ROOT / "src"))

from machine_meta import machine_metadata

# Per-request deadline: generous at idle, binding once a backlog forms —
# the knob that makes the degradation ladder visible under load.  Sized
# against the heaviest workload in the stream (resnet18: ~25 ms exact
# frontier-DP search), so queue wait under load pushes requests down the
# ladder instead of straight to DeadlineExceeded.
DEADLINE_S = 0.06


def _percentile(xs: list[float], q: float) -> float:
    ys = sorted(xs)
    if not ys:
        return float("nan")
    i = min(len(ys) - 1, int(round(q * (len(ys) - 1))))
    return ys[i]


def run_child(qps: float, n: int, faults: bool) -> None:
    """One paced-stream measurement in this (fresh) process; JSON on the
    last line."""
    from repro.core.arch import Constraints, paper_config_space
    from repro.core.errors import EvaluatorError
    from repro.core.ir import resnet18_ir
    from repro.core.service import PlanRequest, PlanningService
    from repro.testing.faults import FaultInjector, _valid_graphs

    injector = (
        FaultInjector(transient_every=3, evict_every=5) if faults else None
    )
    svc = PlanningService(
        config_space=paper_config_space(),
        # Loose constraints: the benchmark measures serving behaviour, so
        # the only failure modes left are deadlines and injected faults.
        constraints=Constraints(*[float("inf")] * 4),
        faults=injector,
        backoff_seconds=0.0,
        max_batch=16,
        max_queue_depth=4 * n,
    )
    graphs = _valid_graphs() + [resnet18_ir()]
    # Cycle length coprime to the graph cycle, so the stream covers the
    # full (graph, budget) key space yet still repeats -> cache hits.
    budgets = [float("inf"), 4e6, 1e6]

    # Warmup: compile the fleet executable once (steady-state serving).
    svc.plan(PlanRequest(graph=graphs[0]))

    interval = 1.0 / qps
    rids = []
    t_start = time.perf_counter()
    for i in range(n):
        target = t_start + i * interval
        # Pace: tick while waiting for the next arrival, else sleep.
        while time.perf_counter() < target:
            if svc.queue_depth:
                svc.tick()
            else:
                time.sleep(min(1e-4, max(0.0,
                                         target - time.perf_counter())))
        rids.append(svc.submit(PlanRequest(
            graph=graphs[i % len(graphs)],
            sram_budget_words=budgets[i % len(budgets)],
            deadline_seconds=DEADLINE_S,
        )))
    svc.drain()
    wall = time.perf_counter() - t_start

    latencies, outcomes = [], {}
    n_ok = n_degraded = n_cached = 0
    for rid in rids:
        resp = svc.collect(rid)
        assert resp is not None, f"request {rid}: no response"
        if resp.ok:
            n_ok += 1
            n_degraded += resp.degraded
            n_cached += resp.from_cache
            key = f"ok:{resp.rung or 'cache'}"
        else:
            assert isinstance(resp.error, EvaluatorError), (
                f"request {rid}: untyped {type(resp.error).__name__}"
            )
            key = resp.error_type
        outcomes[key] = outcomes.get(key, 0) + 1
        latencies.append(resp.latency_seconds)

    stats = svc.stats()
    print(json.dumps({
        "machine": machine_metadata(),
        "qps_offered": qps,
        "faults": faults,
        "n_requests": n,
        "achieved_qps": round(n / wall, 2),
        "p50_ms": round(_percentile(latencies, 0.50) * 1e3, 3),
        "p99_ms": round(_percentile(latencies, 0.99) * 1e3, 3),
        "ok_rate": round(n_ok / n, 4),
        "degradation_rate": round(n_degraded / max(n_ok, 1), 4),
        "cache_hit_rate": round(n_cached / max(n_ok, 1), 4),
        "outcomes": outcomes,
        "transient_retries": stats["counters"].get("transient_retries", 0),
        "injected": dict(injector.counts) if injector else {},
        "plan_cache": stats["plan_cache"],
    }))


def run_child_async(qps: float, n: int) -> None:
    """The paced stream through the async transport.  Latency here is
    submit-to-future-resolution wall clock — inbox wait, worker loop, and
    delivery included — the number a remote caller would see."""
    import concurrent.futures

    from repro.core.arch import Constraints, paper_config_space
    from repro.core.ir import resnet18_ir
    from repro.core.service import AsyncPlanningService, PlanRequest
    from repro.testing.faults import _valid_graphs

    svc = AsyncPlanningService(
        config_space=paper_config_space(),
        constraints=Constraints(*[float("inf")] * 4),
        backoff_seconds=0.0,
        max_batch=16,
        max_queue_depth=4 * n,
    )
    graphs = _valid_graphs() + [resnet18_ir()]
    budgets = [float("inf"), 4e6, 1e6]
    svc.plan(PlanRequest(graph=graphs[0]), timeout=300)  # warmup compile

    latencies: list[float] = []  # appended from done-callbacks (GIL-atomic)
    futs = []
    interval = 1.0 / qps
    t_start = time.perf_counter()
    for i in range(n):
        target = t_start + i * interval
        while time.perf_counter() < target:
            time.sleep(min(1e-4, max(0.0, target - time.perf_counter())))
        t_sub = time.perf_counter()
        fut = svc.submit(PlanRequest(
            graph=graphs[i % len(graphs)],
            sram_budget_words=budgets[i % len(budgets)],
            deadline_seconds=DEADLINE_S,
        ))
        fut.add_done_callback(
            lambda f, t=t_sub: latencies.append(time.perf_counter() - t))
        futs.append(fut)
    concurrent.futures.wait(futs, timeout=300)
    wall = time.perf_counter() - t_start
    svc.shutdown(drain=True, timeout=300)

    assert all(f.done() for f in futs)
    responses = [f.result() for f in futs]
    n_ok = sum(r.ok for r in responses)
    outcomes: dict[str, int] = {}
    for r in responses:
        key = f"ok:{r.rung or 'cache'}" if r.ok else r.error_type
        outcomes[key] = outcomes.get(key, 0) + 1
    print(json.dumps({
        "machine": machine_metadata(),
        "qps_offered": qps,
        "n_requests": n,
        "achieved_qps": round(n / wall, 2),
        "p50_ms": round(_percentile(latencies, 0.50) * 1e3, 3),
        "p99_ms": round(_percentile(latencies, 0.99) * 1e3, 3),
        "ok_rate": round(n_ok / n, 4),
        "degradation_rate": round(
            sum(r.ok and r.degraded for r in responses) / max(n_ok, 1), 4),
        "outcomes": outcomes,
    }))


def run_child_cancel(rounds: int) -> None:
    """Mid-flight cancellation latency: every sweep is chunk-stalled so
    the cancel provably lands while the fleet program is running; the
    measured time is cancel() -> future resolution."""
    from repro.core.arch import Constraints, paper_config_space
    from repro.core.ir import residual_block_ir
    from repro.core.service import AsyncPlanningService, PlanRequest
    from repro.testing.faults import FaultInjector

    inj = FaultInjector(chunk_stall_seconds=0.05)
    svc = AsyncPlanningService(
        config_space=paper_config_space(),
        constraints=Constraints(*[float("inf")] * 4),
        backoff_seconds=0.0,
        hw_chunk=2,
        faults=inj,
    )
    g = residual_block_ir()
    lats = []
    for r in range(rounds):
        base = inj.counts["chunks"]
        # distinct budgets: cancelled answers are never cached, but keep
        # every round a genuine sweep regardless
        fut = svc.submit(PlanRequest(
            graph=g, sram_budget_words=float(2 ** r) * 1e5))
        deadline = time.perf_counter() + 60.0
        while inj.counts["chunks"] <= base:  # sweep provably in flight
            if time.perf_counter() > deadline:
                raise SystemExit("cancel bench: sweep never started")
            time.sleep(1e-3)
        t0 = time.perf_counter()
        assert svc.cancel(fut)
        resp = fut.result(timeout=300)
        lats.append(time.perf_counter() - t0)
        assert resp.error_type == "RequestCancelled", resp.error_type
    svc.shutdown(drain=True, timeout=300)
    print(json.dumps({
        "machine": machine_metadata(),
        "rounds": rounds,
        "chunk_stall_seconds": inj.chunk_stall_seconds,
        "hw_chunk": 2,
        "cancel_p50_ms": round(_percentile(lats, 0.50) * 1e3, 3),
        "cancel_p99_ms": round(_percentile(lats, 0.99) * 1e3, 3),
    }))


def run_child_recover(n: int) -> None:
    """Crash-recovery replay time: a journaled (fsync'd) run is killed
    mid-stream; recovery replays the WAL (timed) and re-runs what the
    crash left in flight (timed separately)."""
    import tempfile

    from repro.core import journal as journal_mod
    from repro.core.arch import Constraints, paper_config_space
    from repro.core.ir import resnet18_ir
    from repro.core.service import PlanRequest, PlanningService
    from repro.testing.faults import _valid_graphs

    tmp = tempfile.mkdtemp(prefix="bench_recover_")
    space = paper_config_space()
    kw = dict(
        config_space=space,
        constraints=Constraints(*[float("inf")] * 4),
        backoff_seconds=0.0,
    )
    svc = PlanningService(journal_dir=tmp, journal_fsync=True,
                          snapshot_every=0, **kw)
    graphs = _valid_graphs() + [resnet18_ir()]
    budgets = [float("inf"), 4e6, 1e6]
    for i in range(n):
        svc.submit(PlanRequest(
            graph=graphs[i % len(graphs)],
            sram_budget_words=budgets[i % len(budgets)],
        ))
        if i % 5 == 4:  # serve some of the stream before the "crash"
            svc.tick()
    pending_at_crash = svc.queue_depth
    svc.close()  # the crash: everything in memory is gone

    t0 = time.perf_counter()
    rec = PlanningService.recover(tmp, journal_fsync=True, snapshot_every=0,
                                  **kw)
    replay_s = time.perf_counter() - t0
    restored = len(rec._responses)
    assert rec.queue_depth == pending_at_crash
    t1 = time.perf_counter()
    rec.drain()
    rerun_s = time.perf_counter() - t1
    assert len(rec._responses) == n
    rec.close()
    _, records = journal_mod.load(tmp)
    print(json.dumps({
        "machine": machine_metadata(),
        "n_requests": n,
        "wal_records": len(records),
        "responses_restored": restored,
        "reenqueued": pending_at_crash,
        "replay_ms": round(replay_s * 1e3, 3),
        "rerun_ms": round(rerun_s * 1e3, 3),
    }))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="one load level, fewer requests (CI)")
    ap.add_argument("--async", dest="async_", action="store_true",
                    help="also measure the async transport, cancellation "
                         "latency, and recovery-replay time")
    ap.add_argument("--qps", type=float, help="(internal) child load level")
    ap.add_argument("--n", type=int, help="(internal) child request count")
    ap.add_argument("--faults", action="store_true",
                    help="(internal) child fault injection on")
    ap.add_argument("--mode", default="paced",
                    choices=("paced", "async", "cancel", "recover"),
                    help="(internal) child measurement family")
    args = ap.parse_args()
    if args.n:  # child processes always carry --n
        from repro.compile_cache import enable_compile_cache

        enable_compile_cache(ROOT)
        if args.mode == "paced":
            run_child(args.qps, args.n, args.faults)
        elif args.mode == "async":
            run_child_async(args.qps, args.n)
        elif args.mode == "cancel":
            run_child_cancel(args.n)
        else:
            run_child_recover(args.n)
        return

    levels = [100.0] if args.smoke else [25.0, 100.0, 400.0]
    n = 24 if args.smoke else 80
    rows: list[dict] = []
    for qps in levels:
        for faults in (False, True):
            cmd = [sys.executable, str(pathlib.Path(__file__).resolve()),
                   "--qps", str(qps), "--n", str(n)]
            if faults:
                cmd.append("--faults")
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  cwd=ROOT)
            if proc.returncode != 0:  # surface the child's traceback
                sys.stderr.write(proc.stdout)
                sys.stderr.write(proc.stderr)
                raise SystemExit(
                    f"bench_serve child qps={qps} faults={faults} failed"
                )
            row = json.loads(proc.stdout.strip().splitlines()[-1])
            rows.append(row)
            tag = "faults" if faults else "clean "
            print(
                f"qps {qps:6.0f} [{tag}] p50 {row['p50_ms']:8.2f} ms  "
                f"p99 {row['p99_ms']:8.2f} ms  "
                f"degraded {row['degradation_rate']*100:5.1f}%  "
                f"ok {row['ok_rate']*100:5.1f}%"
            )

    # Contract: every stream — with or without faults — is 100% answered
    # (the children already asserted per-request typed responses).
    for row in rows:
        assert sum(row["outcomes"].values()) == row["n_requests"], row
        assert row["ok_rate"] > 0, row
    # Fault injection really fired in every faulted stream.
    assert all(r["injected"].get("injected_transients", 0) > 0
               for r in rows if r["faults"])

    def _run_aux(mode: str, extra: list[str]) -> dict:
        cmd = [sys.executable, str(pathlib.Path(__file__).resolve()),
               "--mode", mode] + extra
        proc = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT)
        if proc.returncode != 0:
            sys.stderr.write(proc.stdout)
            sys.stderr.write(proc.stderr)
            raise SystemExit(f"bench_serve child mode={mode} failed")
        return json.loads(proc.stdout.strip().splitlines()[-1])

    async_rows: list[dict] = []
    cancel_row = recover_row = None
    if args.async_:
        for qps in levels:
            row = _run_aux("async", ["--qps", str(qps), "--n", str(n)])
            async_rows.append(row)
            print(
                f"qps {qps:6.0f} [async ] p50 {row['p50_ms']:8.2f} ms  "
                f"p99 {row['p99_ms']:8.2f} ms  "
                f"ok {row['ok_rate']*100:5.1f}%"
            )
            assert sum(row["outcomes"].values()) == row["n_requests"], row
        cancel_row = _run_aux(
            "cancel", ["--n", "4" if args.smoke else "8"])
        print(
            f"cancel latency      p50 {cancel_row['cancel_p50_ms']:8.2f} ms  "
            f"p99 {cancel_row['cancel_p99_ms']:8.2f} ms"
        )
        recover_row = _run_aux(
            "recover", ["--n", "12" if args.smoke else "32"])
        print(
            f"recovery            replay {recover_row['replay_ms']:8.2f} ms  "
            f"re-run {recover_row['rerun_ms']:8.2f} ms  "
            f"({recover_row['reenqueued']} in flight at crash)"
        )

    record = {
        "bench": "serve",
        "smoke": args.smoke,
        "machine": rows[0]["machine"],
        "metric_note": (
            "Paced request stream at each offered QPS in a fresh process "
            "(one warmup plan, then the timed stream).  Latency is "
            "submit-to-response per request; degradation_rate is the "
            "fraction of successful responses the deadline ladder answered "
            f"below the exact rung (deadline {DEADLINE_S}s).  'faults' "
            "rows run under active injection: a transient sweep failure "
            "every 3rd sweep and an executable-cache eviction storm every "
            "5th tick — the contract (one typed response per request) is "
            "asserted in both modes before this file is written."
        ),
        "deadline_seconds": DEADLINE_S,
        "levels": rows,
    }
    if args.async_:
        record["async_levels"] = async_rows
        record["cancellation"] = cancel_row
        record["recovery"] = recover_row
        record["async_note"] = (
            "async_levels: the same paced stream through "
            "AsyncPlanningService; latency is submit-to-future-resolution "
            "(transport included).  cancellation: chunk-stalled sweeps "
            "cancelled mid-flight, cancel()-to-response time — bounded by "
            "one hw_chunk boundary.  recovery: fsync'd journaled run "
            "killed mid-stream; replay_ms restores served responses "
            "bit-identically, rerun_ms re-answers the in-flight tail."
        )
    OUT.write_text(json.dumps(record, indent=2) + "\n")
    n_rows = len(rows) + len(async_rows)
    print(f"\n[bench_serve] {n_rows} measurement levels -> {OUT}")


if __name__ == "__main__":
    main()
