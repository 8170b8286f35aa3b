"""Sharded co-search benchmark — candidates/s vs host device count.

PR 4 collapsed the fleet sweep to ONE XLA program; this benchmark measures
what sharding that program's *hardware axis* over a 1-D ``hardware`` mesh
buys as devices are added.  For each device count d in (1, 2, 4, 8) a
**fresh subprocess** (cold caches) is launched with
``XLA_FLAGS=--xla_force_host_platform_device_count=d``; d=1 runs the plain
single-device program (``devices=None``), d>1 runs
``run_fleet(devices=d)``.  Every child sweeps the same >= 1000-point
:func:`repro.core.arch.config_space_grid` co-search space with Pareto
extraction on, and reports best metrics + the full Pareto front so the
parent can assert the sharded sweep is **bit-identical** to the
single-device one at every d — the same guarantee the test suite pins at
2/8 devices — before any throughput number is written.

Speedup caveat: ``--xla_force_host_platform_device_count`` splits the host
CPU into d XLA devices regardless of how many physical cores exist, so
scaling saturates at the *core* count (a 1-core container shows ~1x at
every d — honest, and why the record embeds ``machine`` metadata).  Pass
``--require-speedup`` (multi-core CI runners) to assert >= 3x candidates/s
at 8 devices vs 1.

``--faults`` adds the fault-tolerance lanes (in-process, single device):
the wall-clock overhead of salvaging a chunked sweep through injected
shard failures (per-chunk RetryPolicy), and of a kill-at-mid-sweep +
checkpointed resume vs recomputing from scratch — with bit-identity and
exactly-once recomputation asserted before any number is reported.

Writes ``BENCH_shard.json`` at the repo root.

Usage: ``python benchmarks/bench_shard.py [--smoke] [--require-speedup]
[--faults]`` (``--smoke`` = pruned config grid and two workloads, for the
CI smoke job).
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
OUT = ROOT / "BENCH_shard.json"

try:  # running from a checkout without `pip install -e .`
    import repro  # noqa: F401
except ImportError:  # pragma: no cover
    sys.path.insert(0, str(ROOT / "src"))

from machine_meta import machine_metadata

DEVICE_COUNTS = (1, 2, 4, 8)


def _config_space(smoke: bool):
    from repro.core.arch import config_space_grid

    if smoke:  # 256 points: exercises the sharded path, fits the CI budget
        return config_space_grid(
            f1s=(2, 4), f2s=(2, 4), f3s=(2, 4), f4s=(2, 4),
            bus_widths=(2, 4), sram_splits=("unified",),
        )
    return config_space_grid()  # 2560-point co-search space


def _workloads(smoke: bool):
    from repro.core.ir import (
        as_graph,
        encoder_decoder_ir,
        residual_block_ir,
        resnet18_ir,
        vgg16_ir,
    )

    works = {
        "resnet18": resnet18_ir(),
        "residual_block": residual_block_ir(),
    }
    if not smoke:
        works["vgg16"] = as_graph(vgg16_ir(pool_mode="separate"))
        works["encoder_decoder"] = encoder_decoder_ir()
    return works


def _front_digest(front) -> dict:
    """Pareto front as JSON for cross-device bit-identity asserts."""
    return {
        "size": front.size,
        "metrics": front.metrics.tolist(),
        "hw_indices": front.hw_indices.tolist(),
        "cut_indices": front.cut_indices.tolist(),
    }


def run_child(n_devices: int, smoke: bool) -> None:
    """One cold sweep at this device count; JSON on the last line."""
    import jax

    assert len(jax.devices()) == n_devices, (
        f"child expected {n_devices} host devices, jax sees "
        f"{len(jax.devices())} (XLA_FLAGS not applied?)"
    )
    from repro.core import flow
    from repro.core.arch import Constraints

    loose = Constraints(*[float("inf")] * 4)
    space = _config_space(smoke)
    works = _workloads(smoke)
    devices = None if n_devices == 1 else n_devices

    def sweep():
        return flow.run_fleet(
            list(works.values()), config_space=space, constraints=loose,
            groupings="pool", devices=devices, pareto=True,
        )

    t0 = time.perf_counter()
    fl = sweep()
    cold_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    fl2 = sweep()
    steady_wall = time.perf_counter() - t0

    rows = {
        name: [
            r.best_metrics.bandwidth_words, r.best_metrics.latency_cycles,
            r.best_metrics.energy_nj, r.best_metrics.area_um2,
        ]
        for name, r in zip(works, fl.results)
    }
    rows2 = {
        name: [
            r.best_metrics.bandwidth_words, r.best_metrics.latency_cycles,
            r.best_metrics.energy_nj, r.best_metrics.area_um2,
        ]
        for name, r in zip(works, fl2.results)
    }
    assert rows == rows2, "steady-state re-run changed the best points"
    stats = flow.sweep_cache_stats()
    assert stats["misses"] == 1, (
        f"expected ONE compiled executable for the sharded fleet, "
        f"cache reports {stats}"
    )
    print(json.dumps({
        "n_devices": n_devices,
        "device_count_used": fl.device_count,
        "n_workloads": len(works),
        "n_hw_configs": len(space),
        "n_candidates": fl.n_candidates,
        "cold_wall_s": round(cold_wall, 6),
        "steady_wall_s": round(steady_wall, 6),
        "compile_s": round(fl.compile_seconds, 6),
        "sweep_s": round(fl.sweep_seconds, 6),
        "steady_sweep_s": round(fl2.sweep_seconds, 6),
        "candidates_per_s": round(fl2.candidates_per_second),
        "candidates_per_s_cold": round(fl.candidates_per_second),
        "best_metrics": rows,
        "pareto": {
            name: _front_digest(r.pareto)
            for name, r in zip(works, fl.results)
        },
        "machine": machine_metadata(),
    }))


def run_faults(smoke: bool) -> dict:
    """Fault-tolerance lanes: salvage overhead under injected shard
    failures, and checkpointed kill/resume overhead vs full recompute.
    Bit-identity and exactly-once recomputation are asserted before any
    timing is reported."""
    from repro.core import flow
    from repro.core.arch import Constraints
    from repro.core.errors import RetryPolicy
    from repro.testing.faults import FaultInjector

    loose = Constraints(*[float("inf")] * 4)
    space = _config_space(smoke)
    works = _workloads(smoke)
    hw_chunk = max(1, len(space) // 8)  # 8 chunks
    n_chunks = -(-len(space) // hw_chunk)
    policy = RetryPolicy(max_retries=3, backoff_seconds=0.0)

    def sweep(**kw):
        return flow.run_fleet(
            list(works.values()), config_space=space, constraints=loose,
            groupings="pool", hw_chunk=hw_chunk, **kw,
        )

    def best_rows(fl):
        return {
            name: [
                r.best_metrics.bandwidth_words, r.best_metrics.latency_cycles,
                r.best_metrics.energy_nj, r.best_metrics.area_um2,
            ]
            for name, r in zip(works, fl.results)
        }

    sweep()  # warm the executable cache: the lanes time salvage, not XLA
    t0 = time.perf_counter()
    clean = sweep()
    clean_wall = time.perf_counter() - t0

    # Lane 1: salvage — every 3rd chunk compute fails once, the per-chunk
    # RetryPolicy absorbs it, and the answer must not move a bit.
    inj = FaultInjector(shard_fail_every=3)
    t0 = time.perf_counter()
    salvaged = sweep(hooks=inj, retry_policy=policy)
    salvage_wall = time.perf_counter() - t0
    assert best_rows(salvaged) == best_rows(clean), (
        "salvaged sweep diverged from the clean sweep"
    )
    assert inj.counts["injected_shard_failures"] > 0

    # Lane 2: kill at the sweep's midpoint, resume from the checkpoint.
    class _Kill(Exception):
        pass

    kill_at = n_chunks // 2
    state = {"n": 0}

    def killer():
        state["n"] += 1
        if state["n"] > kill_at:
            raise _Kill()

    with tempfile.TemporaryDirectory() as ckpt:
        t0 = time.perf_counter()
        try:
            sweep(checkpoint_dir=ckpt, abort_check=killer)
        except _Kill:
            pass
        killed_wall = time.perf_counter() - t0
        resumed_inj = FaultInjector()
        t0 = time.perf_counter()
        resumed = sweep(checkpoint_dir=ckpt, hooks=resumed_inj)
        resume_wall = time.perf_counter() - t0
    assert resumed.chunks_restored == kill_at, (
        f"expected {kill_at} restored chunks, got {resumed.chunks_restored}"
    )
    assert resumed_inj.counts["chunk_computes"] == n_chunks - kill_at, (
        "resume recomputed already-durable chunks"
    )
    assert best_rows(resumed) == best_rows(clean), (
        "resumed sweep diverged from the clean sweep"
    )

    return {
        "metric_note": (
            "salvage lane: chunked sweep with every 3rd chunk compute "
            "failing once, absorbed by the per-chunk RetryPolicy (zero "
            "backoff) — overhead_vs_clean is the honest retry cost.  "
            "resume lane: sweep killed at the midpoint boundary, resumed "
            "from the SweepCheckpoint — resume_vs_full_recompute compares "
            "against recomputing everything.  At bench scale chunk "
            "compute is milliseconds, so checkpoint decode can dominate "
            "and the ratio exceed 1; it shrinks below 1 as per-chunk "
            "compute grows (the multi-hour co-searches the checkpoint "
            "exists for).  Bit-identity and exactly-once recomputation "
            "are asserted before either number is written."
        ),
        "n_workloads": len(works),
        "n_hw_configs": len(space),
        "n_candidates": clean.n_candidates,
        "hw_chunk": hw_chunk,
        "n_chunks": n_chunks,
        "clean_chunked_wall_s": round(clean_wall, 6),
        "salvage": {
            "injected_shard_failures": inj.counts["injected_shard_failures"],
            "wall_s": round(salvage_wall, 6),
            "overhead_vs_clean": round(salvage_wall / clean_wall, 3),
        },
        "resume": {
            "killed_at_chunk": kill_at,
            "killed_wall_s": round(killed_wall, 6),
            "resume_wall_s": round(resume_wall, 6),
            "chunks_restored": resumed.chunks_restored,
            "chunks_recomputed": resumed.chunks_computed,
            "resume_vs_full_recompute": round(resume_wall / clean_wall, 3),
        },
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="pruned grid + two workloads (CI)")
    ap.add_argument("--require-speedup", action="store_true",
                    help="assert >= 3x candidates/s at 8 devices vs 1 "
                         "(needs >= 8 physical cores)")
    ap.add_argument("--faults", action="store_true",
                    help="add salvage/resume fault-tolerance lanes")
    ap.add_argument("--devices", type=int,
                    help="(internal) run one cold measurement in-process")
    args = ap.parse_args()
    if args.devices or args.faults:
        from repro.compile_cache import enable_compile_cache

        enable_compile_cache(ROOT)
    if args.devices:
        run_child(args.devices, args.smoke)
        return
    if args.faults:
        lanes = run_faults(args.smoke)
        record = json.loads(OUT.read_text()) if OUT.exists() else {
            "bench": "shard", "smoke": args.smoke,
            "machine": machine_metadata(),
        }
        record["faults"] = lanes
        OUT.write_text(json.dumps(record, indent=2) + "\n")
        print(
            f"[bench_shard] faults: salvage "
            f"{lanes['salvage']['overhead_vs_clean']}x clean "
            f"({lanes['salvage']['injected_shard_failures']} failures), "
            f"resume {lanes['resume']['resume_vs_full_recompute']}x full "
            f"recompute ({lanes['resume']['chunks_restored']} chunks "
            f"restored) -> {OUT}"
        )
        return

    rows: dict[int, dict] = {}
    for d in DEVICE_COUNTS:
        cmd = [sys.executable, str(pathlib.Path(__file__).resolve()),
               "--devices", str(d)]
        if args.smoke:
            cmd.append("--smoke")
        # This benchmark measures the sharding contract on d virtual CPU
        # devices (forced host platform), so each child runs on the CPU
        # backend on purpose, also on a machine with a TPU.  The sharded
        # sweep on real chips is `python chip_smoke.py --chips 4`.
        env = {
            **os.environ,
            "XLA_FLAGS": f"--xla_force_host_platform_device_count={d}",
            "JAX_PLATFORMS": "cpu",
        }
        proc = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT,
                              env=env)
        if proc.returncode != 0:  # surface the child's traceback in CI logs
            sys.stderr.write(proc.stdout)
            sys.stderr.write(proc.stderr)
            raise SystemExit(f"bench_shard child --devices {d} failed")
        rows[d] = json.loads(proc.stdout.strip().splitlines()[-1])
        r = rows[d]
        print(
            f"devices {d}  sweep {r['sweep_s']*1e3:8.1f} ms cold / "
            f"{r['steady_sweep_s']*1e3:8.1f} ms steady  "
            f"({r['candidates_per_s']:>12,} cand/s, compile "
            f"{r['compile_s']*1e3:6.0f} ms)"
        )

    # The contract before any throughput claim: every device count finds
    # the SAME best points and the SAME Pareto fronts, bit for bit.
    base = rows[DEVICE_COUNTS[0]]
    for d in DEVICE_COUNTS[1:]:
        assert rows[d]["best_metrics"] == base["best_metrics"], (
            f"devices={d} best metrics diverge from single-device"
        )
        assert rows[d]["pareto"] == base["pareto"], (
            f"devices={d} Pareto front diverges from single-device"
        )
        assert rows[d]["n_candidates"] == base["n_candidates"]

    speedup = {
        d: round(rows[d]["candidates_per_s"] / base["candidates_per_s"], 2)
        for d in DEVICE_COUNTS
    }
    machine = base["machine"]
    record = {
        "bench": "shard",
        "smoke": args.smoke,
        "machine": machine,
        "metric_note": (
            "candidates_per_s = steady-state fleet sweep throughput (warm "
            "executable, the co-search inner loop); cold variants include "
            "the one-off XLA compile.  d=1 is the plain single-device "
            "program, d>1 shards the hardware axis over a 1-D `hardware` "
            "mesh of d host devices (XLA_FLAGS=--xla_force_host_platform_"
            "device_count).  All device counts are asserted bit-identical "
            "on best metrics AND full Pareto fronts before speedups are "
            "reported.  Host-platform devices share physical cores: "
            "speedup saturates at machine.cpu_count, so interpret "
            "speedup_vs_1_device against that."
        ),
        "n_workloads": base["n_workloads"],
        "n_hw_configs": base["n_hw_configs"],
        "n_candidates": base["n_candidates"],
        "device_counts": list(DEVICE_COUNTS),
        "runs": {str(d): rows[d] for d in DEVICE_COUNTS},
        "speedup_vs_1_device": {str(d): speedup[d] for d in DEVICE_COUNTS},
        "pareto_front_sizes": {
            name: front["size"] for name, front in base["pareto"].items()
        },
    }
    OUT.write_text(json.dumps(record, indent=2) + "\n")
    print(f"\n[bench_shard] {base['n_candidates']:,} candidates x "
          f"{len(DEVICE_COUNTS)} device counts -> {OUT}")
    print(f"[bench_shard] speedup vs 1 device: {speedup} "
          f"(physical cores: {machine['cpu_count']})")
    if args.require_speedup:
        assert speedup[8] >= 3.0, (
            f"8-device sweep only {speedup[8]}x vs 1 device "
            f"(cores: {machine['cpu_count']})"
        )


if __name__ == "__main__":
    main()
