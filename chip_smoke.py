#!/usr/bin/env python3
"""Bring-up smoke: the evaluator's main path, once, on one TPU chip.

Run from the repository root::

    python chip_smoke.py             # phases a-e on one chip
    python chip_smoke.py --chips 4   # the sharded co-search only (4 chips)

Phases, each printing its own ``[smoke]`` line:

a. device     — fails unless JAX's first device is a TPU;
b. paper lock — VGG-16 picks hsiao (4,4,4,4) and the fused-vs-layer-by-
   layer reductions equal the CPU-pinned values bit for bit;
c. zoo        — qwen3-0.6b and phi3-mini-3.8b superblocks, full width at
   seq_len 512, swept over the 2560-point ``config_space_grid()`` by one
   ``run_fleet``; every (hw, cut) cell must equal the scalar oracle
   ``metrics.evaluate_ref`` bit for bit, and so must the selection;
d. service    — a journaled ``PlanningService`` answers four requests,
   each bit-identical to the offline ``run_fleet`` plan;
e. kernels    — the four Pallas kernels, compiled at the blocks
   ``planner.plan_model`` picks for qwen3-0.6b, against ``kernels/ref.py``.

``--chips 4`` runs only the zoo sweep of (c) sharded over four chips
(``devices=4``) and unsharded, and asserts the two ``FleetResult``s are
bit-identical.  Timings printed here are a bring-up reading, not a
benchmark.  Everything runs in this one process: the chip belongs to one
process at a time.  The last line of standard output is
``{"ok": true, "device": {...}}``, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out" / "smoke"
SEQ_LEN = 512
ZOO = ("qwen3-0.6b", "phi3-mini-3.8b")
SEED = 0  # random kernel operands
# compare_fusion on VGG-16 at (4,4,4,4), as the CPU tests pin it
# (60.2 / 37.7 / 40.6 %): bandwidth, latency, energy.
PAPER_REDUCTIONS = (0.60222334440501, 0.3771900517192992, 0.4064104958352044)


class SmokeFailure(Exception):
    """A phase found something wrong."""


def check(cond: bool, msg: str) -> None:
    """Raise :class:`SmokeFailure` with ``msg`` unless ``cond``."""
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, msg: str) -> None:
    """One progress line on standard output."""
    print(f"[smoke] {phase}: {msg}", flush=True)


def phase_device():
    """(a) The first JAX device must be a TPU; returns the device list."""
    import jax

    devs = jax.devices()
    check(
        devs[0].platform == "tpu",
        f"no TPU found: JAX's first device is {devs[0].platform!r} "
        f"({devs[0].device_kind}); this smoke never falls back",
    )
    say("device", f"{devs[0].device_kind} x{len(devs)} (jax {jax.__version__})")
    return devs


def phase_paper_lock() -> None:
    """(b) VGG-16 paper lock through ``run_flow`` on the device."""
    from repro.core import metrics as M
    from repro.core.arch import (
        PAPER_CONSTRAINTS, PAPER_OPTIMAL_CONFIG, paper_config_space,
    )
    from repro.core.flow import compare_fusion, run_flow
    from repro.core.ir import vgg16_ir

    g = vgg16_ir(pool_mode="separate")
    res = run_flow(g, config_space=paper_config_space(),
                   constraints=PAPER_CONSTRAINTS, groupings="pool")
    check(res.best_hw == PAPER_OPTIMAL_CONFIG,
          f"paper optimum moved: {res.best_hw.describe()}")
    check(res.quarantine is None, "paper sweep quarantined cells")
    ref = M.evaluate_ref(g, res.best_cuts, res.best_hw)
    check(ref == res.best_metrics,
          f"paper winner {res.best_metrics} != oracle {ref}")
    cmp = compare_fusion(g, PAPER_OPTIMAL_CONFIG)
    got = (cmp.bw_reduction, cmp.latency_reduction, cmp.energy_reduction)
    check(got == PAPER_REDUCTIONS, f"reductions {got} != {PAPER_REDUCTIONS}")
    say("paper", f"{res.best_hw.describe()} reductions "
        f"{' / '.join(f'{100 * r:.1f}%' for r in got)} (bit-identical)")


def zoo_workload():
    """(graphs, cut batches, config grid, timings) for phase (c): the two
    smoke configs at full width, each with layer-by-layer, fully fused and
    the ``optimal_cuts`` optimum."""
    import numpy as np

    from repro.configs import REGISTRY
    from repro.core import fusion
    from repro.core.arch import config_space_grid
    from repro.core.frontend import transformer_graph

    t0 = time.perf_counter()
    graphs = [transformer_graph(REGISTRY[n], seq_len=SEQ_LEN) for n in ZOO]
    trace_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    batches = []
    for g in graphs:
        lbl = np.asarray(fusion.layer_by_layer_cuts(g), bool)
        best = np.asarray(fusion.optimal_cuts(g).cuts, bool)
        batches.append(np.stack([lbl, np.zeros_like(lbl), best]))
    search_s = time.perf_counter() - t0
    return graphs, batches, config_space_grid(), {
        "trace_s": trace_s, "search_s": search_s,
    }


class _PlaneTap:
    """Read-only use of ``run_fleet``'s ``hooks`` seam: keeps the raw
    (G, H, C, 5) plane the sweep computed, unchanged."""

    def __init__(self):
        self.planes = []

    def poison_plane(self, plane, h0):
        self.planes.append(plane)
        return plane


def _oracle_plane(g, cuts, grid):
    """(H, C, 4) ``evaluate_ref`` rows for every (hw, cut) cell."""
    import numpy as np

    from repro.core.metrics import evaluate_ref

    rows = np.empty((len(grid), len(cuts), 4))
    for h, hw in enumerate(grid):
        for c, cut in enumerate(cuts):
            m = evaluate_ref(g, cut, hw)
            rows[h, c] = (m.bandwidth_words, m.latency_cycles, m.energy_nj,
                          m.area_um2)
    return rows


def phase_zoo(graphs, batches, grid, timings) -> dict:
    """(c) The full-width zoo co-search on one chip vs the scalar oracle."""
    import numpy as np

    from repro.core import flow
    from repro.core import metrics as M
    from repro.core.arch import Constraints

    loose = Constraints(*[float("inf")] * 4)
    tap = _PlaneTap()
    t0 = time.perf_counter()
    fl = flow.run_fleet(graphs, config_space=grid, constraints=loose,
                        groupings=batches, pareto=True, hooks=tap)
    wall_s = time.perf_counter() - t0
    check(fl.quarantine is None,
          "finite guard fired:\n" + (fl.quarantine.describe()
                                     if fl.quarantine else ""))
    check(not fl.mesh_degraded and fl.device_count == 1,
          f"mesh_degraded={fl.mesh_degraded} device_count={fl.device_count}")
    check(len(tap.planes) == 1, f"{len(tap.planes)} raw planes, expected 1")
    H = len(grid)
    plane = M.compose_metrics(tap.planes[0][:, :H], np.stack(
        [c.as_row() for c in grid]))
    n_mismatch = 0
    for gi, (g, cuts, r) in enumerate(zip(graphs, batches, fl.results)):
        C = cuts.shape[0]
        got = plane[gi, :, :C]
        ref = _oracle_plane(g, cuts, grid)
        bad = np.argwhere(np.any(got != ref, axis=-1))
        n_mismatch += len(bad)
        if len(bad):
            h, c = bad[0]
            say("zoo", f"{g.name}: {len(bad)} of {H * C} cells differ from "
                f"the oracle; first (h={h}, c={c}): device {got[h, c]!r} "
                f"oracle {ref[h, c]!r}")
        win = M.evaluate_ref(g, r.best_cuts, r.best_hw)
        check(win == r.best_metrics,
              f"{g.name}: winner {r.best_metrics} != oracle {win}")
        pick = flow._best_flow_result(
            ref, cuts, g, grid, loose, n_pruned=0, compile_seconds=0.0,
            sweep_seconds=0.0, candidates_per_second=0.0, pareto=True)
        check(pick.best_hw == r.best_hw
              and np.array_equal(pick.best_cuts, r.best_cuts),
              f"{g.name}: oracle picks {pick.best_hw.describe()} "
              f"{pick.best_cuts.astype(int)}, sweep picked "
              f"{r.best_hw.describe()} {r.best_cuts.astype(int)}")
        check(np.array_equal(pick.pareto.metrics, r.pareto.metrics),
              f"{g.name}: Pareto front differs from the oracle's")
        say("zoo", f"{g.name} L={g.n_nodes} E={g.n_edges}: best "
            f"{r.best_hw.describe()} groups={list(r.group_sizes)}, "
            f"pareto {r.pareto.size}, all {H * C} cells "
            f"{'bit-identical to the oracle' if not len(bad) else 'checked'}")
    check(n_mismatch == 0, f"{n_mismatch} sweep cells differ from the oracle")
    reading = {
        **timings,
        "compile_s": fl.compile_seconds,
        "sweep_s": fl.sweep_seconds,
        "run_fleet_wall_s": wall_s,
        "n_candidates": fl.n_candidates,
        "candidates_per_s": fl.candidates_per_second,
    }
    say("zoo", "bring-up reading, not a benchmark: " + ", ".join(
        f"{k} {v!r}" for k, v in reading.items()))
    return reading


def phase_service(graphs, grid, journal) -> None:
    """(d) A PlanningService journaling to ``journal`` vs the offline
    ``run_fleet`` plan."""
    import numpy as np

    from repro.core import flow
    from repro.core.arch import Constraints
    from repro.core.ir import resnet18_ir
    from repro.core.service import PlanningService, PlanRequest

    loose = Constraints(*[float("inf")] * 4)
    shutil.rmtree(journal, ignore_errors=True)
    resnet = resnet18_ir()
    asks = [(g, float("inf")) for g in graphs]
    asks += [(resnet, float("inf")), (resnet, 4e5)]
    svc = PlanningService(config_space=grid, constraints=loose,
                          journal_dir=journal)
    try:
        t0 = time.perf_counter()
        rids = [svc.submit(PlanRequest(graph=g, sram_budget_words=b,
                                       deadline_seconds=600.0))
                for g, b in asks]
        t1 = time.perf_counter()
        svc.drain()
        t2 = time.perf_counter()
        resps = [svc.collect(rid) for rid in rids]
        stats = svc.stats()
    finally:
        svc.close()
    say("service", f"bring-up reading, not a benchmark: submit_s "
        f"{t1 - t0!r} (admission + journal), drain_s {t2 - t1!r}")
    for (g, budget), resp in zip(asks, resps):
        check(resp is not None and resp.ok,
              f"{getattr(g, 'name', g)}: {resp and resp.error!r}")
        check(not resp.degraded, f"{g.name}: degraded to {resp.rung}")
        off = flow.run_fleet([g], config_space=grid, constraints=loose,
                             groupings="search",
                             sram_budget_words=budget).results[0]
        plan = resp.plan
        check(plan.best_hw == off.best_hw
              and np.array_equal(plan.best_cuts, off.best_cuts)
              and plan.best_metrics == off.best_metrics
              and plan.quarantine is None,
              f"{g.name}: service plan differs from the offline run_fleet")
        say("service", f"{g.name} budget={budget:g}: {resp.engine} "
            f"{plan.best_hw.describe()} in {resp.latency_seconds:.3f} s, "
            "bit-identical to offline")
    check(stats["counters"].get("transient_retries", 0) == 0,
          f"the service retried transient failures: {stats['counters']}")


def phase_kernels(interpret: bool = False) -> None:
    """(e) The four Pallas kernels at qwen3-0.6b's planner blocks."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import REGISTRY
    from repro.core.planner import plan_model
    from repro.kernels import (
        fused_attention, fused_conv, fused_mlp, mamba_scan, ref,
    )

    cfg = REGISTRY["qwen3-0.6b"]
    plan = plan_model(cfg, SEQ_LEN)
    ks = jax.random.split(jax.random.key(SEED), 12)
    bf16, f32 = jnp.bfloat16, jnp.float32
    H, KV, hd, d, ff = (cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
                        cfg.d_model, cfg.d_ff)

    def normal(k, shape, dtype, scale=1.0):
        return (jax.random.normal(k, shape, f32) * scale).astype(dtype)

    q = normal(ks[0], (1, SEQ_LEN, H, hd), bf16)
    k_ = normal(ks[1], (1, SEQ_LEN, KV, hd), bf16)
    v = normal(ks[2], (1, SEQ_LEN, KV, hd), bf16)
    x = normal(ks[3], (SEQ_LEN, d), bf16)
    w1 = normal(ks[4], (d, ff), bf16, d ** -0.5)
    w2 = normal(ks[5], (ff, d), bf16, ff ** -0.5)
    w3 = normal(ks[6], (d, ff), bf16, d ** -0.5)
    # VGG-16 conv3_3 (56x56, 256 -> 256, pooled): the paper's own workload
    cx = normal(ks[7], (1, 56, 56, 256), bf16)
    cw = normal(ks[8], (3, 3, 256, 256), bf16, (9 * 256) ** -0.5)
    cb = normal(ks[9], (256,), bf16, 0.1)
    di, ds = cfg.d_inner, cfg.ssm_state
    dA = jax.random.uniform(ks[10], (1, SEQ_LEN, di, ds), f32, 0.3, 0.98)
    dBx = normal(ks[11], (1, SEQ_LEN, di, ds), f32, 0.1)
    C = normal(ks[0], (1, SEQ_LEN, ds), f32)

    cases = [
        ("attention", f"{plan.attn_block_q}x{plan.attn_block_k}", 2e-2,
         lambda: fused_attention.flash_attention(
             q, k_, v, block_q=plan.attn_block_q,
             block_k=plan.attn_block_k, interpret=interpret),
         lambda: ref.flash_attention_ref(q, k_, v)),
        ("mlp", f"{plan.mlp_block_m}x{plan.mlp_block_f}", 2e-2,
         lambda: fused_mlp.fused_mlp(
             x, w1, w2, w3, block_m=plan.mlp_block_m,
             block_f=plan.mlp_block_f, interpret=interpret),
         lambda: ref.fused_mlp_ref(x, w1, w2, w3)),
        ("conv3x3", f"block_c={plan.conv_block_c}", 2e-2,
         lambda: fused_conv.fused_conv3x3(
             cx, cw, cb, pool=True, block_c=plan.conv_block_c,
             interpret=interpret),
         lambda: ref.fused_conv3x3_ref(cx, cw, cb, pool=True)),
        ("ssm_scan", f"chunk={plan.mamba_chunk} block_d={plan.mamba_block_d}",
         1e-4,
         lambda: mamba_scan.selective_scan(
             dA, dBx, C, chunk=plan.mamba_chunk,
             block_d=plan.mamba_block_d, interpret=interpret),
         lambda: ref.selective_scan_ref(dA, dBx, C)),
    ]
    for name, blocks, tol, kernel, oracle in cases:
        t0 = time.perf_counter()
        out = np.asarray(jax.block_until_ready(kernel()), np.float32)
        dt = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            want = np.asarray(oracle(), np.float32)
        check(out.shape == want.shape and np.all(np.isfinite(out)),
              f"{name}: shape {out.shape} vs {want.shape} or non-finite")
        err = float(np.max(np.abs(out - want)))
        scale = float(np.max(np.abs(want)))
        check(np.allclose(out, want, atol=tol, rtol=tol),
              f"{name}: max |kernel - ref| {err!r} over |ref| <= {scale!r} "
              f"exceeds tolerance {tol}")
        say("kernels", f"{name} [{blocks}] {tuple(out.shape)} max err "
            f"{err:.3g} (|ref| <= {scale:.3g}, tol {tol}), first call "
            f"{dt:.2f} s incl. compile")


def _flow_equal(a, b) -> bool:
    """Bit-identity of two FlowResults: winner and Pareto front."""
    import numpy as np

    return (
        a.best_hw == b.best_hw
        and np.array_equal(a.best_cuts, b.best_cuts)
        and a.best_metrics == b.best_metrics
        and a.n_feasible == b.n_feasible
        and np.array_equal(a.pareto.metrics, b.pareto.metrics)
        and np.array_equal(a.pareto.hw_indices, b.pareto.hw_indices)
        and np.array_equal(a.pareto.cut_indices, b.pareto.cut_indices)
    )


def phase_sharded(graphs, batches, grid, n_devices: int) -> None:
    """(f) The zoo co-search sharded over ``n_devices`` chips vs one."""
    from repro.core import flow
    from repro.core.arch import Constraints

    loose = Constraints(*[float("inf")] * 4)
    runs = {}
    for devices in (n_devices, None):
        runs[devices] = flow.run_fleet(
            graphs, config_space=grid, constraints=loose, groupings=batches,
            pareto=True, devices=devices)
    mesh, single = runs[n_devices], runs[None]
    check(mesh.device_count == n_devices and not mesh.mesh_degraded,
          f"device_count={mesh.device_count} mesh_degraded="
          f"{mesh.mesh_degraded}")
    check(mesh.quarantine is None and single.quarantine is None,
          "finite guard fired")
    for a, b in zip(mesh.results, single.results):
        check(_flow_equal(a, b),
              f"sharded result differs from one device: {a.describe()} vs "
              f"{b.describe()}")
    say("sharded", f"{n_devices}-chip sweep bit-identical to 1 device "
        f"(winners and Pareto fronts); bring-up reading, not a benchmark: "
        f"compile_s {mesh.compile_seconds!r} sweep_s {mesh.sweep_seconds!r} "
        f"vs 1 device compile_s {single.compile_seconds!r} sweep_s "
        f"{single.sweep_seconds!r}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded co-search, on four chips")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        print(f"[smoke] FAIL: no src/repro beside {__file__}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        devs = phase_device()
        check(len(devs) >= args.chips,
              f"--chips {args.chips} but {len(devs)} devices visible")
        from repro.compile_cache import enable_compile_cache

        say("cache", str(enable_compile_cache(ROOT)))
        OUT.mkdir(parents=True, exist_ok=True)
        graphs, batches, grid, timings = zoo_workload()
        if args.chips > 1:
            phase_sharded(graphs, batches, grid, args.chips)
        else:
            phase_paper_lock()
            reading = phase_zoo(graphs, batches, grid, timings)
            (OUT / "zoo_reading.json").write_text(json.dumps(reading) + "\n")
            phase_service(graphs, grid, OUT / "journal")
            phase_kernels()
    except SmokeFailure as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
