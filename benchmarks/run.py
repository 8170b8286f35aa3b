"""Benchmark harness — one function per paper table/figure + system tables.

Prints ``name,us_per_call,derived`` CSV rows (one per table entry) and a
human-readable block per table.  Usage: ``python -m benchmarks.run``.
"""
from __future__ import annotations

import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import REGISTRY, SHAPES
from repro.core import fusion, metrics as M
from repro.core.arch import (
    Constraints, DLAConfig, PAPER_CONSTRAINTS, PAPER_OPTIMAL_CONFIG,
    default_config_space, paper_config_space,
)
from repro.core.flow import compare_fusion, run_flow
from repro.core.ir import residual_block_ir, resnet18_ir, vgg16_ir
from repro.core.planner import plan_model

ROWS: list[str] = []


def emit(name: str, us_per_call: float, derived: str):
    row = f"{name},{us_per_call:.2f},{derived}"
    ROWS.append(row)
    print(row)


def timed(fn, *args, reps=3, **kw):
    fn(*args, **kw)  # warmup / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    return out, (time.perf_counter() - t0) / reps * 1e6


# ---------------------------------------------------------------------------
def table1_vgg16_flow():
    """Paper Sec. III: optimal config under the four constraints + the
    fusion-vs-layer-by-layer reductions (paper: (4,4,4,4); 55.6/36.7/49.2%).
    """
    print("\n== table1: VGG-16 optimisation flow (paper Sec. III) ==")
    ir = vgg16_ir(pool_mode="separate")
    res, us = timed(
        run_flow, ir, config_space=paper_config_space(),
        constraints=PAPER_CONSTRAINTS, groupings="pool",
    )
    emit("table1.optimal_config", us,
         f"{res.best_hw.style}(F={res.best_hw.f1}x{res.best_hw.f2}x"
         f"{res.best_hw.f3}x{res.best_hw.f4});paper=(4x4x4x4)")
    cmp = compare_fusion(ir, PAPER_OPTIMAL_CONFIG)
    emit("table1.bw_reduction_pct", us, f"{cmp.bw_reduction*100:.1f};paper=55.6")
    emit("table1.latency_reduction_pct", us,
         f"{cmp.latency_reduction*100:.1f};paper=36.7")
    emit("table1.energy_reduction_pct", us,
         f"{cmp.energy_reduction*100:.1f};paper=49.2")
    emit("table1.lbl_meets_constraints", us, str(cmp.lbl.meets(PAPER_CONSTRAINTS)))
    emit("table1.fused_meets_constraints", us,
         str(cmp.fused.meets(PAPER_CONSTRAINTS)))
    print(cmp.describe())


def table2_energy_per_group():
    """Paper Fig. 2: per-fusion-group energy, fused vs layer-by-layer."""
    print("\n== table2: energy per fusion group (paper Fig. 2) ==")
    from repro.core.ir import NetworkIR

    ir = vgg16_ir(pool_mode="separate")
    hw = PAPER_OPTIMAL_CONFIG
    cuts = ir.pool_boundary_cuts()
    groups = M.groups_from_cuts(cuts)
    t0 = time.perf_counter()
    for gi, g in enumerate(groups):
        sub_ir = NetworkIR(f"g{gi}", tuple(ir.layers[g[0] : g[-1] + 1]))
        lbl = M.energy_ref(sub_ir, fusion.layer_by_layer_cuts(len(sub_ir)), hw)
        fus = M.energy_ref(sub_ir, np.zeros(len(sub_ir) - 1, bool), hw)
        emit(f"table2.group{gi+1}_energy_mJ", 0.0,
             f"lbl={lbl/1e6:.2f};fused={fus/1e6:.2f};"
             f"red={100*(1-fus/lbl):.1f}%")
    us = (time.perf_counter() - t0) * 1e6 / len(groups)
    emit("table2.us_per_group", us, f"{len(groups)}groups")


def table3_arch_compare():
    """Hsiao [2] vs VWA [3] across uniform configs (evaluator application)."""
    print("\n== table3: accelerator architecture comparison ==")
    ir = vgg16_ir(pool_mode="separate")
    cuts = ir.pool_boundary_cuts()
    for style, f3 in (("hsiao", None), ("vwa", 3)):
        for f in (4, 8):
            hw = DLAConfig(style, f, f, f3 or f, f)
            m, us = timed(M.evaluate_ref, ir, cuts, hw, reps=5)
            emit(f"table3.{style}_{f}", us,
                 f"lat={m.latency_cycles/1e6:.2f}Mcyc;E={m.energy_nj/1e6:.1f}mJ;"
                 f"A={m.area_um2/1e6:.1f}mm2;BW={m.bandwidth_words/1e6:.1f}MB")


def table4_sweep_throughput():
    """Vectorised flow throughput: the exhaustive sweep as one XLA program."""
    print("\n== table4: evaluator sweep throughput ==")
    ir = vgg16_ir(pool_mode="separate")
    res, us = timed(
        run_flow, ir, constraints=PAPER_CONSTRAINTS, groupings="exhaustive",
        reps=1,
    )
    emit("table4.exhaustive_sweep", us,
         f"{res.n_candidates}cand;{res.candidates_per_second:,.0f}cand_per_s")
    res2, us2 = timed(
        run_flow, ir, constraints=PAPER_CONSTRAINTS, groupings="pool", reps=3,
    )
    emit("table4.predefined_sweep", us2,
         f"{res2.n_candidates}cand;{res2.candidates_per_second:,.0f}cand_per_s")


def table5_kernel_fusion():
    """Per-kernel Eq. (1) HBM-traffic savings (fused vs layer-by-layer) and
    interpret-mode correctness residual vs the jnp oracle."""
    print("\n== table5: kernel fusion groups ==")
    from repro.kernels import ops, ref

    key = jax.random.key(0)
    # attention: (Sq x Skv) score frame stays in VMEM
    B, S, H, KV, hd = 1, 512, 4, 2, 64
    q = jax.random.normal(key, (B, S, H, hd))
    k = jax.random.normal(jax.random.key(1), (B, S, KV, hd))
    v = jax.random.normal(jax.random.key(2), (B, S, KV, hd))
    out, us = timed(ops.attention, q, k, v, reps=1)
    err = float(jnp.abs(out - ref.flash_attention_ref(q, k, v)).max())
    unfused = (B * H * S * S * 4) * 2 + B * S * (H + 2 * KV) * hd * 4
    fused = B * S * (H + 2 * KV) * hd * 4 * 2
    emit("table5.flash_attention", us,
         f"hbm_lbl={unfused/2**20:.0f}MiB;hbm_fused={fused/2**20:.1f}MiB;"
         f"saving={100*(1-fused/unfused):.1f}%;maxerr={err:.1e}")
    # mlp: (T x ff) hidden frame stays in VMEM
    T, d, ff = 256, 128, 512
    x = jax.random.normal(key, (T, d))
    w1 = jax.random.normal(jax.random.key(3), (d, ff)) * 0.1
    w3 = jax.random.normal(jax.random.key(4), (d, ff)) * 0.1
    w2 = jax.random.normal(jax.random.key(5), (ff, d)) * 0.1
    out, us = timed(ops.mlp, x, w1, w2, w3, reps=1)
    err = float(jnp.abs(out - ref.fused_mlp_ref(x, w1, w2, w3)).max())
    unfused = (2 * T * ff + T * (2 * d + ff)) * 4
    fusedb = (2 * T * d) * 4
    emit("table5.fused_mlp", us,
         f"hbm_lbl={unfused/2**20:.1f}MiB;hbm_fused={fusedb/2**20:.2f}MiB;"
         f"saving={100*(1-fusedb/unfused):.1f}%;maxerr={err:.1e}")
    # conv+pool: pre-pool frame stays in VMEM (the paper's own fusion)
    xi = jax.random.normal(key, (1, 32, 32, 16))
    wc = jax.random.normal(jax.random.key(6), (3, 3, 16, 32)) * 0.1
    bc = jnp.zeros((32,))
    out, us = timed(ops.conv3x3, xi, wc, bc, pool=True, reps=1)
    err = float(jnp.abs(out - ref.fused_conv3x3_ref(xi, wc, bc, pool=True)).max())
    unfused = (32 * 32 * 32 * 2 + 16 * 16 * 32) * 4
    fusedb = 16 * 16 * 32 * 4
    emit("table5.fused_conv_pool", us,
         f"hbm_lbl={unfused/2**10:.0f}KiB;hbm_fused={fusedb/2**10:.0f}KiB;"
         f"saving={100*(1-fusedb/unfused):.1f}%;maxerr={err:.1e}")
    # mamba scan: state sequence never materialised
    Bs, Ss, di, ds = 1, 256, 64, 16
    dA = jax.random.uniform(key, (Bs, Ss, di, ds), minval=0.5, maxval=0.98)
    dBx = jax.random.normal(jax.random.key(7), (Bs, Ss, di, ds)) * 0.1
    C = jax.random.normal(jax.random.key(8), (Bs, Ss, ds))
    out, us = timed(ops.ssm_scan, dA, dBx, C, chunk=64, block_d=32, reps=1)
    err = float(jnp.abs(out - ref.selective_scan_ref(dA, dBx, C)).max())
    unfused = Bs * Ss * di * ds * 4 * 3  # h sequence write+read + dA/dBx
    fusedb = Bs * Ss * di * ds * 4 * 2  # dA/dBx streamed once
    emit("table5.mamba_scan", us,
         f"hbm_lbl={unfused/2**20:.1f}MiB;hbm_fused={fusedb/2**20:.1f}MiB;"
         f"saving={100*(1-fusedb/unfused):.1f}%;maxerr={err:.1e}")


def table6_planner():
    """The evaluator driving kernel selection for every assigned arch."""
    print("\n== table6: fusion planner decisions (10 archs) ==")
    for name, cfg in sorted(REGISTRY.items()):
        plan, us = timed(plan_model, cfg, 4096, reps=2)
        emit(f"table6.{name}", us,
             f"attn={plan.attn_block_q}x{plan.attn_block_k};"
             f"mlp={plan.mlp_block_m}x{plan.mlp_block_f};"
             f"blockBWsave={plan.bw_saving*100:.1f}%")


def table7_resnet_fusion():
    """Graph-IR fusion on residual networks — groupings the chain IR could
    never express (the skip tensor stays on-chip across a fused block)."""
    print("\n== table7: resnet fusion (graph IR; beyond-paper) ==")
    hw = PAPER_OPTIMAL_CONFIG

    # One basic block: brute-force edge-cut optimum vs the best grouping a
    # chain IR could express (= the skip edge forced to round-trip DRAM).
    rb = residual_block_ir()
    lbl_bw = M.bandwidth_ref(rb, fusion.layer_by_layer_cuts(rb))
    dag, us = timed(fusion.brute_force_min_bw, rb)
    dag_bw = M.bandwidth_ref(rb, dag.cuts)
    skip_idx = next(
        k for k, e in enumerate(rb.edges) if (e.src, e.dst) == (0, 3)
    )
    # Chain-best = the optimum with the skip edge forced to round-trip DRAM,
    # scored by the batched evaluator in one call.
    valid = fusion.enumerate_valid_edge_cuts(rb)
    chain_bw = float(M.bandwidth_batch_graph(rb, valid[valid[:, skip_idx]]).min())
    emit("table7.resblock_bw_reduction_pct", us,
         f"{100*(1-dag_bw/lbl_bw):.1f};chain_best={100*(1-chain_bw/lbl_bw):.1f};"
         f"dag_only_delta={100*(chain_bw-dag_bw)/lbl_bw:.1f}")

    # Full ResNet-18: search-grouped vs layer-by-layer under the paper's
    # hw.  The 38-edge DAG is beyond the 2^22 enumeration wall; the
    # frontier DP certifies the optimum exactly (engine provenance below),
    # where earlier revisions could only report a beam heuristic.
    g = resnet18_ir()
    search, us = timed(fusion.optimal_cuts, g, reps=1)
    cmp = compare_fusion(g, hw, fused_cuts=search.cuts)
    emit("table7.resnet18_bw_reduction_pct", us, f"{cmp.bw_reduction*100:.1f}")
    emit("table7.resnet18_latency_reduction_pct", us,
         f"{cmp.latency_reduction*100:.1f}")
    emit("table7.resnet18_energy_reduction_pct", us,
         f"{cmp.energy_reduction*100:.1f}")
    emit("table7.resnet18_groups", us,
         f"{search.n_groups};engine={search.engine};exact={search.exact}")
    print(cmp.describe())


def table9_frontend_workloads():
    """Traced-model scenarios (frontend; beyond-paper): a depthwise
    MobileNet stack and a gated transformer MLP block — workloads no hand
    builder existed for — through the full grouping search + flow."""
    print("\n== table9: traced frontend workloads (beyond-paper) ==")
    from repro.core.frontend import mlp_block_graph, mobilenet_graph

    g, us = timed(mobilenet_graph, reps=2)
    emit("table9.mobilenet_trace", us,
         f"{g.n_nodes}nodes;{g.n_edges}edges;"
         f"dw={sum(1 for n in g.nodes if n.groups > 1)}")
    best, us = timed(fusion.optimal_cuts, g, reps=1)
    lbl = M.bandwidth_ref(g, fusion.layer_by_layer_cuts(g))
    bw = M.bandwidth_ref(g, best.cuts)
    emit("table9.mobilenet_bw_reduction_pct", us,
         f"{100*(1-bw/lbl):.1f};groups={best.n_groups};engine={best.engine}")
    res, us = timed(run_flow, g, groupings="search", reps=1)
    emit("table9.mobilenet_flow", us,
         f"{res.n_candidates}cand;E={res.best_metrics.energy_nj/1e6:.2f}mJ")

    m, us = timed(mlp_block_graph, d_model=1024, d_ff=4096, seq_len=512,
                  reps=2)
    emit("table9.mlp_trace", us, f"{m.n_nodes}nodes;{m.n_edges}edges")
    best, us = timed(fusion.optimal_cuts, m, reps=1)
    lbl = M.bandwidth_ref(m, fusion.layer_by_layer_cuts(m))
    bw = M.bandwidth_ref(m, best.cuts)
    emit("table9.mlp_bw_reduction_pct", us,
         f"{100*(1-bw/lbl):.1f};groups={best.n_groups};engine={best.engine}")
    # The 25 M-MAC gated block busts the paper's CNN-scale envelope; lift
    # the latency/energy ceilings and let the flow pick the best config.
    loose = Constraints(max_latency_cycles=1e9, max_energy_nj=1e9)
    res, us = timed(run_flow, m, groupings="search", constraints=loose, reps=1)
    emit("table9.mlp_flow", us,
         f"{res.n_candidates}cand;E={res.best_metrics.energy_nj/1e6:.2f}mJ")


def table7_roofline_summary():
    """Condensed §Roofline: per (arch x shape) single-pod bound + mfu cap."""
    print("\n== table7: dry-run roofline summary (single pod) ==")
    import json
    import pathlib

    droot = pathlib.Path(__file__).resolve().parents[1] / "experiments/dryrun"
    if not droot.exists():
        emit("table7.missing", 0.0, "run launch/dryrun --all first")
        return
    for f in sorted(droot.glob("*__single.json")):
        rec = json.loads(f.read_text())
        if rec.get("tag"):
            continue  # §Perf iteration records are reported separately
        rl = rec["roofline"]
        step_s = max(rl["compute_s"], rl["memory_s"], rl["collective_s"])
        emit(
            f"table7.{rec['arch']}.{rec['shape']}", 0.0,
            f"bound={rl['bound']};mfu_cap={rl['mfu_bound']*100:.1f}%;"
            f"step_ms={step_s*1e3:.1f}",
        )


def table8_perf_iterations():
    """§Perf hillclimb: every tagged dry-run record vs its baseline."""
    print("\n== table8: perf iterations (tagged dry-run records) ==")
    import json
    import pathlib

    droot = pathlib.Path(__file__).resolve().parents[1] / "experiments/dryrun"
    if not droot.exists():
        emit("table8.missing", 0.0, "run launch/dryrun --all first")
        return
    recs = [json.loads(f.read_text()) for f in sorted(droot.glob("*.json"))]
    base = {
        (r["arch"], r["shape"], r["mesh"]): r for r in recs if not r.get("tag")
    }
    for r in recs:
        if not r.get("tag"):
            continue
        b = base.get((r["arch"], r["shape"], r["mesh"]))
        if b is None:
            continue
        step = lambda x: max(
            x["roofline"]["compute_s"], x["roofline"]["memory_s"],
            x["roofline"]["collective_s"],
        )
        emit(
            f"table8.{r['arch']}.{r['shape']}.{r['mesh']}.{r['tag']}", 0.0,
            f"step={step(r)*1e3:.1f}ms;baseline={step(b)*1e3:.1f}ms;"
            f"speedup={step(b)/max(step(r),1e-12):.2f}x;"
            f"bound={r['roofline']['bound']};"
            f"mfu={r['roofline']['mfu_bound']*100:.2f}%",
        )


TABLES = [
    table1_vgg16_flow,
    table2_energy_per_group,
    table3_arch_compare,
    table4_sweep_throughput,
    table5_kernel_fusion,
    table6_planner,
    table7_resnet_fusion,
    table7_roofline_summary,
    table8_perf_iterations,
    table9_frontend_workloads,
]


def main() -> None:
    enable_compile_cache(pathlib.Path(__file__).resolve().parents[1])
    print("name,us_per_call,derived")
    for t in TABLES:
        t()
    print(f"\n[benchmarks] {len(ROWS)} rows emitted")


if __name__ == "__main__":
    main()
