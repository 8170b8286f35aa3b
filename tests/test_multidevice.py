"""Multi-device semantics via subprocesses (tests proper see 1 CPU device;
each case spawns a fresh interpreter with xla_force_host_platform_device_count).

Covers: pjit-sharded train step == single-device step; elastic re-mesh
resume; pipeline parallelism vs sequential; compressed cross-pod psum;
and the sharded hardware co-search (run_fleet(devices=...) bit-identical
to the single-device sweep at 2/4/8 host devices, padded-H masking
included).
"""
import subprocess
import sys
import textwrap

import pytest

REPO_SRC = "src"


def run_py(body: str, n_devices: int = 8, timeout: int = 420) -> str:
    prog = textwrap.dedent(f"""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_devices}"
    import jax, jax.numpy as jnp, numpy as np
    {textwrap.indent(textwrap.dedent(body), '    ').strip()}
    """)
    res = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=timeout, env={"PYTHONPATH": REPO_SRC, "PATH": "/usr/bin:/bin",
                              "HOME": "/root",
                              # without this, libtpu probes GCP instance
                              # metadata (30 retries per var) before falling
                              # back to CPU -- minutes of nanosleep
                              "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo",
    )
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


def test_sharded_train_step_matches_single_device():
    out = run_py("""
    from repro.configs.base import ModelConfig, RunConfig
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.optim import AdamWConfig, init_opt_state
    from repro.parallel import sharding as SH
    from repro.runtime.steps import make_train_step

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                      dtype="float32")
    rc = RunConfig(xent_chunk=16, attn_chunk_kv=16, learning_rate=1e-3,
                   warmup_steps=1)
    key = jax.random.key(0)
    params = M.init_params(key, cfg)
    opt = init_opt_state(params, AdamWConfig())
    batch = {"tokens": jax.random.randint(key, (8, 32), 0, 256),
             "labels": jax.random.randint(jax.random.key(1), (8, 32), 0, 256)}
    step = make_train_step(cfg, rc)

    # single device
    p1, o1, m1 = jax.jit(step)(params, opt, batch)

    # sharded over (2 data, 4 model)
    mesh = make_mesh((2, 4), ("data", "model"))
    ap = jax.eval_shape(lambda: params)
    pshard = SH.param_shardings(mesh, ap)
    bshard = SH.batch_shardings(mesh, jax.eval_shape(lambda: batch))
    aopt = jax.eval_shape(lambda: opt)
    oshard = SH.opt_state_shardings(mesh, aopt, pshard)
    params_s = jax.device_put(params, pshard)
    opt_s = jax.device_put(opt, oshard)
    batch_s = jax.device_put(batch, bshard)
    with jax.set_mesh(mesh):
        p2, o2, m2 = jax.jit(step, in_shardings=(pshard, oshard, bshard))(
            params_s, opt_s, batch_s)
    print("loss1", float(m1["loss"]), "loss2", float(m2["loss"]))
    d = max(float(jnp.abs(a - b).max())
            for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    print("maxdiff", d)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-4
    assert d < 2e-4
    """)
    assert "maxdiff" in out


def test_elastic_remesh_resume(tmp_path):
    out = run_py(f"""
    from repro.configs.base import ModelConfig, RunConfig
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.optim import AdamWConfig, init_opt_state
    from repro import checkpoint as CKPT
    from repro.runtime.elastic import resume_on_mesh

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                      dtype="float32")
    params = M.init_params(jax.random.key(0), cfg)
    opt = init_opt_state(params, AdamWConfig())
    CKPT.save("{tmp_path}", 3, {{"params": params, "opt": opt}})

    # resume on a "2-pod" mesh, then on a "1-pod" mesh
    for shape, axes in [((2, 2, 2), ("pod", "data", "model")),
                        ((2, 4), ("data", "model"))]:
        mesh = make_mesh(shape, axes)
        p2, o2 = resume_on_mesh("{tmp_path}", 3, cfg, mesh)
        d = max(float(jnp.abs(a - jnp.asarray(b)).max())
                for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(params)))
        print(axes, "diff", d)
        assert d == 0.0
    """)
    assert out.count("diff 0.0") == 2


def test_pipeline_parallel_matches_sequential():
    out = run_py("""
    from functools import partial
    from repro.launch.mesh import make_mesh
    from repro.parallel import sharding as SH
    from repro.parallel.pipeline import pipeline_apply, bubble_fraction

    stages, n_micro, mb, d = 4, 6, 8, 16
    mesh = make_mesh((stages,), ("stage",))
    key = jax.random.key(0)
    ws = jax.random.normal(key, (stages, d, d)) * 0.3

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    x = jax.random.normal(jax.random.key(1), (n_micro, mb, d))
    with jax.set_mesh(mesh):
        out = pipeline_apply(stage_fn, ws, x, mesh=mesh)
    # sequential reference
    ref = x
    for s in range(stages):
        ref = jnp.tanh(ref @ ws[s])
    d_ = float(jnp.abs(out - ref).max())
    print("pp maxdiff", d_, "bubble", bubble_fraction(n_micro, stages))
    assert d_ < 1e-5
    """, n_devices=4)
    assert "pp maxdiff" in out


def test_compressed_train_step_learns_with_s8_wire():
    out = run_py("""
    from repro.configs.base import ModelConfig, RunConfig
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.optim import AdamWConfig, init_opt_state
    from repro.parallel import sharding as SH
    from repro.runtime.spmd_train import make_compressed_train_step

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                      dtype="float32")
    rc = RunConfig(xent_chunk=16, attn_chunk_kv=16, learning_rate=2e-3,
                   warmup_steps=2)
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    params = M.init_params(jax.random.key(0), cfg)
    opt = init_opt_state(params, AdamWConfig())
    step, init_ef = make_compressed_train_step(cfg, rc, mesh)
    ef = init_ef(params)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (8, 32), 0, 128),
             "labels": jax.random.randint(jax.random.key(2), (8, 32), 0, 128)}
    with jax.set_mesh(mesh):
        jstep = jax.jit(step)
        losses = []
        for _ in range(8):
            params, opt, ef, m = jstep(params, opt, ef, batch)
            losses.append(float(m["loss"]))
        txt = jax.jit(step).lower(params, opt, ef, batch).compile().as_text()
    s8 = sum(1 for l in txt.splitlines() if "all-reduce" in l and "s8[" in l)
    print("losses", [round(l, 3) for l in losses], "s8_allreduces", s8)
    assert losses[-1] < losses[0] - 0.2   # converges through int8 sync
    assert s8 >= 5                        # grads really cross pods as int8
    """)
    assert "s8_allreduces" in out


def test_sharded_fleet_bit_identical_vs_single_device():
    # H=37 is not a multiple of 2, 4 or 8, so every mesh exercises the
    # padded-H path (inert copies of config 0, sliced before composition).
    out = run_py("""
    from repro.core import flow
    from repro.core.arch import Constraints, config_space_grid
    from repro.core.ir import residual_block_ir, resnet18_ir

    loose = Constraints(*[float("inf")] * 4)
    space = config_space_grid(
        f1s=(2, 4), f2s=(2, 4), f3s=(2, 4), f4s=(2, 4),
        bus_widths=(2, 4), sram_splits=("unified",),
    )[:37]
    irs = [resnet18_ir(), residual_block_ir()]
    base = flow.run_fleet(irs, config_space=space, constraints=loose,
                          groupings="pool", pareto=True)
    for d in (2, 4, 8):
        fl = flow.run_fleet(irs, config_space=space, constraints=loose,
                            groupings="pool", devices=d, pareto=True)
        assert fl.device_count == d
        assert fl.n_candidates == base.n_candidates  # padded H not counted
        for a, b in zip(base.results, fl.results):
            assert a.best_metrics == b.best_metrics, (d, a, b)
            assert a.best_hw == b.best_hw
            assert np.array_equal(a.best_cuts, b.best_cuts)
            assert a.group_sizes == b.group_sizes
            assert a.n_feasible == b.n_feasible
            # the whole Pareto front, not just the argmin, is bit-identical
            assert np.array_equal(a.pareto.metrics, b.pareto.metrics)
            assert np.array_equal(a.pareto.hw_indices, b.pareto.hw_indices)
            assert np.array_equal(a.pareto.cut_indices, b.pareto.cut_indices)
            assert np.array_equal(a.pareto.cuts, b.pareto.cuts)
        print("devices", d, "ok")
    layouts = {(e["mesh_axis"], e["device_count"])
               for e in flow.sweep_cache_stats()["entries"]}
    assert ("single", 1) in layouts
    assert {("hardware", d) for d in (2, 4, 8)} <= layouts
    print("sharded fleet ok", len(space))
    """)
    assert "sharded fleet ok 37" in out


def test_sharded_fleet_search_groupings_and_budget_8dev():
    # The sharded path composes with the rest of the flow: frontier-DP
    # groupings + SRAM budget prefilter, best metrics == plain run_flow.
    out = run_py("""
    from repro.core import flow
    from repro.core.arch import Constraints, default_config_space
    from repro.core.ir import residual_block_ir, resnet18_ir

    loose = Constraints(*[float("inf")] * 4)
    budget = 2.0e6
    irs = [resnet18_ir(), residual_block_ir()]
    fl = flow.run_fleet(irs, config_space=default_config_space(),
                        constraints=loose, groupings="search",
                        sram_budget_words=budget, devices=8)
    for g, r in zip(irs, fl.results):
        solo = flow.run_flow(g, config_space=default_config_space(),
                             constraints=loose, groupings="search",
                             sram_budget_words=budget)
        assert r.best_metrics == solo.best_metrics
        assert np.array_equal(r.best_cuts, solo.best_cuts)
        assert r.search_engine == solo.search_engine
    print("sharded search ok", fl.device_count)
    """)
    assert "sharded search ok 8" in out


# Spies on run_fleet's sweeps in a subprocess: the summary each pruned
# sweep fetched, and the FlowResult fields a sweep answers with.
_SUMMARY_SPY = """
from repro.core import flow, spans
from repro.core import metrics as M

summaries = []
_run_sweep = flow._run_sweep


def spy(exe, args):
    out, dt = _run_sweep(exe, args)
    if isinstance(out, flow.DevicePlane):
        summaries.append(out.summary)
    return out, dt


flow._run_sweep = spy


def same_summary(a, b):
    for f in M.PlaneSummary._fields:
        x, y = getattr(a, f), getattr(b, f)
        if f == "rows":
            x, y = x.view(np.uint64), y.view(np.uint64)
        assert x.dtype == y.dtype and np.array_equal(x, y), (f, x, y)


def fields(fl):
    return [(r.best_hw, r.best_cuts.tolist(), r.best_metrics, r.n_feasible,
             r.group_sizes, r.n_candidates, r.n_pruned, r.search_engine,
             repr(r.quarantine)) for r in fl.results] + [
        fl.n_candidates, repr(fl.quarantine)]


def select_work():
    return spans.per_call("fleet.call", "fleet.select")[-1][1]
"""


def test_sharded_pruned_summary_equals_single_device():
    # H=37 is a multiple of none of 2, 3 and 4: each mesh pads H with
    # copies of config 0, which the summary must not count.
    out = run_py(_SUMMARY_SPY + textwrap.dedent("""
    from repro.core.arch import Constraints, config_space_grid
    from repro.core.ir import as_graph, residual_block_ir, vgg16_ir

    space = config_space_grid(
        f1s=(2, 4), f2s=(2, 4), f3s=(2, 4), f4s=(2, 4),
        bus_widths=(2, 4), sram_splits=("unified",),
    )[:37]
    irs = [vgg16_ir(pool_mode="separate"), residual_block_ir()]
    rng = np.random.default_rng(7)
    batches = [np.unique(rng.random((300, as_graph(g).n_edges)) < 0.5, axis=0)
               for g in irs]
    inf = float("inf")
    # the paper's limits, and a tight set some cells are surely over
    for limits in (Constraints(), Constraints(inf, 5e7, 1e8, 1.2e7)):
        base = flow.run_fleet(irs, config_space=space, constraints=limits,
                              groupings=batches)
        one = summaries[-1]
        assert one.decides()
        assert (one.n_sure + one.n_undecided
                < len(space) * np.array([len(b) for b in batches])).any()
        for d in (2, 3, 4):
            fl = flow.run_fleet(irs, config_space=space, constraints=limits,
                                groupings=batches, devices=d)
            assert fl.device_count == d
            same_summary(summaries[-1], one)
            assert fields(fl) == fields(base)
            assert select_work() <= M.PRUNE_ROWS * len(irs)
            print("devices", d, "ok")
    """), n_devices=4)
    assert out.count("ok") == 6


def test_sharded_pruned_fallbacks_equal_the_whole_plane():
    # The crafted planes of tests/test_fleet_reduce.py, each shard's part
    # put in the fleet kernel's place, swept on a 4-device mesh (12
    # hardware rows a shard): the pruned pick, the summary and the
    # fallbacks (overflow across shards, no surely-feasible cell, poison
    # in shards 1 and 2) all equal the single-device program's, and each
    # pick equals the whole-plane path's, quarantine provenance included.
    out = run_py(_SUMMARY_SPY + textwrap.dedent("""
    import sys
    from jax import lax
    from repro.core.arch import Constraints
    from repro.core.errors import RetryPolicy
    from repro.testing.faults import FaultInjector

    sys.path.insert(0, "tests")
    import test_fleet_reduce as R

    kernel = M._evaluate_fleet_graph

    def use(fn):
        # put fn in the fleet kernel's place, to be traced anew
        M._evaluate_fleet_graph = fn
        M._jit_fleet_graph_pruned = jax.jit(
            lambda *a: M._evaluate_fleet_graph_pruned(*a))
        M._SHARDED_PRUNED_KERNELS.clear()
        flow.clear_sweep_cache()

    def put(plane):
        def crafted(*args):
            n = args[7].shape[0]  # this program's hardware rows
            if n == plane.shape[1]:
                return jnp.asarray(plane)
            start = lax.axis_index("hardware") * n
            return lax.dynamic_slice_in_dim(jnp.asarray(plane), start, n,
                                            axis=1)

        use(crafted)

    def outcome(graphs, batches, limits, devices, hooks=None):
        return R._outcome(lambda: flow.run_fleet(
            graphs, config_space=R.GRID, constraints=limits,
            groupings=batches, devices=devices, hooks=hooks))

    for case in sorted(R.CASES):
        rng = np.random.default_rng([0, len(case)])
        plane, counts, limits = R.CASES[case](rng)
        put(plane)
        graphs = R._graphs(len(counts))
        batches = R._batches(graphs, counts)
        single = outcome(graphs, batches, limits, None)
        one = summaries[-1]
        sharded = outcome(graphs, batches, limits, 4)
        same_summary(summaries[-1], one)
        work = select_work()
        assert sharded == single, case
        assert outcome(graphs, batches, limits, 4, R.Full()) == single, case
        if case in R.FALLS_BACK:
            assert work == R.H * sum(counts), case
        else:
            assert work <= R.K * len(counts), case
        if case == "poisoned":
            assert sharded[-1].count("QuarantinedCell(") == 4
        print(case, "ok")

    # a sick mesh degrades a pruned sweep to the single-device program
    use(kernel)
    graphs = R._graphs()
    batches = R._batches(graphs, [16])
    single = flow.run_fleet(graphs, config_space=R.GRID,
                            constraints=Constraints(), groupings=batches)
    faults = FaultInjector(mesh_fail_sweeps=2)
    fl = flow.run_fleet(graphs, config_space=R.GRID,
                        constraints=Constraints(), groupings=batches,
                        devices=4, hooks=faults,
                        retry_policy=RetryPolicy(max_retries=1,
                                                 backoff_seconds=0.0))
    assert fl.mesh_degraded and fl.device_count == 1
    assert faults.counts["injected_mesh_failures"] == 2
    assert select_work() <= R.K
    assert fields(fl) == fields(single)
    print("degraded ok")
    """), n_devices=4)
    assert out.count(" ok") == 15


def test_compressed_psum_accuracy_and_wire_dtype():
    out = run_py("""
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.parallel.compression import compressed_psum

    mesh = make_mesh((2,), ("pod",))

    @partial(jax.shard_map, mesh=mesh, in_specs=P("pod"),
             out_specs=P("pod"), check_vma=False)
    def sync(x):
        out, err = compressed_psum(x[0], "pod", mean=True)
        return (out + err * 0)[None]

    x = jax.random.normal(jax.random.key(0), (2, 1024)) * 3.0
    with jax.set_mesh(mesh):
        got = sync(x)
        txt = jax.jit(sync).lower(x).compile().as_text()
    expect = x.mean(axis=0)
    rel = float(jnp.abs(got[0] - expect).max() / (jnp.abs(expect).max()))
    n_s8 = sum(1 for l in txt.splitlines() if "all-reduce" in l and "s8[" in l)
    print("rel err", rel, "s8 allreduces", n_s8)
    assert rel < 0.05      # int8 quantisation error bound
    assert n_s8 >= 1       # payload really goes over the wire as int8
    """, n_devices=2)
    assert "s8 allreduces" in out
