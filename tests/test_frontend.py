"""Frontend <-> hand-builder lock-step.

Since the tracing frontend landed, ``repro.core.ir.vgg16_ir`` and
``resnet18_ir`` are thin wrappers over tracing the real JAX models.  The
oracles here are *verbatim transcriptions of the pre-frontend hand-built
constructions* (repo convention: a regression in the tracer cannot hide
behind both paths changing together) — the traced graphs must reproduce
them node-and-edge-identically, and the fusion search must return identical
best cuts on both.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import frontend as F
from repro.core import fusion, metrics as M
from repro.core.arch import PAPER_OPTIMAL_CONFIG
from repro.core.flow import run_flow
from repro.core.ir import (
    RESNET18_STAGE_PLAN,
    VGG16_CONV_PLAN,
    EdgeSpec,
    GraphIR,
    LayerSpec,
    NetworkIR,
    as_graph,
    resnet18_ir,
    vgg16_ir,
)


# ---------------------------------------------------------------------------
# Verbatim transcriptions of the pre-frontend hand builders (the oracles)
# ---------------------------------------------------------------------------


def _vgg16_ir_handbuilt(*, pool_mode="separate", include_fc=False) -> NetworkIR:
    if pool_mode not in ("separate", "absorbed"):
        raise ValueError(pool_mode)
    layers = []
    for name, n_in, n_out, hw, pooled in VGG16_CONV_PLAN:
        if pooled and pool_mode == "absorbed":
            layers.append(
                LayerSpec(name, "conv", n_in, n_out, hw, hw, 3, 3, 1, pool_after=2)
            )
        else:
            layers.append(LayerSpec(name, "conv", n_in, n_out, hw, hw, 3, 3, 1))
            if pooled:
                layers.append(
                    LayerSpec(f"pool{name[4]}", "pool", n_out, n_out, hw, hw, 2, 2, 2)
                )
    if include_fc:
        layers.append(LayerSpec("fc6", "fc", 512 * 7 * 7, 4096, 1, 1))
        layers.append(LayerSpec("fc7", "fc", 4096, 4096, 1, 1))
        layers.append(LayerSpec("fc8", "fc", 4096, 1000, 1, 1))
    return NetworkIR("vgg16", tuple(layers))


def _resnet18_ir_handbuilt(*, input_hw=224) -> GraphIR:
    nodes, edges = [], []

    def add_node(spec):
        nodes.append(spec)
        return len(nodes) - 1

    def connect(src, dst, words=None):
        edges.append(
            EdgeSpec(src, dst, nodes[src].out_words if words is None else words)
        )

    conv1 = add_node(LayerSpec("conv1", "conv", 3, 64, input_hw, input_hw, 7, 7, 2))
    pool1 = add_node(
        LayerSpec("pool1", "pool", 64, 64, input_hw // 2, input_hw // 2, 3, 3, 2)
    )
    connect(conv1, pool1)
    cur = pool1
    c_in = 64
    hw_cur = input_hw // 4
    for stage, n_blocks, c_out, stride0 in RESNET18_STAGE_PLAN:
        for b in range(n_blocks):
            stride = stride0 if b == 0 else 1
            cin_blk = c_in if b == 0 else c_out
            tag = f"s{stage}b{b}"
            ca = add_node(
                LayerSpec(f"{tag}.conv_a", "conv", cin_blk, c_out, hw_cur, hw_cur, 3, 3, stride)
            )
            connect(cur, ca)
            hw_out = hw_cur // stride
            cb = add_node(
                LayerSpec(f"{tag}.conv_b", "conv", c_out, c_out, hw_out, hw_out, 3, 3, 1)
            )
            connect(ca, cb)
            if stride != 1 or cin_blk != c_out:
                ds = add_node(
                    LayerSpec(f"{tag}.downsample", "conv", cin_blk, c_out, hw_cur, hw_cur, 1, 1, stride)
                )
                connect(cur, ds)
                skip = ds
            else:
                skip = cur
            add = add_node(
                LayerSpec(f"{tag}.add", "elementwise", c_out, c_out, hw_out, hw_out)
            )
            connect(cb, add)
            connect(skip, add)
            cur = add
            hw_cur = hw_out
        c_in = c_out
    gap = add_node(
        LayerSpec("avgpool", "pool", 512, 512, hw_cur, hw_cur, hw_cur, hw_cur, hw_cur)
    )
    connect(cur, gap)
    fc = add_node(LayerSpec("fc", "fc", 512, 1000, 1, 1))
    connect(gap, fc)
    return GraphIR("resnet18", tuple(nodes), tuple(edges))


def _anon(g: GraphIR) -> GraphIR:
    """Strip node names (the only field the raw tracer cannot know)."""
    return GraphIR(
        g.name,
        tuple(dataclasses.replace(n, name=f"n{i}") for i, n in enumerate(g.nodes)),
        g.edges,
    )


# ---------------------------------------------------------------------------
# Traced == hand-built (nodes, edges, buffer sizes)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "kw",
    [
        {"pool_mode": "separate"},
        {"pool_mode": "absorbed"},
        {"pool_mode": "separate", "include_fc": True},
        {"pool_mode": "absorbed", "include_fc": True},
    ],
    ids=lambda kw: "-".join(f"{k}={v}" for k, v in kw.items()),
)
def test_traced_vgg16_equals_handbuilt(kw):
    assert vgg16_ir(**kw) == _vgg16_ir_handbuilt(**kw)


def test_traced_resnet18_equals_handbuilt():
    g, h = resnet18_ir(), _resnet18_ir_handbuilt()
    assert g.nodes == h.nodes
    assert g.edges == h.edges
    assert g == h


def test_raw_trace_of_vgg_forward_matches_as_graph():
    """``frontend.trace(model)`` with *no* renaming reproduces
    ``as_graph(vgg16_ir(...))`` — structure, edges and every buffer-relevant
    field — so the frontend needs zero per-model knowledge."""
    import jax
    import jax.numpy as jnp

    from repro.models import vgg

    g = F.trace(
        vgg.forward,
        vgg.param_specs(),
        jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32),
        name="vgg16",
    )
    hand = as_graph(_vgg16_ir_handbuilt(pool_mode="separate", include_fc=True))
    assert _anon(g) == _anon(hand)


def test_traced_buffer_sizes_identical():
    g, h = resnet18_ir(), _resnet18_ir_handbuilt()
    rng = np.random.default_rng(0)
    for _ in range(10):
        cuts = rng.random(g.n_edges) < 0.5
        assert M.buffer_words_ref(g, cuts) == M.buffer_words_ref(h, cuts)
        assert M.bandwidth_ref(g, cuts) == M.bandwidth_ref(h, cuts)


# ---------------------------------------------------------------------------
# Fusion search parity on traced vs hand-built IRs
# ---------------------------------------------------------------------------


def test_optimal_cuts_identical_on_traced_vgg():
    a = fusion.optimal_cuts(vgg16_ir(pool_mode="separate"))
    b = fusion.optimal_cuts(_vgg16_ir_handbuilt(pool_mode="separate"))
    np.testing.assert_array_equal(a.cuts, b.cuts)
    assert a.group_cost_words == b.group_cost_words
    assert a.n_groups == b.n_groups


def test_optimal_cuts_identical_on_traced_resnet18():
    a = fusion.optimal_cuts(resnet18_ir())
    b = fusion.optimal_cuts(_resnet18_ir_handbuilt())
    np.testing.assert_array_equal(a.cuts, b.cuts)
    assert a.group_cost_words == b.group_cost_words


# ---------------------------------------------------------------------------
# Previously unrepresentable workloads through the full flow
# ---------------------------------------------------------------------------

SMALL_PLAN = ((32, 16, 1, 1), (16, 16, 1, 4))  # stem + 2 blocks, one skip


def test_mobilenet_graph_structure():
    g = F.mobilenet_graph(input_hw=56, plan=SMALL_PLAN)
    names = [n.name for n in g.nodes]
    assert names == [
        "stem", "b0.dw", "b0.project",
        "b1.expand", "b1.dw", "b1.project", "b1.add",
    ]
    dw = {n.name: n for n in g.nodes}
    # depthwise: groups == channels, one kernel per channel
    assert dw["b0.dw"].groups == 32 and dw["b0.dw"].weight_words == 9 * 32
    assert dw["b1.dw"].groups == 64 and dw["b1.dw"].contracted_channels == 1
    # the stride-1 bottleneck contributes a residual join
    add = names.index("b1.add")
    assert len(g.predecessors(add)) == 2
    assert not g.is_chain


def test_mobilenet_flow_batched_equals_scalar():
    g = F.mobilenet_graph(input_hw=56, plan=SMALL_PLAN)
    best = fusion.brute_force_min_bw(g)
    best_scalar = fusion._brute_force_min_bw_scalar(g)
    np.testing.assert_array_equal(best.cuts, best_scalar.cuts)
    beam = fusion.beam_merge_cuts(g)
    beam_scalar = fusion._beam_merge_cuts_scalar(g)
    np.testing.assert_array_equal(beam.cuts, beam_scalar.cuts)
    res = run_flow(g, groupings="search")
    assert res.best_metrics.bandwidth_words > 0
    assert res.n_feasible >= 1


def test_mlp_block_graph_structure_and_flow():
    g = F.mlp_block_graph(d_model=128, d_ff=512, seq_len=64, act="swiglu")
    names = [n.name for n in g.nodes]
    assert names == ["mlp.w1", "mlp.w3", "mlp.gate", "mlp.w2"]
    assert [n.kind for n in g.nodes] == ["matmul", "matmul", "elementwise", "matmul"]
    assert [(e.src, e.dst) for e in g.edges] == [(0, 2), (1, 2), (2, 3)]
    # both gate operands are (seq, d_ff) activations
    assert all(e.words == 64 * 512 for e in g.edges[:2])
    best = fusion.brute_force_min_bw(g)
    best_scalar = fusion._brute_force_min_bw_scalar(g)
    np.testing.assert_array_equal(best.cuts, best_scalar.cuts)
    res = run_flow(g, groupings="search")
    assert res.n_feasible >= 1
    # fusing the whole gated block keeps both d_ff-wide operands on chip
    lbl = M.bandwidth_ref(g, fusion.layer_by_layer_cuts(g))
    assert M.bandwidth_ref(g, best.cuts) < lbl


def test_mlp_block_graph_ungated_is_chain():
    g = F.mlp_block_graph(d_model=128, d_ff=512, seq_len=64, act="gelu")
    assert [n.name for n in g.nodes] == ["mlp.w1", "mlp.w2"]
    assert g.is_chain


# ---------------------------------------------------------------------------
# Tracer guard rails
# ---------------------------------------------------------------------------


def test_trace_rejects_batch_gt_one():
    import jax
    import jax.numpy as jnp

    from repro.models import vgg

    with pytest.raises(ValueError, match="batch size 1"):
        F.trace(
            vgg.forward,
            vgg.param_specs(),
            jax.ShapeDtypeStruct((2, 224, 224, 3), jnp.float32),
        )


def test_trace_rejects_valid_padding_geometry():
    import jax
    import jax.numpy as jnp

    def fn(w, x):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC")
        )

    with pytest.raises(ValueError, match="SAME-padding"):
        F.trace(
            fn,
            jax.ShapeDtypeStruct((3, 3, 8, 8), jnp.float32),
            jax.ShapeDtypeStruct((1, 16, 16, 8), jnp.float32),
        )


def test_fold_pool_requires_window_eq_stride_and_conv_producer():
    """ResNet traces identically with fold_pool: its 3x3/2 max pool has
    window != stride and its global avg pool follows an elementwise add,
    so neither can be absorbed into a conv's inline pool unit."""
    import jax
    import jax.numpy as jnp

    from repro.models import resnet

    g = F.trace(
        resnet.forward,
        resnet.param_specs(),
        jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32),
        name="resnet18",
        fold_pool=True,
    )
    assert _anon(g) == _anon(_resnet18_ir_handbuilt())
    assert [n.kind for n in g.nodes].count("pool") == 2


def test_rename_nodes_length_checked():
    g = F.mlp_block_graph()
    with pytest.raises(ValueError, match="names"):
        F.rename_nodes(g, ["a", "b"])


def test_traced_mobilenet_runs_paper_flow_end_to_end():
    """The full paper flow (Sec. II-C) on a traced depthwise workload."""
    g = F.mobilenet_graph()  # default 5-block plan, 18 edges
    res = run_flow(g, groupings="search", sram_budget_words=2**20)
    assert res.n_pruned >= 0 and res.n_feasible >= 1
    cmp_lbl = M.bandwidth_ref(g, fusion.layer_by_layer_cuts(g))
    assert res.best_metrics.bandwidth_words < cmp_lbl


# ---------------------------------------------------------------------------
# Dead dataflow: nodes from which no traced output can be reached
# ---------------------------------------------------------------------------


def _reaches_output(g) -> bool:
    """Every node has a path to a sink, and every sink is a traced output
    (the builders below return one tensor, so one sink)."""
    sinks = [i for i in range(g.n_nodes) if g.sink_mask[i]]
    return len(sinks) == 1 and sinks[0] == g.n_nodes - 1


def test_trace_leaves_out_dead_dataflow():
    """A loss term the function computes and drops (two matmuls and the
    join of their sums) adds no node; the live nodes are renumbered."""
    import jax
    import jax.numpy as jnp

    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    params = {k: sds(64, 64) for k in ("w1", "w2", "w3", "w4")}

    def fn(p, x):
        h = x @ p["w1"]
        aux = jnp.sum(h @ p["w2"]) + jnp.sum(x @ p["w4"])  # dropped
        del aux
        return h @ p["w3"]

    g = F.trace(fn, params, sds(16, 64), name="dead")
    assert [n.name for n in g.nodes] == ["matmul0", "matmul1"]
    assert [(e.src, e.dst) for e in g.edges] == [(0, 1)]


def test_trace_with_only_dead_layers_is_refused():
    import jax
    import jax.numpy as jnp

    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731

    def fn(p, x):
        del p
        jnp.sum(x @ jnp.ones((64, 8)))
        return x

    with pytest.raises(ValueError, match="no layers traced"):
        F.trace(fn, {}, sds(16, 64), name="none")


def test_every_traced_node_reaches_an_output():
    from repro.configs import REGISTRY

    jamba = F.transformer_graph(REGISTRY["jamba2-mini"], seq_len=4096)
    mixtral = F.transformer_graph(REGISTRY["mixtral-8x7b"], seq_len=512)
    for g in (F.resnet18_graph(), F.mobilenet_graph(), mixtral, jamba):
        assert _reaches_output(g), g.name
    # the builders' graphs are the ones they were before dead dataflow was
    # left out: Mixtral's period has one router and so no dropped join
    assert (mixtral.n_nodes, mixtral.n_edges) == (43, 60)
    assert (jamba.n_nodes, jamba.n_edges) == (369, 563)
    assert sorted({n.kind for n in jamba.nodes}) == [
        "actmul", "elementwise", "matmul", "scan"]
    assert sum(n.kind == "scan" for n in jamba.nodes) == 7
    assert all(n.state_words == 8192 * 16 for n in jamba.nodes
               if n.kind == "scan")


@pytest.mark.parametrize("batch_groups", [False, True])
def test_batch_groups_prices_one_product_per_head(batch_groups):
    """Q K^T over H heads: the batch axis folds into contraction and output
    either way (same words, same edges); as groups its MACs are those of H
    per-head products, folded H times that (the default)."""
    import jax
    import jax.numpy as jnp

    S, H, hd = 16, 4, 8
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    params = {"wq": sds(H * hd, H * hd), "wk": sds(H * hd, H * hd)}

    def fn(p, x):
        q = (x @ p["wq"]).reshape(S, H, hd)
        k = (x @ p["wk"]).reshape(S, H, hd)
        return jnp.einsum("qhd,khd->hqk", q, k)

    g = F.trace(fn, params, sds(S, H * hd), name="qk",
                batch_groups=batch_groups)
    (a,) = [n for n in g.nodes if n.kind == "actmul"]
    assert (a.n_in, a.n_out, a.h_in, a.w_in) == (H * hd, H * S, S, 1)
    assert a.groups == (H if batch_groups else 1)
    assert a.macs == H * S * S * hd * (1 if batch_groups else H)
    assert a.out_words == H * S * S
    assert [e.words for e in g.edges] == [S * H * hd, S * H * hd]


# Each config whose period repeats a sublayer kind, and Mixtral (a period
# of one), at two prefill lengths that tile its routing groups.
_PERIOD_CASES = [
    ("jamba2-mini", 4096), ("jamba2-mini", 131072),
    ("jamba-1.5-large-398b", 1024), ("jamba-1.5-large-398b", 4096),
    ("gemma3-27b", 1024), ("gemma3-27b", 4096),
    ("llama4-maverick-400b-a17b", 1024), ("llama4-maverick-400b-a17b", 4096),
    ("mixtral-8x7b", 512), ("mixtral-8x7b", 4096),
]


@pytest.mark.parametrize("batch_groups", [False, True])
@pytest.mark.parametrize("config,seq_len", _PERIOD_CASES)
def test_period_traced_per_kind_equals_whole_period_trace(
        config, seq_len, batch_groups):
    """``transformer_graph`` traces each distinct sublayer kind once and
    lowers the period sublayer by sublayer; the oracle traces the whole
    period's ``block_forward`` in one jaxpr.  Every node's name and spec
    and every edge agree, in order."""
    from repro.configs import REGISTRY
    from repro.configs.base import RunConfig
    from repro.models import transformer as T

    cfg = REGISTRY[config]
    kinds = cfg.sublayer_kinds(0, cfg.pattern_period)
    want = F.trace(
        lambda p, x: T.block_forward(p, x, cfg, kinds, rc=RunConfig(),
                                     attn_impl="reference"),
        T.sublayer_param_specs(cfg, kinds), F._sds(1, seq_len, cfg.d_model),
        name=f"{cfg.name}.block", batch_groups=batch_groups)
    got = F.transformer_graph(cfg, seq_len=seq_len,
                              batch_groups=batch_groups)
    assert [n.name for n in got.nodes] == [n.name for n in want.nodes]
    assert got.nodes == want.nodes
    assert got.edges == want.edges
    assert got == want


@pytest.mark.parametrize("config,seq_len,works", [
    ("jamba2-mini", 4096, [3, 4, 1]),  # (mamba, dense), (mamba, moe), attn
    ("mixtral-8x7b", 512, [1]),
])
def test_period_trace_records_one_jaxpr_span_per_kind(config, seq_len, works):
    import time

    from repro.configs import REGISTRY
    from repro.core import spans

    cfg = REGISTRY[config]
    t0 = time.perf_counter()
    g = F.transformer_graph(cfg, seq_len=seq_len)
    (root,) = spans.records("frontend.trace", t0)
    assert root.work == g.n_nodes
    kids = [r for r in spans.records(t_from=t0)
            if r.parent_id == root.span_id]
    assert [r.name for r in kids] == ["frontend.jaxpr"] * len(works)
    assert [r.work for r in kids] == works
    assert sum(r.seconds for r in kids) < root.seconds
