"""Compiles for a described (not attached) TPU v5e:2x2, at real size.

The TPU compiler refuses what the CPU backend and the Pallas interpreter
accept: f64 programs it cannot emulate, kernel blocks off the (8, 128)
tiling, more scoped VMEM than a kernel may claim.  These tests compile the
programs ``chip_smoke.py`` runs, at its shapes, so a later change that the
chip would refuse fails here without one:

* the fleet kernel at the zoo co-search shapes (qwen3-0.6b and
  phi3-mini-3.8b superblocks at seq_len 512 x the 2560-point grid);
* the same kernel shard_mapped over a 4-device ``hardware`` mesh;
* the four Pallas kernels at the blocks ``planner.plan_model`` picks for
  qwen3-0.6b, with ``interpret=False``.

The topology is described inside a fixture, never at import, and the
persistent compilation cache is off around the compiles (a compile for a
described chip cannot be read back without one).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import REGISTRY
from repro.core import flow
from repro.core import metrics as M
from repro.core.arch import TPU_V5E, config_space_grid
from repro.core.frontend import transformer_graph
from repro.core.ir import bucket_size, pad_graph
from repro.core.planner import plan_model
from repro.kernels import (
    VMEM_LIMIT_BYTES, fused_attention, fused_conv, fused_mlp, mamba_scan,
)
from repro.parallel.sharding import HW_AXIS

SEQ_LEN = 512
ZOO = ("qwen3-0.6b", "phi3-mini-3.8b")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def fleet_args():
    """The run_fleet argument arrays of the zoo co-search (f64 words)."""
    graphs = [transformer_graph(REGISTRY[n], seq_len=SEQ_LEN) for n in ZOO]
    L = bucket_size(max(g.n_nodes for g in graphs), flow.NODE_BUCKET_FLOOR)
    E = bucket_size(max(g.n_edges for g in graphs), flow.EDGE_BUCKET_FLOOR)
    C = bucket_size(3, flow.CUT_BUCKET_FLOOR)  # lbl, fused, optimum
    padded = [pad_graph(g, n_nodes=L, n_edges=E) for g in graphs]
    grid = config_space_grid()
    return (
        np.stack([pg.feat for pg in padded]),
        np.stack([pg.esrc for pg in padded]),
        np.stack([pg.edst for pg in padded]),
        np.stack([pg.ewords for pg in padded]),
        np.stack([pg.src_mask for pg in padded]),
        np.stack([pg.sink_mask for pg in padded]),
        np.zeros((len(graphs), C, E), bool),
        np.stack([c.as_row() for c in grid]),
        M.area_consts_of_space(grid),
        np.stack([pg.node_mask for pg in padded]),
        np.stack([pg.edge_mask for pg in padded]),
    )


def _specs(args, shardings):
    return [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
            for a, s in zip(args, shardings)]


def test_fleet_kernel_compiles_for_one_chip(one_chip, fleet_args):
    assert fleet_args[0].dtype == np.float64
    assert fleet_args[7].shape == (2560, 11)
    with jax.enable_x64(True):
        specs = _specs(fleet_args, [one_chip] * len(fleet_args))
        compiled = M._jit_fleet_graph.lower(*specs).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def test_sharded_fleet_kernel_compiles_for_four_chips(topo, fleet_args):
    mesh = Mesh(np.asarray(topo.devices), (HW_AXIS,))
    assert mesh.devices.size == 4
    shardings = [NamedSharding(mesh, P())] * len(fleet_args)
    shardings[7] = NamedSharding(mesh, P(HW_AXIS))
    with jax.enable_x64(True):
        specs = _specs(fleet_args, shardings)
        compiled = M.sharded_fleet_kernel(mesh).lower(*specs).compile()
    # one H-shard per device; the plane is gathered on fetch, not in-program
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30
    assert "all-gather" not in compiled.as_text()


def _pallas_cases():
    cfg = REGISTRY["qwen3-0.6b"]
    plan = plan_model(cfg, SEQ_LEN)
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    d, ff, di, ds = cfg.d_model, cfg.d_ff, cfg.d_inner, cfg.ssm_state
    bf16, f32 = jnp.bfloat16, jnp.float32
    return {
        "attention": (
            lambda q, k, v: fused_attention.flash_attention(
                q, k, v, block_q=plan.attn_block_q,
                block_k=plan.attn_block_k, interpret=False),
            [((1, SEQ_LEN, H, hd), bf16), ((1, SEQ_LEN, KV, hd), bf16),
             ((1, SEQ_LEN, KV, hd), bf16)]),
        "mlp": (
            lambda x, w1, w2, w3: fused_mlp.fused_mlp(
                x, w1, w2, w3, block_m=plan.mlp_block_m,
                block_f=plan.mlp_block_f, interpret=False),
            [((SEQ_LEN, d), bf16), ((d, ff), bf16), ((ff, d), bf16),
             ((d, ff), bf16)]),
        "conv3x3": (
            lambda x, w, b: fused_conv.fused_conv3x3(
                x, w, b, pool=True, block_c=plan.conv_block_c,
                interpret=False),
            [((1, 56, 56, 256), bf16), ((3, 3, 256, 256), bf16),
             ((256,), bf16)]),
        "ssm_scan": (
            lambda a, b, c: mamba_scan.selective_scan(
                a, b, c, chunk=plan.mamba_chunk, block_d=plan.mamba_block_d,
                interpret=False),
            [((1, SEQ_LEN, di, ds), f32), ((1, SEQ_LEN, di, ds), f32),
             ((1, SEQ_LEN, ds), f32)]),
    }


@pytest.mark.parametrize("name", ["attention", "mlp", "conv3x3", "ssm_scan"])
def test_pallas_kernel_compiles_at_planner_blocks(one_chip, name):
    fn, shapes = _pallas_cases()[name]
    specs = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
             for s, dt in shapes]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_kernel_vmem_limit_is_the_planner_budget():
    assert VMEM_LIMIT_BYTES == TPU_V5E.vmem_bytes // 4
    plan = plan_model(REGISTRY["qwen3-0.6b"], SEQ_LEN)
    assert plan.mlp_vmem_bytes <= VMEM_LIMIT_BYTES
    assert plan.attn_vmem_bytes <= VMEM_LIMIT_BYTES
    assert 3072 % plan.mlp_block_f == 0 and SEQ_LEN % plan.attn_block_q == 0
