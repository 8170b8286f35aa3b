"""Fusion planner: the paper's optimization flow driving runtime kernels.

The paper's flow (Sec. II-C) picks hardware + layer-group configuration by
evaluating candidates against constraints.  Here the "hardware config" is
a Pallas kernel block shape and the "constraint" is the 128 MiB VMEM of a
v5e core: for each fusion group (attention, MLP, conv, SSM scan) the
planner enumerates candidate block shapes (MXU-aligned, multiples of 128),
rejects those whose VMEM working set does not fit, and picks the feasible
candidate minimising predicted HBM traffic (Eq. (1) with VMEM in place of
SRAM) — then the model stack executes that choice via repro.kernels.ops.

``plan_model`` also runs the *layer-grouping* half of the flow over the
architecture's transformer-block IR (repro.core.ir.transformer_block_ir)
to report the per-block bandwidth saving of fused vs. layer-by-layer
execution — the numbers in benchmarks table5/table6.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

from .arch import TPU_V5E, TPUSpec
from . import fusion
from . import ir as IR
from . import metrics as M

MXU = 128


@functools.lru_cache(maxsize=256)
def _block_bandwidths(
    name: str,
    d_model: int,
    n_heads: int,
    n_kv_heads: int,
    d_ff: int,
    seq_len: int,
    ffn_act: str,
    n_experts: int,
    top_k: int,
) -> tuple[float, float, str]:
    """(layer-by-layer, fused, engine) Eq. (1) bandwidth of one transformer
    block plus the search-engine provenance of the fused grouping.

    Memoised on the block-shaping config fields + seq_len: building the
    block IR and running ``optimal_cuts`` dominate ``plan_model``, and every
    caller (quickstart, benchmarks, repeated planning in a serve loop) asks
    for the same few (cfg, seq_len) points — repeats are a cache hit.
    """
    block_ir = IR.as_graph(IR.transformer_block_ir(
        name=name, d_model=d_model, n_heads=n_heads, n_kv_heads=n_kv_heads,
        d_ff=d_ff, seq_len=seq_len, ffn_act=ffn_act, n_experts=n_experts,
        top_k=top_k,
    ))
    # fused grouping: {q,kv} | {qk, pv} (flash) | {o} | {w1/w3, w2} (fused MLP)
    dp = fusion.optimal_cuts(block_ir)
    # Both groupings scored in one batched-evaluator call (lock-step with
    # bandwidth_ref, so the reported saving is unchanged).
    bws = M.bandwidth_batch_graph(
        block_ir, np.stack([fusion.layer_by_layer_cuts(block_ir), dp.cuts])
    )
    return float(bws[0]), float(bws[1]), dp.engine


@dataclasses.dataclass(frozen=True)
class FusionPlan:
    """Kernel tile/block choices for one (arch, seq_len) plus the
    evaluator's fused-vs-layer-by-layer bandwidth verdict."""

    arch: str
    seq_len: int
    # attention
    use_flash: bool
    attn_block_q: int
    attn_block_k: int
    attn_vmem_bytes: int
    # mlp
    use_fused_mlp: bool
    mlp_block_m: int
    mlp_block_f: int
    mlp_vmem_bytes: int
    # ssm
    mamba_chunk: int
    mamba_block_d: int
    # conv (vgg path)
    conv_block_c: int
    # evaluator outputs
    bw_fused_words: float
    bw_lbl_words: float
    # grouping-search provenance ("chain_dp" for transformer block chains;
    # "frontier_dp"/"beam" would signal a DAG-shaped block IR)
    search_engine: str = ""

    @property
    def bw_saving(self) -> float:
        """Fractional DRAM-traffic reduction of fused over lbl."""
        return 1.0 - self.bw_fused_words / max(self.bw_lbl_words, 1.0)

    def describe(self) -> str:
        """One-line tiling + bandwidth-saving summary."""
        return (
            f"{self.arch}@{self.seq_len}: flash({self.attn_block_q}x"
            f"{self.attn_block_k}, {self.attn_vmem_bytes/2**20:.1f}MiB) "
            f"mlp({self.mlp_block_m}x{self.mlp_block_f}, "
            f"{self.mlp_vmem_bytes/2**20:.1f}MiB) "
            f"block-BW saving {self.bw_saving*100:.1f}%"
        )


def _plan_attention(hd: int, seq: int, spec: TPUSpec):
    """Largest MXU-aligned (block_q, block_k) whose working set fits VMEM/4
    (leave headroom for double buffering + other live buffers)."""
    from ..kernels.fused_attention import vmem_bytes

    budget = spec.vmem_bytes // 4
    best = (MXU, MXU, vmem_bytes(MXU, MXU, hd))
    for bq in (128, 256, 512, 1024):
        for bk in (128, 256, 512, 1024):
            if bq > seq or bk > seq or seq % bq or seq % bk:
                continue
            b = vmem_bytes(bq, bk, hd)
            if b <= budget and bq * bk > best[0] * best[1]:
                best = (bq, bk, b)
    return best


def _plan_mlp(d: int, ff: int, spec: TPUSpec):
    from ..kernels.fused_mlp import vmem_bytes

    budget = spec.vmem_bytes // 4
    best = None
    for bm in (128, 256, 512):
        for bf in (128, 256, 512, 1024, 2048):
            if bf > ff or ff % bf:  # the kernel tiles d_ff exactly
                continue
            b = vmem_bytes(bm, bf, d)
            if b <= budget and (best is None or bm * bf > best[0] * best[1]):
                best = (bm, bf, b)
    if best is None:  # d too large for any tile: fall back to minimum
        best = (MXU, MXU, vmem_bytes(MXU, MXU, d))
    return best


def _plan_ssm(d_inner: int, d_state: int, spec: TPUSpec):
    """Longest scan chunk whose working set fits VMEM/4 at
    ``block_d = min(512, d_inner)`` (d_state pads to 128 lanes, so the
    chunk is what the budget bounds)."""
    from ..kernels.mamba_scan import vmem_bytes

    block_d = min(512, d_inner)
    for chunk in (64, 32, 16):
        if vmem_bytes(chunk, block_d, d_state) <= spec.vmem_bytes // 4:
            return chunk, block_d
    return 8, block_d


def plan_model(cfg, seq_len: int, spec: TPUSpec = TPU_V5E) -> FusionPlan:
    """Plan kernel tilings for one config and score fused vs lbl traffic."""
    hd = cfg.resolved_head_dim
    bq, bk, attn_b = _plan_attention(hd, seq_len, spec)
    bm, bf, mlp_b = _plan_mlp(cfg.d_model, max(cfg.d_ff, cfg.d_model), spec)
    chunk, block_d = _plan_ssm(cfg.d_inner, cfg.ssm_state, spec)

    # Evaluator pass over one transformer block: fused vs layer-by-layer BW.
    # The block chain embeds as a GraphIR so the same edge-cut search that
    # handles residual DAGs drives kernel selection here (chain DP fast
    # path); memoised per (cfg shape, seq_len) so repeated planning of the
    # same model is an evaluator-cache hit.
    lbl, fused, engine = _block_bandwidths(
        cfg.name, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
        max(cfg.d_ff, 1), seq_len, cfg.ffn_act, cfg.n_experts, cfg.top_k,
    )

    return FusionPlan(
        arch=cfg.name,
        seq_len=seq_len,
        use_flash=True,
        attn_block_q=bq,
        attn_block_k=bk,
        attn_vmem_bytes=attn_b,
        use_fused_mlp=cfg.d_ff > 0,
        mlp_block_m=bm,
        mlp_block_f=bf,
        mlp_vmem_bytes=mlp_b,
        mamba_chunk=chunk,
        mamba_block_d=block_d,
        conv_block_c=MXU,
        bw_fused_words=fused,
        bw_lbl_words=lbl,
        search_engine=engine,
    )
