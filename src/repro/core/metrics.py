"""Evaluation metrics — Eq. (1)-(4) of the paper, in edge-cut semantics.

Two implementations, kept deliberately in lock-step (tests assert equality):

* ``*_ref``      — direct, readable transcriptions of the equations operating
  on :class:`repro.core.ir.GraphIR` (or a chain :class:`repro.core.ir.NetworkIR`,
  embedded losslessly via :func:`repro.core.ir.as_graph`) + a cut vector.
  These are the oracle.
* ``evaluate_batch_graph`` — a vectorised jnp version broadcast over a batch
  of hardware configurations (H) x a batch of fusion groupings (C), so the
  paper's exhaustive optimisation flow (Sec. II-C) runs as ONE jitted XLA
  program instead of a Python loop over ~5 M candidates.  Optional
  ``node_mask``/``edge_mask`` arguments admit zero-padded inputs (shape
  buckets, :func:`repro.core.ir.pad_graph`) with padded rows exactly inert;
  ``evaluate_fleet_graph`` adds a leading graph axis so a whole fleet of
  padded graphs evaluates as a single program (:mod:`repro.core.flow`).
  ``evaluate_batch`` is the chain-shaped wrapper kept for the original
  (L, F) x (C, L-1) call signature.

Grouping representation: a boolean *cut vector* over the graph's **edges**
(canonically sorted by ``(src, dst)``).  ``cuts[k]`` True means edge ``k``
crosses a fusion-group boundary.  The cost model per Eq. (1)-(4):

* a **cut** edge costs DRAM on both ends — the producer writes its output
  frame once (however many cut consumers it feeds), and each cut consumer
  reads the edge's ``words`` back;
* an **internal** (uncut) edge costs only SRAM: the tensor ping-pongs
  between the on-chip frame buffers and never touches DRAM, but its
  *pre-pool* frame must fit on chip (Eq. (4) sizing);
* source nodes always read their input frame from DRAM; sink nodes always
  write their output frame.

On a chain embedding (edge ``i`` = layer ``i`` -> ``i+1``) this reduces
exactly to the paper's per-group ``in_first + out_last`` accounting:
layer-by-layer execution is ``cuts = all True``; whole-network fusion is
``all False``.  See :mod:`repro.core.ir` for an ASCII picture of a residual
block's cut space.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import enable_x64

from .errors import ConfigValidationError, GraphValidationError

from .arch import DLAConfig
from .ir import GraphIR, NetworkIR, as_graph

# Staging buffer (words) for tiles streamed directly from/to DRAM at group
# edges — a group's first input and last output never need full-frame SRAM.
STAGING_WORDS = 4096.0


def group_masks(cuts: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(start, end) boolean masks of shape (L,) from a chain cut vector (L-1,)."""
    cuts = np.asarray(cuts, dtype=bool)
    L = cuts.shape[0] + 1
    start = np.concatenate([[True], cuts])
    end = np.concatenate([cuts, [True]])
    assert start.shape == (L,) and end.shape == (L,)
    return start, end


def groups_from_cuts(cuts: np.ndarray) -> list[list[int]]:
    """Explicit group index lists (for printing / brute-force tests)."""
    start, _ = group_masks(cuts)
    groups: list[list[int]] = []
    for i, s in enumerate(start):
        if s:
            groups.append([i])
        else:
            groups[-1].append(i)
    return groups


def edge_io_masks(g: GraphIR, cuts: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(reads_input, writes_output) node masks of shape (L,) for a cut vector.

    ``reads_input[i]``  — node i streams its *external* input frame from DRAM
    (only source nodes; cut-edge reads are accounted per edge, not here).
    ``writes_output[i]`` — node i writes its output frame to DRAM (sink node,
    or at least one outgoing edge is cut).
    """
    cuts = np.asarray(cuts, dtype=bool)
    if cuts.shape != (g.n_edges,):
        raise ValueError(f"cut vector shape {cuts.shape} != (E={g.n_edges},)")
    reads = g.source_mask.copy()
    writes = g.sink_mask.copy()
    for k, e in enumerate(g.edges):
        if cuts[k]:
            writes[e.src] = True
    return reads, writes


# ---------------------------------------------------------------------------
# Reference implementations (the paper's equations in edge-cut form)
# ---------------------------------------------------------------------------


def bandwidth_ref(ir: NetworkIR | GraphIR, cuts: np.ndarray) -> float:
    """Eq. (1): BW = sum_p { sum_q {N Nkh Nkw M}_Lpq + N Nih Niw + Noh Now M }_Lp.

    Edge-cut form: every node's weights stream from DRAM; every source node
    reads its input frame (plus any node's ``ext_in_words`` — edge-less
    operands re-read in every grouping); every cut edge is read back by its
    consumer; every node with a cut outgoing edge (or no consumer) writes
    its output frame once.
    """
    g = as_graph(ir)
    cuts = np.asarray(cuts, dtype=bool)
    reads, writes = edge_io_masks(g, cuts)
    bw = 0.0
    for i, n in enumerate(g.nodes):
        bw += n.weight_words  # every layer's weights stream from DRAM
        bw += n.ext_in_words  # edge-less activation operands (always DRAM)
        if reads[i]:
            bw += n.in_words  # external input frame read
        if writes[i]:
            bw += n.out_words  # group output frame write
    for k, e in enumerate(g.edges):
        if cuts[k]:
            bw += e.words  # cut tensor read back by the consumer
    return bw


def latency_ref(ir: NetworkIR | GraphIR, cuts: np.ndarray, hw: DLAConfig) -> float:
    """Eq. (2): L = sum_p { sum_q {t_rd_W + t_PB + t_PL}_Lpq + t_rd_IF + t_wr_OF }_Lp."""
    g = as_graph(ir)
    cuts = np.asarray(cuts, dtype=bool)
    reads, writes = edge_io_masks(g, cuts)
    lat = 0.0
    for i, n in enumerate(g.nodes):
        lat += n.weight_words / hw.dram_words_per_cycle  # t_rd_W
        lat += hw.pe_busy_cycles(  # t_PB
            macs=n.macs,
            n_in=n.contracted_channels,
            n_out=n.n_out,
            kh=n.kh,
            kw=n.kw,
            pixels_out=(n.h_in // n.stride) * (n.w_in // n.stride),
        )
        lat += hw.pipeline_latency  # t_PL
        lat += n.ext_in_words / hw.dram_words_per_cycle
        if reads[i]:
            lat += n.in_words / hw.dram_words_per_cycle  # t_rd_IF
        if writes[i]:
            lat += n.out_words / hw.dram_words_per_cycle  # t_wr_OF
    for k, e in enumerate(g.edges):
        if cuts[k]:
            lat += e.words / hw.dram_words_per_cycle  # cut tensor read back
    return lat


def sram_accesses_ref(ir: NetworkIR | GraphIR) -> float:
    """C_SRAM: every layer operand passes on-chip SRAM exactly once,
    independent of grouping (fusion only changes what *also* touches DRAM).

    A node's input traffic is max(in_words, sum of incoming edge words +
    edge-less ``ext_in_words``): multi-input nodes (ResNet add) stream
    every fused operand through SRAM even though ``in_words`` describes a
    single frame, while chain embeddings (one edge carrying exactly
    ``in_words``) are unchanged.
    """
    g = as_graph(ir)
    in_edge = np.zeros(len(g.nodes))
    for e in g.edges:
        in_edge[e.dst] += e.words
    return float(
        sum(
            n.weight_words
            + max(n.in_words, in_edge[i] + n.ext_in_words)
            + n.out_words
            for i, n in enumerate(g.nodes)
        )
    )


def pe_energy_count_ref(ir: NetworkIR | GraphIR, hw: DLAConfig) -> float:
    """C_PE: busy cycles x pe_units (per-PE-cycle or per-block-cycle)."""
    g = as_graph(ir)
    total = 0.0
    for n in g.nodes:
        total += hw.pe_busy_cycles(
            macs=n.macs,
            n_in=n.contracted_channels,
            n_out=n.n_out,
            kh=n.kh,
            kw=n.kw,
            pixels_out=(n.h_in // n.stride) * (n.w_in // n.stride),
        )
    return total * hw.pe_units


# Back-compat alias (pre-calibration name).
pe_block_cycles_ref = pe_energy_count_ref


def energy_ref(ir: NetworkIR | GraphIR, cuts: np.ndarray, hw: DLAConfig) -> float:
    """Eq. (3): E = E_DRAM*C_DRAM + E_SRAM*C_SRAM + E_PB*C_PB   [nJ]."""
    c_dram = bandwidth_ref(ir, cuts)
    c_sram = sram_accesses_ref(ir)
    c_pb = pe_energy_count_ref(ir, hw)
    return hw.e_dram_nj * c_dram + hw.e_sram_nj * c_sram + hw.e_pb_nj * c_pb


def buffer_words_ref(
    ir: NetworkIR | GraphIR, cuts: np.ndarray
) -> tuple[float, float, float]:
    """SRAM sizing (IF, W, OF) in words for Eq. (4).

    Fused intermediates ping-pong between the input and output frame SRAMs;
    group-edge tensors stream through small staging buffers.  A node's IF
    SRAM must hold *all* of its internal incoming tensors simultaneously
    (one per uncut edge); its OF SRAM must hold the **pre-pool** output
    frame whenever any consumer is fused with it — the inline pool unit
    (Fig. 1) reduces the frame only on the DRAM write-out path, so a fused
    consumer sees the full pre-pool intermediate.  A recurrent node's
    ``state_words`` carry lives in IF SRAM for its whole execution, on top
    of whatever input it streams, in every grouping.  Weight SRAM holds the
    largest single layer's kernels.
    """
    g = as_graph(ir)
    cuts = np.asarray(cuts, dtype=bool)
    if_need, of_need = STAGING_WORDS, STAGING_WORDS
    internal_in = np.zeros(len(g.nodes))
    internal_out = np.zeros(len(g.nodes), dtype=bool)
    for k, e in enumerate(g.edges):
        if not cuts[k]:
            internal_in[e.dst] += e.words
            internal_out[e.src] = True
    for i, n in enumerate(g.nodes):
        src = internal_in[i] if internal_in[i] > 0 else STAGING_WORDS
        src += float(n.state_words)
        dst = float(n.out_words_prepool) if internal_out[i] else STAGING_WORDS
        if_need = max(if_need, src)
        of_need = max(of_need, dst)
    w_need = max(float(n.weight_words) for n in g.nodes)
    return float(if_need), float(w_need), float(of_need)


def area_ref(ir: NetworkIR | GraphIR, cuts: np.ndarray, hw: DLAConfig) -> float:
    """Eq. (4): A = A_PB + A_IFM + A_WB + A_OFM   [um^2]."""
    if_w, w_w, of_w = buffer_words_ref(ir, cuts)
    return hw.area_um2(if_sram_words=if_w, w_sram_words=w_w, of_sram_words=of_w)


@dataclasses.dataclass(frozen=True)
class Metrics:
    """The paper's four scores for one (graph, grouping, hw) candidate."""

    bandwidth_words: float
    latency_cycles: float
    energy_nj: float
    area_um2: float

    def meets(self, c) -> bool:
        """All four metrics within the :class:`Constraints` bounds."""
        return (
            self.bandwidth_words <= c.max_bandwidth_words
            and self.latency_cycles <= c.max_latency_cycles
            and self.energy_nj <= c.max_energy_nj
            and self.area_um2 <= c.max_area_um2
        )


def evaluate_ref(ir: NetworkIR | GraphIR, cuts: np.ndarray, hw: DLAConfig) -> Metrics:
    """Scalar-oracle Eq. (1)-(4) for one candidate (the lock-step ref)."""
    return Metrics(
        bandwidth_words=bandwidth_ref(ir, cuts),
        latency_cycles=latency_ref(ir, cuts, hw),
        energy_nj=energy_ref(ir, cuts, hw),
        area_um2=area_ref(ir, cuts, hw),
    )


# ---------------------------------------------------------------------------
# Batched numpy kernels — the search engine's scoring path
# ---------------------------------------------------------------------------
#
# The grouping search evaluates (C, E) cut batches thousands of times with a
# different C every round, so it scores with plain numpy (no per-shape XLA
# recompile, no dispatch overhead); `evaluate_batch_graph` below remains the
# jitted evaluator for the final (hw x grouping) sweep.  All sums here are of
# integer-valued float64 words (< 2^53), so the batched kernels are exactly
# equal to the scalar oracles, not just approximately (locked in tests).


@dataclasses.dataclass(frozen=True)
class GraphArrays:
    """Cached numpy views of a GraphIR consumed by the batched kernels."""

    feat: np.ndarray  # (L, F)
    esrc: np.ndarray  # (E,)
    edst: np.ndarray  # (E,)
    ewords: np.ndarray  # (E,)
    src_mask: np.ndarray  # (L,) bool
    sink_mask: np.ndarray  # (L,) bool
    inc_src: np.ndarray  # (E, L) 1.0 at [k, esrc[k]]
    win_dst: np.ndarray  # (E, L) ewords[k] at [k, edst[k]]
    out_edges: tuple[np.ndarray, ...]  # per node: its outgoing edge indices
    base_bw: float  # weights + unconditional source-frame reads


def graph_arrays(g: GraphIR) -> GraphArrays:
    """Per-instance memo (GraphIR is immutable, so this can never go stale);
    an attribute lookup rather than an lru_cache so the hot search loops do
    not re-hash the whole graph on every scoring call."""
    ga = g.__dict__.get("_graph_arrays")
    if ga is not None:
        return ga
    feat = g.node_features()
    esrc, edst, ewords = g.edge_arrays()
    E, L = len(esrc), len(g.nodes)
    inc_src = np.zeros((E, L))
    inc_src[np.arange(E), esrc] = 1.0
    win_dst = np.zeros((E, L))
    win_dst[np.arange(E), edst] = ewords
    out_edges = tuple(np.flatnonzero(esrc == i) for i in range(L))
    src_mask, sink_mask = g.source_mask, g.sink_mask
    base_bw = float(
        feat[:, F_W].sum() + feat[:, F_EXT].sum() + feat[src_mask, F_IN].sum()
    )
    ga = GraphArrays(
        feat=feat, esrc=esrc, edst=edst, ewords=ewords, src_mask=src_mask,
        sink_mask=sink_mask, inc_src=inc_src, win_dst=win_dst,
        out_edges=out_edges, base_bw=base_bw,
    )
    object.__setattr__(g, "_graph_arrays", ga)
    return ga


@dataclasses.dataclass(frozen=True)
class PrefixCostTables:
    """Per-node views of the grouping-dependent Eq. (1) terms, organised so
    the cost of a *prefix* of edge decisions is exactly decomposable.

    Sweeping nodes in any topological order and deciding each node's
    incoming edges as it arrives, Eq. (1) bandwidth (minus the
    grouping-independent weights, captured in ``const_words``) accumulates
    in exact per-decision increments:

    * a cut edge adds its ``words`` (the consumer's DRAM read-back), plus
      the producer's ``out_words`` **iff** this is the producer's first cut
      out-edge (the output frame is written once however many cut
      consumers it feeds);
    * a sink node adds its ``sink_charge`` unconditionally when processed;
    * an uncut edge adds nothing — but its words join the consumer's
      internal-input sum and put the producer's ``prepool_words`` frame on
      chip, the two Eq. (4)-style terms ``graph_max_intermediate`` bounds.

    This is the table set behind the frontier-state DP
    (:func:`repro.core.fusion.frontier_dp_min_bw`): all quantities are
    integer-valued float64 words, so the accumulated cost is bit-identical
    to :func:`bandwidth_ref` minus the weights, not approximately equal.
    """

    in_edges: tuple[np.ndarray, ...]  # per node: incoming edge indices
    in_srcs: tuple[np.ndarray, ...]  # per node: those edges' producers
    in_words: tuple[np.ndarray, ...]  # per node: those edges' words
    out_words: np.ndarray  # (L,) output frame (post-pool) words
    prepool_words: np.ndarray  # (L,) on-chip pre-pool frame words
    sink_charge: np.ndarray  # (L,) out_words where sink else 0.0
    const_words: float  # sources + ext reads (Eq. (1) minus weights)
    state_words: np.ndarray  # (L,) recurrent carry held in SRAM per node


def graph_prefix_tables(g: GraphIR) -> PrefixCostTables:
    """Per-instance memo of :class:`PrefixCostTables` (same discipline as
    :func:`graph_arrays`: GraphIR is immutable, so this can never go
    stale)."""
    pt = g.__dict__.get("_prefix_tables")
    if pt is not None:
        return pt
    ga = graph_arrays(g)
    L = len(g.nodes)
    in_edges = tuple(np.flatnonzero(ga.edst == i) for i in range(L))
    pt = PrefixCostTables(
        in_edges=in_edges,
        in_srcs=tuple(ga.esrc[ks] for ks in in_edges),
        in_words=tuple(ga.ewords[ks] for ks in in_edges),
        out_words=ga.feat[:, F_OUT].copy(),
        prepool_words=ga.feat[:, F_OUT_PRE].copy(),
        sink_charge=np.where(ga.sink_mask, ga.feat[:, F_OUT], 0.0),
        const_words=ga.base_bw - float(ga.feat[:, F_W].sum()),
        state_words=ga.feat[:, F_STATE].copy(),
    )
    object.__setattr__(g, "_prefix_tables", pt)
    return pt


def bandwidth_batch_graph(
    ir: NetworkIR | GraphIR, cuts_batch: np.ndarray
) -> np.ndarray:
    """(C,) Eq. (1) bandwidth for a (C, E) cut batch — bit-identical to
    :func:`bandwidth_ref` per row, with no per-candidate Python."""
    g = as_graph(ir)
    ga = graph_arrays(g)
    cuts = np.atleast_2d(np.asarray(cuts_batch, dtype=bool))
    cutf = cuts.astype(np.float64)
    writes = (cutf @ ga.inc_src) > 0.0  # (C, L): >= 1 cut outgoing edge
    writes |= ga.sink_mask[None, :]
    return (
        ga.base_bw
        + cutf @ ga.ewords  # cut tensors read back by their consumers
        + writes.astype(np.float64) @ ga.feat[:, F_OUT]
    )

# Feature column indices (must match NetworkIR.FEATURES order).
(F_W, F_IN, F_OUT, F_OUT_PRE, F_MACS, F_ISPOOL, F_KH, F_KW, F_NIN, F_NOUT,
 F_PIX, F_EXT, F_STATE) = range(13)
# HW row indices (must match DLAConfig.ROW_FIELDS order).
(H_F1, H_F2, H_F3, H_F4, H_MPP, H_DWPC, H_TPL, H_EDRAM, H_ESRAM, H_EPB,
 H_PEU) = range(11)


def _ceil_div(a, b):
    return jnp.ceil(a / b)


def _pe_busy_cycles_vec(feat: jnp.ndarray, hw: jnp.ndarray) -> jnp.ndarray:
    """t_PB per layer, (L,) given one hw row — branch on PE style."""
    co = _ceil_div(feat[:, F_NOUT], hw[H_F1])
    ci = _ceil_div(feat[:, F_NIN], hw[H_F4])
    px_h = _ceil_div(feat[:, F_PIX], hw[H_F2] * hw[H_F3])  # hsiao: F2*F3 pixels
    kc_h = _ceil_div(feat[:, F_KH] * feat[:, F_KW], 9.0)
    px_v = _ceil_div(feat[:, F_PIX], hw[H_F2])  # vwa: F2 rows
    kc_v = feat[:, F_KH] * _ceil_div(feat[:, F_KW], 3.0)
    is_hsiao = hw[H_MPP] == 9
    cyc = jnp.where(is_hsiao, co * ci * px_h * kc_h, co * ci * px_v * kc_v)
    return jnp.where(feat[:, F_MACS] > 0, cyc, 0.0)


def _evaluate_one_graph(
    feat: jnp.ndarray,  # (L, F)
    esrc: jnp.ndarray,  # (E,) int
    edst: jnp.ndarray,  # (E,) int
    ewords: jnp.ndarray,  # (E,) float
    src_mask: jnp.ndarray,  # (L,) bool — in-degree 0
    sink_mask: jnp.ndarray,  # (L,) bool — out-degree 0
    cuts: jnp.ndarray,  # (E,) bool
    hw: jnp.ndarray,
    area_consts: jnp.ndarray,
    node_mask: jnp.ndarray,  # (L,) bool — False on padded node rows
    edge_mask: jnp.ndarray,  # (E,) bool — False on padded edge slots
) -> jnp.ndarray:
    """Raw row for one (grouping, hw) pair -> (5,) [bw, lat, c_sram, c_pb,
    area]; :func:`compose_metrics` turns it into [bw, lat, energy, area].

    Energy is deliberately NOT composed here: every quantity this kernel
    emits is exact in float64 (integer-valued sums; latency divides only by
    the power-of-two bus width; all area constants are dyadic), so results
    are bit-identical across program shapes — but ``e_sram``/``e_pb`` are
    non-dyadic, and XLA's freedom to FMA-fuse ``mul+add`` differently in
    the batch vs the vmapped fleet program would make an in-kernel energy
    differ between the two by an ulp.  Composing outside XLA (numpy) keeps
    every compiled variant bit-identical to the scalar oracles.

    ``node_mask``/``edge_mask`` admit zero-padded inputs (shape buckets, see
    :func:`repro.core.ir.pad_graph`): a padded edge is neither cut nor
    internal regardless of its ``cuts`` bit, and a padded node contributes
    no pipeline latency.  Padded feature rows are all-zero, so with the
    masks every padded term is exactly 0.0 (or the STAGING_WORDS floor in
    the Eq. (4) maxes) and padded evaluation is bit-identical to unpadded
    (integer-valued float64 words sum exactly in any order).
    """
    L = feat.shape[0]
    # A padded edge is inert on both sides of the cut/internal split.
    cut_real = cuts & edge_mask
    internal_real = (~cuts) & edge_mask
    cutf = cut_real.astype(feat.dtype)

    # Node write mask: sink, or >= 1 cut outgoing edge (scatter-max over src).
    any_out_cut = jnp.zeros(L, feat.dtype).at[esrc].max(cutf) > 0.5
    writes = any_out_cut | sink_mask

    # Eq. (1) — ext_in_words are edge-less operands, read in every grouping
    read_src = jnp.sum(jnp.where(src_mask, feat[:, F_IN], 0.0)) + jnp.sum(
        feat[:, F_EXT]
    )
    read_edges = jnp.sum(jnp.where(cut_real, ewords, 0.0))
    write_out = jnp.sum(jnp.where(writes, feat[:, F_OUT], 0.0))
    bw = jnp.sum(feat[:, F_W]) + read_src + read_edges + write_out

    # Eq. (2) — pipeline latency counts real nodes, not the padded shape
    t_pb = _pe_busy_cycles_vec(feat, hw)
    n_real = jnp.sum(node_mask.astype(feat.dtype))
    lat = (
        jnp.sum(feat[:, F_W]) / hw[H_DWPC]
        + jnp.sum(t_pb)
        + n_real * hw[H_TPL]
        + (read_src + read_edges) / hw[H_DWPC]
        + write_out / hw[H_DWPC]
    )

    # Eq. (3) — per-node input SRAM traffic is max(in_words, incoming edges)
    # so multi-input nodes count every operand (see sram_accesses_ref).
    in_edge = jnp.zeros(L, feat.dtype).at[edst].add(
        jnp.where(edge_mask, ewords, 0.0)
    )
    c_sram = jnp.sum(
        feat[:, F_W]
        + jnp.maximum(feat[:, F_IN], in_edge + feat[:, F_EXT])
        + feat[:, F_OUT]
    )
    c_pb = jnp.sum(t_pb) * hw[H_PEU]

    # Eq. (4): internal incoming tensors coexist in IF SRAM; a node with any
    # fused consumer holds its *pre-pool* frame in OF SRAM.
    internal_in = jnp.zeros(L, feat.dtype).at[edst].add(
        jnp.where(internal_real, ewords, 0.0)
    )
    any_out_internal = (
        jnp.zeros(L, feat.dtype).at[esrc].max(internal_real.astype(feat.dtype))
        > 0.5
    )
    src_need = (
        jnp.where(internal_in > 0, internal_in, STAGING_WORDS)
        + feat[:, F_STATE]
    )
    dst_need = jnp.where(any_out_internal, feat[:, F_OUT_PRE], STAGING_WORDS)
    if_need = jnp.maximum(jnp.max(src_need), STAGING_WORDS)
    of_need = jnp.maximum(jnp.max(dst_need), STAGING_WORDS)
    w_need = jnp.max(feat[:, F_W])
    a_mult, a_pe_ovh, a_byte, a_ctrl = area_consts
    n_pes = hw[H_F1] * hw[H_F4] * hw[H_F2] * hw[H_F3]
    area = (
        n_pes * (hw[H_MPP] * a_mult + a_pe_ovh)
        + (if_need + w_need + of_need) * a_byte
        + a_ctrl
    )
    return jnp.stack([bw, lat, c_sram, c_pb, area])


def _evaluate_batch_graph(
    feat: jnp.ndarray,  # (L, F) float
    esrc: jnp.ndarray,  # (E,) int
    edst: jnp.ndarray,  # (E,) int
    ewords: jnp.ndarray,  # (E,) float
    src_mask: jnp.ndarray,  # (L,) bool
    sink_mask: jnp.ndarray,  # (L,) bool
    cuts_batch: jnp.ndarray,  # (C, E) bool
    hw_rows: jnp.ndarray,  # (H, 11) float
    area_consts: jnp.ndarray,  # (4,) float
    node_mask: jnp.ndarray | None = None,  # (L,) bool; None = no padding
    edge_mask: jnp.ndarray | None = None,  # (E,) bool; None = no padding
) -> jnp.ndarray:
    """Unjitted kernel body -> RAW (H, C, 5) rows (eager path for tests);
    :func:`compose_metrics` folds them to (H, C, 4) metrics."""
    if node_mask is None:
        node_mask = jnp.ones(feat.shape[0], dtype=bool)
    if edge_mask is None:
        edge_mask = jnp.ones(esrc.shape[0], dtype=bool)
    per_cut = jax.vmap(
        _evaluate_one_graph,
        in_axes=(None, None, None, None, None, None, 0, None, None, None, None),
    )
    per_hw = jax.vmap(
        per_cut,
        in_axes=(None, None, None, None, None, None, None, 0, None, None, None),
    )
    return per_hw(
        feat, esrc, edst, ewords, src_mask, sink_mask, cuts_batch, hw_rows,
        area_consts, node_mask, edge_mask,
    )


# Jitted kernels (used AOT by repro.core.flow, always under enable_x64).
# They return RAW (…, 5) rows; compose_metrics folds them to (…, 4).
_jit_batch_graph = jax.jit(_evaluate_batch_graph)


def compose_metrics(raw, hw_rows) -> np.ndarray:
    """(…, H, C, 5) raw kernel rows -> (…, H, C, 4) [bw, lat, energy, area].

    Eq. (3) is composed here, outside XLA, in numpy: separate multiply and
    add passes cannot be FMA-fused, so every compiled kernel variant
    (exact-shape, shape-bucketed, vmapped fleet) yields bit-identical
    energy — and the term order matches :func:`energy_ref` exactly.
    """
    raw = np.asarray(raw)
    hw = np.asarray(hw_rows)
    bw, lat, c_sram, c_pb, area = np.moveaxis(raw, -1, 0)
    # (H, 1) factors broadcast against (…, H, C) metric planes.
    e_dram = hw[:, H_EDRAM, None]
    e_sram = hw[:, H_ESRAM, None]
    e_pb = hw[:, H_EPB, None]
    energy = e_dram * bw + e_sram * c_sram + e_pb * c_pb
    return np.stack([bw, lat, energy, area], axis=-1)


# ---------------------------------------------------------------------------
# Finite guard — poison detection on raw result planes
# ---------------------------------------------------------------------------

# The bit-identity discipline: every raw kernel row is an exact
# integer-valued float64, so any count above 2^53 has silently lost ulps
# and the "bit-identical across kernel variants" guarantee is void.
MAX_EXACT_WORDS = float(2 ** 53)


def poison_mask(raw) -> np.ndarray:
    """(…, 5) raw kernel rows -> (…,) bool mask of *poisoned* cells.

    A cell (one [bw, lat, c_sram, c_pb, area] row) is poisoned when any
    entry is NaN, +/-Inf, negative, or above ``2**53`` (the largest f64
    magnitude at which integer word counts are still exact) — any such
    row would silently corrupt the argmin / Pareto composition, so
    :mod:`repro.core.flow` excludes these cells *before* selection and
    reports them with (g, h, c) provenance instead.
    """
    raw = np.asarray(raw)
    bad = ~np.isfinite(raw) | (raw < 0.0) | (raw > MAX_EXACT_WORDS)
    return np.any(bad, axis=-1)


def assert_exact_f64(arr, *, what: str = "feature table") -> None:
    """Assert ``arr`` holds exactly-representable f64 word counts.

    The evaluator's equality-to-oracle guarantee assumes every feature /
    edge-word entry is a finite, non-negative, integer-valued float64
    below ``2**53``.  The giant-config zoo graphs (llama4 / arctic edge
    words reach ~1e10) are well inside that range, but a corrupted or
    overflowed table would break bit-identity silently — fail loudly at
    the sweep boundary instead.  Raises :class:`GraphValidationError`
    naming ``what`` and the first offending flat index.
    """
    a = np.asarray(arr, dtype=np.float64)
    bad = ~np.isfinite(a) | (a < 0.0) | (a > MAX_EXACT_WORDS) | (
        a != np.floor(a)
    )
    if bad.any():
        idx = int(np.flatnonzero(bad.ravel())[0])
        raise GraphValidationError(
            f"{what} is not exactly representable in f64: entry at flat "
            f"index {idx} is {a.ravel()[idx]!r} (must be a finite, "
            f"non-negative integer <= 2**53 for bit-exact evaluation)"
        )


def evaluate_batch_graph(
    feat,
    esrc,
    edst,
    ewords,
    src_mask,
    sink_mask,
    cuts_batch,
    hw_rows,
    area_consts,
    node_mask=None,
    edge_mask=None,
) -> np.ndarray:
    """All metrics for every (hw, grouping) pair -> (H, C, 4).

    The optional node/edge masks admit zero-padded (shape-bucketed) inputs;
    with masks of all-True (or None) this is exactly the unpadded evaluator.

    Evaluation runs under a *scoped* ``enable_x64`` (the global JAX config
    is untouched), so the dtype follows the inputs: float64 numpy arrays —
    the flow's path — evaluate in float64 and are **bit-identical** to the
    scalar ``*_ref`` oracles (all words are integer-valued, every division
    is by the power-of-two DRAM bus width, energy is composed outside XLA
    by :func:`compose_metrics`, and multiplication order matches the
    oracles term for term); pre-converted float32 ``jnp`` arrays keep
    float32 semantics.
    """
    with enable_x64(True):
        raw = _jit_batch_graph(
            feat, esrc, edst, ewords, src_mask, sink_mask, cuts_batch,
            hw_rows, area_consts, node_mask, edge_mask,
        )
    return compose_metrics(raw, hw_rows)


def _evaluate_fleet_graph(
    feat: jnp.ndarray,  # (G, L, F) float — padded to one fleet bucket
    esrc: jnp.ndarray,  # (G, E) int
    edst: jnp.ndarray,  # (G, E) int
    ewords: jnp.ndarray,  # (G, E) float
    src_mask: jnp.ndarray,  # (G, L) bool
    sink_mask: jnp.ndarray,  # (G, L) bool
    cuts_batch: jnp.ndarray,  # (G, C, E) bool
    hw_rows: jnp.ndarray,  # (H, 11) float — shared across the fleet
    area_consts: jnp.ndarray,  # (4,) float
    node_mask: jnp.ndarray,  # (G, L) bool
    edge_mask: jnp.ndarray,  # (G, E) bool
) -> jnp.ndarray:
    """Raw rows for every (graph, hw, grouping) triple -> (G, H, C, 5).

    One more vmap level over :func:`evaluate_batch_graph`: a whole fleet of
    graphs, zero-padded to a common ``(L, E, C)`` bucket
    (:func:`repro.core.ir.pad_graph`), evaluated by a single XLA program —
    the multi-graph sweep pays one compile regardless of fleet size.
    """
    per_graph = jax.vmap(
        _evaluate_batch_graph,
        in_axes=(0, 0, 0, 0, 0, 0, 0, None, None, 0, 0),
    )
    return per_graph(
        feat, esrc, edst, ewords, src_mask, sink_mask, cuts_batch, hw_rows,
        area_consts, node_mask, edge_mask,
    )


_jit_fleet_graph = jax.jit(_evaluate_fleet_graph)


# Per-mesh jitted shard_map wrappers around the fleet kernel.  Meshes are
# few (one per device layout the process ever sweeps on), so an unbounded
# memo is fine; the AOT executable cache in repro.core.flow is what bounds
# compiled-program memory.
_SHARDED_FLEET_KERNELS: dict = {}


def sharded_fleet_kernel(mesh):
    """The fleet kernel shard_mapped over ``mesh``'s 1-D hardware axis.

    ``hw_rows`` is sharded ``P(axis)`` along H; every other argument is
    replicated; the output keeps its (G, H, C, 5) logical shape with the H
    axis laid out across devices (``P(None, axis)``), so fetching the
    result is the one cross-device gather of the sweep.  Each device runs
    :func:`_evaluate_fleet_graph` on its H-shard — per-row arithmetic is
    identical to the single-device program (rows are vmapped independently;
    no cross-row reduction exists to reassociate), which is why the sharded
    sweep is bit-identical, not just close (asserted in
    tests/test_multidevice.py at 2 and 8 host devices).

    Callers must pad H to a multiple of the device count first
    (:func:`repro.core.flow.run_fleet` pads with copies of row 0 and slices
    the padded rows off before metrics composition — the PR 4 inert-padding
    idiom applied to the hardware axis).
    """
    from ..parallel.sharding import HW_AXIS, mesh_fingerprint

    key = mesh_fingerprint(mesh)
    fn = _SHARDED_FLEET_KERNELS.get(key)
    if fn is None:
        from jax.sharding import PartitionSpec as P

        repl = P()
        fn = jax.jit(
            jax.shard_map(
                _evaluate_fleet_graph,
                mesh=mesh,
                in_specs=(repl,) * 7 + (P(HW_AXIS), repl, repl, repl),
                out_specs=P(None, HW_AXIS),
            )
        )
        _SHARDED_FLEET_KERNELS[key] = fn
    return fn


def area_consts_of_space(config_space) -> np.ndarray:
    """Shared area constants of a config space, validating they ARE shared.

    The sweep kernels take one ``area_consts`` vector for the whole
    hardware batch (only row fields vary per config), so a space mixing
    area calibrations would silently evaluate every config under
    ``config_space[0]``'s constants — reject it instead."""
    consts = {
        (
            c.area_per_mult_um2,
            c.area_per_pe_overhead_um2,
            c.area_per_sram_byte_um2,
            c.area_controller_um2,
        )
        for c in config_space
    }
    if len(consts) != 1:
        raise ConfigValidationError(
            f"config space mixes {len(consts)} area-constant calibrations; "
            "the sweep shares one area_consts vector across the hardware "
            "batch — sweep each calibration separately"
        )
    return area_consts_of(config_space[0])


def pareto_front_mask(rows: np.ndarray) -> np.ndarray:
    """Boolean mask of the Pareto-optimal rows of an (N, M) metric matrix,
    minimising every column.

    A row is kept iff no other row is <= it in every column and < in at
    least one.  Exact-duplicate metric rows keep only their FIRST
    occurrence (lowest index) — the same deterministic lowest-index
    convention as the flow's argmin tie-break, so the front is invariant
    to padding and, up to identical metric rows, to permutation of the
    candidate axes.

    Complexity O(N log N + N * F) where F is the front size (rows are
    scanned in lexicographic order, in which any dominator of a row
    precedes it, so each row is tested against the accumulated front
    only).
    """
    rows = np.atleast_2d(np.asarray(rows, dtype=np.float64))
    n = rows.shape[0]
    mask = np.zeros(n, dtype=bool)
    if n == 0:
        return mask
    uniq, first_idx = np.unique(rows, axis=0, return_index=True)
    front = np.empty_like(uniq)
    k = 0
    for i, r in enumerate(uniq):
        # uniq rows are distinct, so componentwise <= already implies
        # strict dominance somewhere.
        if k and np.all(front[:k] <= r, axis=1).any():
            continue
        front[k] = r
        k += 1
        mask[first_idx[i]] = True
    return mask


def evaluate_fleet_graph(
    feat,
    esrc,
    edst,
    ewords,
    src_mask,
    sink_mask,
    cuts_batch,
    hw_rows,
    area_consts,
    node_mask,
    edge_mask,
) -> np.ndarray:
    """(G, H, C, 4) metrics — scoped-x64 wrapper over the jitted fleet
    kernel (see :func:`evaluate_batch_graph` for the dtype contract)."""
    with enable_x64(True):
        raw = _jit_fleet_graph(
            feat, esrc, edst, ewords, src_mask, sink_mask, cuts_batch,
            hw_rows, area_consts, node_mask, edge_mask,
        )
    return compose_metrics(raw, hw_rows)


def chain_edge_arrays(feat: np.ndarray):
    """(esrc, edst, ewords, src_mask, sink_mask) for a chain's (L, F) features."""
    L = feat.shape[0]
    esrc = np.arange(L - 1, dtype=np.int64)
    edst = np.arange(1, L, dtype=np.int64)
    ewords = np.asarray(feat[1:, F_IN], dtype=np.float64)
    src_mask = np.zeros(L, dtype=bool)
    src_mask[0] = True
    sink_mask = np.zeros(L, dtype=bool)
    sink_mask[-1] = True
    return esrc, edst, ewords, src_mask, sink_mask


def evaluate_batch(
    feat: jnp.ndarray,  # (L, F) float
    cuts_batch: jnp.ndarray,  # (C, L-1) bool
    hw_rows: jnp.ndarray,  # (H, 11) float
    area_consts: jnp.ndarray,  # (4,) float
) -> jnp.ndarray:
    """Chain-shaped wrapper around :func:`evaluate_batch_graph` -> (H, C, 4)."""
    esrc, edst, ewords, src_mask, sink_mask = chain_edge_arrays(np.asarray(feat))
    return evaluate_batch_graph(
        jnp.asarray(feat),
        jnp.asarray(esrc),
        jnp.asarray(edst),
        jnp.asarray(ewords),
        jnp.asarray(src_mask),
        jnp.asarray(sink_mask),
        jnp.asarray(cuts_batch),
        jnp.asarray(hw_rows),
        jnp.asarray(area_consts),
    )


def area_consts_of(hw: DLAConfig) -> np.ndarray:
    """The per-config area-calibration constants as a feature row."""
    return np.asarray(
        [
            hw.area_per_mult_um2,
            hw.area_per_pe_overhead_um2,
            hw.area_per_sram_byte_um2,
            hw.area_controller_um2,
        ],
        dtype=np.float64,
    )
