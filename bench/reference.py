"""Plain reference of the evaluator's answers: Eq. (1)-(4) of the paper, the
constraint filter, the min-energy pick and the Pareto front.

Written from the paper's equations and the documented cost model, in
numpy, importing nothing of the program under test.  It takes a
:class:`PlainGraph` (layer geometry and edges) and a hardware table built
by :func:`design_space` from the published grid definition, and answers
for any (hardware point x grouping) block:

* ``raw_block``    -> (H, C, 5) rows [bw, lat, c_sram, c_pb, area];
* ``metric_block`` -> (H, C, 4) rows [bw, lat, energy, area];
* ``pick``         -> the feasible min-energy (h, c) with the tie order
  (energy, bw, lat, area, h, c);
* ``pareto``       -> the feasible non-dominated (h, c) set.

Every integer-valued quantity is counted with integer arithmetic (ceil
divisions included), so the float64 values it returns are exact.  The
``dtype`` argument computes the same blocks in a lower precision: that is
the control that the comparison in ``bench/correct.py`` has to reject.
"""
from __future__ import annotations

import dataclasses
import itertools

import numpy as np

# Staging buffer (words) for tiles streamed from/to DRAM at group edges:
# a group's first input and last output need no full frame on chip.
STAGING_WORDS = 4096

WEIGHTED = ("conv", "fc", "matmul")
NO_MACS = ("pool", "elementwise", "scan")


@dataclasses.dataclass(frozen=True)
class Layer:
    """One node's geometry, in the paper's notation."""

    kind: str
    n_in: int
    n_out: int
    h_in: int
    w_in: int
    kh: int = 1
    kw: int = 1
    stride: int = 1
    pool_after: int = 1
    groups: int = 1
    ext_in_words: int = 0
    state_words: int = 0


@dataclasses.dataclass(frozen=True)
class PlainGraph:
    """A DAG of layers; ``edges`` rows are (src, dst, words), sorted."""

    name: str
    layers: tuple
    edges: np.ndarray  # (E, 3) int64

    @property
    def n_nodes(self) -> int:
        return len(self.layers)

    @property
    def n_edges(self) -> int:
        return int(self.edges.shape[0])


def chain(name: str, layers) -> PlainGraph:
    """A chain: edge i joins layer i to layer i+1 and carries i+1's input."""
    layers = tuple(layers)
    edges = [
        (i, i + 1, layers[i + 1].n_in * layers[i + 1].h_in * layers[i + 1].w_in)
        for i in range(len(layers) - 1)
    ]
    return PlainGraph(name, layers, np.asarray(edges, np.int64).reshape(-1, 3))


def _node_terms(g: PlainGraph) -> dict:
    """Per-node integer terms (python ints, exact)."""
    w, inw, outw, pre, macs, pix, nin = [], [], [], [], [], [], []
    for l in g.layers:
        ci = l.n_in // l.groups
        ho = l.h_in // l.stride
        wo = l.w_in // l.stride
        w.append(ci * l.kh * l.kw * l.n_out if l.kind in WEIGHTED else 0)
        base = l.n_in * l.h_in * l.w_in
        if l.kind == "actmul":  # both operands are activations
            base += l.n_in * l.kh * l.kw * l.n_out
        inw.append(base)
        outw.append(l.n_out * max(1, ho // l.pool_after)
                    * max(1, wo // l.pool_after))
        pre.append(l.n_out * ho * wo)
        macs.append(0 if l.kind in NO_MACS
                    else ci * l.kh * l.kw * l.n_out * ho * wo)
        pix.append(ho * wo)
        nin.append(ci)
    return dict(w=w, inw=inw, outw=outw, pre=pre, macs=macs, pix=pix, nin=nin)


def _cdiv(a, b):
    return -(-a // b)


# ---------------------------------------------------------------------------
# Hardware design space
# ---------------------------------------------------------------------------

HW_FIELDS = ("f1", "f2", "f3", "f4", "mults_per_pe", "bus", "t_pl",
             "e_dram", "e_sram", "e_pb", "pe_units")


def design_space(spec: dict) -> np.ndarray:
    """(H, 11) hardware rows in the order of ``HW_FIELDS``.

    ``spec`` is a configuration's ``design_space`` entry: the PE-array
    styles (hsiao: 9 multipliers a PE and F2 x F3 blocks; vwa: 1 and F2 x
    3), the factor sets, the DRAM bus widths, the SRAM splits with their
    per-access energy, and the scalar constants.  Points are enumerated
    style, then SRAM split, then bus width, then (F1, F2, F3, F4).
    """
    rows = []
    for style in spec["styles"]:
        mpp = 9 if style == "hsiao" else 1
        f3s = spec["f3"] if style == "hsiao" else [3]
        for _split, e_sram in spec["sram_splits"]:
            for bus in spec["bus_widths"]:
                for f1, f2, f3, f4 in itertools.product(
                        spec["f1"], spec["f2"], f3s, spec["f4"]):
                    n_pes = f1 * f2 * f3 * f4
                    rows.append([f1, f2, f3, f4, mpp, bus,
                                 spec["pipeline_latency"], spec["e_dram_nj"],
                                 e_sram, spec["e_pb_nj"], n_pes])
    return np.asarray(rows, np.float64)


def area_constants(spec: dict) -> tuple:
    """(per multiplier, per PE overhead, per SRAM byte, controller) um^2."""
    a = spec["area_um2"]
    return (a["per_mult"], a["per_pe_overhead"], a["per_sram_byte"],
            a["controller"])


# ---------------------------------------------------------------------------
# Eq. (1)-(4)
# ---------------------------------------------------------------------------


class Evaluator:
    """Eq. (1)-(4) of one graph over one hardware table."""

    def __init__(self, g: PlainGraph, hw: np.ndarray, area: tuple):
        self.g = g
        self.hw = np.asarray(hw, np.float64)
        self.area = area
        t = _node_terms(g)
        L = g.n_nodes
        src, dst, words = (g.edges[:, 0], g.edges[:, 1], g.edges[:, 2])
        indeg = np.bincount(dst, minlength=L) if len(dst) else np.zeros(L, int)
        outdeg = np.bincount(src, minlength=L) if len(src) else np.zeros(L, int)
        self.src, self.dst, self.words = src, dst, words
        self.outw = np.asarray(t["outw"], np.int64)
        self.pre = np.asarray(t["pre"], np.int64)
        self.sink = outdeg == 0
        self.state = np.asarray([l.state_words for l in g.layers], np.int64)
        ext = sum(l.ext_in_words for l in g.layers)
        self.sum_w = sum(t["w"])
        self.w_max = max(t["w"])
        # Source nodes read their input frame; ext words are read always.
        self.read_src = ext + sum(
            t["inw"][i] for i in range(L) if indeg[i] == 0)
        in_edge = np.zeros(L, np.int64)
        np.add.at(in_edge, dst, words)
        self.c_sram = sum(
            t["w"][i] + max(t["inw"][i], int(in_edge[i])
                            + g.layers[i].ext_in_words) + t["outw"][i]
            for i in range(L))
        # t_PB per hardware point, summed over nodes (exact integers).
        hwi = self.hw[:, :7].astype(np.int64)
        tpb = np.zeros(len(hwi), np.int64)
        for i, l in enumerate(g.layers):
            if t["macs"][i] <= 0:
                continue
            f1, f2, f3, f4, mpp = (hwi[:, k] for k in range(5))
            co = _cdiv(l.n_out, f1)
            ci = _cdiv(t["nin"][i], f4)
            hs = co * ci * _cdiv(t["pix"][i], f2 * f3) * _cdiv(l.kh * l.kw, 9)
            vw = co * ci * _cdiv(t["pix"][i], f2) * l.kh * _cdiv(l.kw, 3)
            tpb += np.where(mpp == 9, hs, vw)
        self.tpb = tpb
        self.n_nodes = L

    def cut_terms(self, cuts: np.ndarray) -> dict:
        """Per-grouping integer terms of a (C, E) cut batch."""
        cuts = np.asarray(cuts, bool)
        C = cuts.shape[0]
        L = self.n_nodes
        writes = np.tile(self.sink, (C, 1))
        internal_in = np.zeros((C, L), np.int64)
        internal_out = np.zeros((C, L), bool)
        read_edges = np.zeros(C, np.int64)
        for k in range(len(self.src)):
            s, d, w = int(self.src[k]), int(self.dst[k]), int(self.words[k])
            ck = cuts[:, k]
            writes[:, s] |= ck
            read_edges += np.where(ck, w, 0)
            internal_in[:, d] += np.where(ck, 0, w)
            internal_out[:, s] |= ~ck
        write_out = writes.astype(np.int64) @ self.outw
        src_need = np.where(internal_in > 0, internal_in, STAGING_WORDS) \
            + self.state[None, :]
        dst_need = np.where(internal_out, self.pre[None, :], STAGING_WORDS)
        if_need = np.maximum(src_need.max(axis=1), STAGING_WORDS)
        of_need = np.maximum(dst_need.max(axis=1), STAGING_WORDS)
        bw = self.sum_w + self.read_src + read_edges + write_out
        return dict(bw=bw, read_edges=read_edges, write_out=write_out,
                    buf=if_need + self.w_max + of_need)

    def raw_block(self, cuts, h_index=None, dtype=np.float64) -> np.ndarray:
        """(H, C, 5) [bw, lat, c_sram, c_pb, area] in ``dtype``."""
        h = np.arange(len(self.hw)) if h_index is None else np.asarray(h_index)
        return self._raw(self.cut_terms(cuts), h[:, None], dtype)

    def raw_pairs(self, cuts, h_index, dtype=np.float64) -> np.ndarray:
        """(K, 5) raw rows of K (cut row, hardware index) pairs."""
        return self._raw(self.cut_terms(cuts), np.asarray(h_index), dtype)

    def _raw(self, t: dict, h: np.ndarray, dtype) -> np.ndarray:
        """Raw rows of hardware indices ``h`` (broadcast against the cut
        axis of ``t``, the last axis)."""
        f = lambda x: np.asarray(x, dtype)  # noqa: E731
        hw = self.hw[h]  # (..., 11)
        col = lambda k: f(hw[..., k])  # noqa: E731
        bus = col(5)
        tpb = f(self.tpb[h])
        lat = (f(self.sum_w) / bus + tpb + f(self.n_nodes) * col(6)
               + f(self.read_src + t["read_edges"]) / bus
               + f(t["write_out"]) / bus)
        a_mult, a_ovh, a_byte, a_ctrl = (f(a) for a in self.area)
        n_pes = col(0) * col(3) * col(1) * col(2)
        area = n_pes * (col(4) * a_mult + a_ovh) + f(t["buf"]) * a_byte \
            + a_ctrl
        shape = np.broadcast_shapes(lat.shape, area.shape)
        out = np.empty(shape + (5,), dtype)
        out[..., 0] = f(t["bw"])
        out[..., 1] = lat
        out[..., 2] = f(self.c_sram)
        out[..., 3] = tpb * col(10)
        out[..., 4] = area
        return out

    def compose_pairs(self, raw, h_index) -> np.ndarray:
        """(K, 5) raw rows of K pairs -> (K, 4) metrics."""
        return self.compose(raw[:, None, :], np.asarray(h_index))[:, 0, :]

    def compose(self, raw, h_index=None) -> np.ndarray:
        """(H, C, 5) raw rows -> (H, C, 4) [bw, lat, energy, area]."""
        hw = self.hw if h_index is None else self.hw[h_index]
        dt = raw.dtype
        e = (hw[:, 7, None].astype(dt) * raw[..., 0]
             + hw[:, 8, None].astype(dt) * raw[..., 2]
             + hw[:, 9, None].astype(dt) * raw[..., 3])
        return np.stack([raw[..., 0], raw[..., 1], e, raw[..., 4]], axis=-1)

    def metric_block(self, cuts, h_index=None, dtype=np.float64):
        """(H, C, 4) [bw, lat, energy, area]."""
        return self.compose(self.raw_block(cuts, h_index, dtype), h_index)


# ---------------------------------------------------------------------------
# Selection
# ---------------------------------------------------------------------------


def pick(metrics: np.ndarray, limits) -> tuple | None:
    """Feasible min-energy (h, c) of an (H, C, 4) block, ties broken by
    (bw, lat, area, h, c); None when nothing is feasible."""
    feas = np.all(metrics <= np.asarray(limits)[None, None, :], axis=-1)
    if not feas.any():
        return None
    hs, cs = np.nonzero(feas)
    rows = metrics[hs, cs]
    order = np.lexsort((cs, hs, rows[:, 3], rows[:, 1], rows[:, 0],
                        rows[:, 2]))
    return int(hs[order[0]]), int(cs[order[0]])


def key(row, h: int, c: int) -> tuple:
    """The total order of the pick: (energy, bw, lat, area, h, c)."""
    return (float(row[2]), float(row[0]), float(row[1]), float(row[3]),
            int(h), int(c))


def pareto(metrics: np.ndarray, limits) -> set:
    """Feasible non-dominated (h, c) cells of an (H, C, 4) block; of rows
    with equal metrics only the first in (h, c) order is kept."""
    feas = np.all(metrics <= np.asarray(limits)[None, None, :], axis=-1)
    hs, cs = np.nonzero(feas)
    rows = metrics[hs, cs]
    keep = set()
    seen = set()
    for i in range(len(rows)):
        r = rows[i]
        dominated = np.any(np.all(rows <= r, axis=1)
                           & np.any(rows < r, axis=1))
        t = tuple(r.tolist())
        if not dominated and t not in seen:
            seen.add(t)
            keep.add((int(hs[i]), int(cs[i])))
    return keep


# ---------------------------------------------------------------------------
# Graphs handed over by the program's frontend
# ---------------------------------------------------------------------------


def plain_from_program(g) -> PlainGraph:
    """A traced graph's layer geometry and edges as a plain graph.  Only
    the geometry is read: every cost is counted again here."""
    layers = tuple(
        Layer(n.kind, n.n_in, n.n_out, n.h_in, n.w_in, n.kh, n.kw, n.stride,
              n.pool_after, n.groups, n.ext_in_words, n.state_words)
        for n in g.nodes)
    edges = np.asarray([(e.src, e.dst, e.words) for e in g.edges],
                       np.int64).reshape(-1, 3)
    return PlainGraph(g.name, layers, edges)


def graph_diffs(a: PlainGraph, b: PlainGraph) -> int:
    """Fields in which two plain graphs differ (0 when identical)."""
    if a.n_nodes != b.n_nodes or a.n_edges != b.n_edges:
        return 1 + abs(a.n_nodes - b.n_nodes) + abs(a.n_edges - b.n_edges)
    n = sum(x != y for la, lb in zip(a.layers, b.layers)
            for x, y in zip(dataclasses.astuple(la), dataclasses.astuple(lb)))
    return n + int(np.sum(a.edges != b.edges))
