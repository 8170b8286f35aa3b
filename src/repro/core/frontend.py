"""Tracing frontend — real JAX models to :class:`repro.core.ir.GraphIR`.

Every workload the evaluator scores used to be a hand-written ``*_ir``
builder; this module removes that transcription step.  :func:`trace` runs
``jax.make_jaxpr`` on a model's forward pass (weights may be
``jax.ShapeDtypeStruct`` pytrees — nothing is materialised) and lowers the
jaxpr onto the paper's layer abstraction:

* ``conv_general_dilated``  -> ``conv`` nodes (``feature_group_count`` maps
  to :class:`LayerSpec` ``groups``, so depthwise/grouped convs cost the
  right kernels words and MACs);
* ``dot_general``           -> ``matmul`` nodes (a matmul is the degenerate
  1x1 convolution over ``M`` "pixels"; ``M == 1`` is tagged ``fc``).  A
  ``dot_general`` whose *both* operands are activations becomes ``actmul``
  (attention's QK^T / PV — the "kernel" operand is activation traffic);
  batch dimensions fold into the contraction and the output (``k *= B``,
  ``n *= B``) so one actmul node prices all heads, and its O(S^2) score
  matrix is an explicit edge.  Folding both prices B times the MACs of B
  independent products; ``trace(..., batch_groups=True)`` also sets
  :class:`LayerSpec` ``groups`` to B, so each output contracts only its
  own batch index's ``k`` and the MAC count is that of the B products.
  Two-activation products whose operands descend from the *same* dataflow
  source (MoE's combine-weights einsum — a rearrangement of one tensor)
  fold instead of minting a bogus giant actmul.  An activation against a
  *batched weight* stack (MoE's ``(E, d, ff)`` expert einsums) expands
  into ``E`` branch ``matmul`` nodes whose incoming edges carry the routed
  per-expert capacity words — the producer becomes a *tuple* of node ids,
  and downstream elementwise ops over equal-length tuples stay branched
  (per-expert gate nodes) until a two-source product joins them;
* ``scan``                  -> one ``scan`` node (SSM selective scan): a
  weightless recurrent layer whose carry words (``d_state x d_inner``)
  become :class:`LayerSpec` ``state_words`` — SRAM the carry occupies in
  *every* grouping, priced by Eq. (4) and the buffer-feasibility checks.
  The chunk-recurrent form (``repro.models.ssm.selective_scan_chunked``)
  traces to the same node; splitting a model at a chunk boundary
  (:func:`mamba_graph` with ``chunks > 1``) exposes the carry hand-off as
  a real cuttable edge;
* ``reduce_window_{max,sum,min}`` -> ``pool`` nodes, or — with
  ``fold_pool=True`` and a window that equals its stride — absorbed into
  the producing conv's ``pool_after`` (the DLA's inline pool unit, Fig. 1);
* everything else is **folded**: an elementwise op (bias add, ReLU/SiLU,
  BN scale/shift, reshape/transpose/cast plumbing) whose activation
  operands come from a single producer node contributes no node — its
  output is re-attributed to that producer.  An elementwise op that *joins*
  two or more distinct dataflow sources (a residual add, a gated-MLP
  product; every graph input is its own source) becomes an ``elementwise``
  node, which is exactly how fan-in is represented in the hand-built DAGs.
  Operands read straight off a graph input have no producer node to fuse
  over, so a non-source consumer (a join, or an ``actmul``/``matmul`` with
  one produced operand) charges their words as ``LayerSpec.ext_in_words``
  — DRAM traffic in every grouping, counted by Eq. (1)-(3).

Dataflow recovery: a var is an *activation* iff it descends from a
designated activation argument (default: the last positional argument, so
``forward(params, x)`` traces with ``x`` as the input frame); every other
invar/constvar is weight or constant traffic.  Edges follow jaxpr use-def
between surviving nodes and carry the consumed tensor's word count, so
fan-out (a tensor read by several consumers) and skip paths come out as
real DAG edges.

The canonical builders at the bottom (``vgg16_network``,
``resnet18_graph``, ``mobilenet_graph``, ``mlp_block_graph``) trace the
real models in :mod:`repro.models` and rename nodes to the historical
builder names; ``repro.core.ir.vgg16_ir`` / ``resnet18_ir`` are thin
wrappers over them (locked node-and-edge-identical to verbatim
transcriptions of the old hand builders in ``tests/test_frontend.py``).

Geometry is validated as it is derived: the evaluator's ``SAME``-padding
``h_in // stride`` arithmetic must reproduce the traced output shape of
every conv/pool node, otherwise :func:`trace` raises rather than emitting
an IR whose edge words disagree with its node frames.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax.extend import core as jex_core

from . import spans
from .errors import GraphValidationError, UnsupportedOpError
from .ir import (
    RESNET18_STAGE_PLAN,
    VGG16_CONV_PLAN,
    EdgeSpec,
    GraphIR,
    LayerSpec,
    NetworkIR,
)

_REDUCE_WINDOW_PRIMS = ("reduce_window_max", "reduce_window_sum", "reduce_window_min")
_SPATIAL_REDUCE_PRIMS = ("reduce_sum", "reduce_max", "reduce_min")


def _words(aval) -> int:
    """Word count of a traced tensor (the paper uses one word per element)."""
    return int(np.prod(aval.shape, dtype=np.int64)) if aval.shape else 1


def _chw(shape: tuple[int, ...]) -> tuple[int, int, int]:
    """(channels, h, w) of an activation tensor: channels-last, leading
    size-1 batch axis dropped, remaining axes flattened into (h, w)."""
    if len(shape) > 2 and shape[0] == 1:
        shape = shape[1:]
    if not shape:
        return 1, 1, 1
    c = shape[-1]
    spatial = shape[:-1]
    if not spatial:
        return c, 1, 1
    if len(spatial) == 1:
        return c, int(spatial[0]), 1
    return c, int(spatial[0]), int(math.prod(spatial[1:]))


@dataclasses.dataclass
class _PendingNode:
    spec: LayerSpec
    inputs: dict[int, int]  # producer node id -> words read from it


class _Tracer:
    """``producer`` maps every activation var to the *dataflow source* it
    descends from: an ``int`` node id, or — for values read straight off a
    graph input — the original input var itself, so two different inputs
    stay two different sources (and two views of one input stay one)."""

    def __init__(self, *, name: str, fold_pool: bool, batch_groups: bool):
        self.name = name
        self.fold_pool = fold_pool
        self.batch_groups = batch_groups
        self.nodes: list[_PendingNode] = []
        self.producer: dict[Any, Any] = {}  # activation var -> source

    # ---- helpers -----------------------------------------------------------
    def _act_inputs(self, eqn) -> list[tuple[Any, Any]]:
        return [
            (v, self.producer[v])
            for v in eqn.invars
            if not isinstance(v, jex_core.Literal) and v in self.producer
        ]

    def _add_node(self, spec: LayerSpec, act_in) -> int:
        node = _PendingNode(spec=spec, inputs={})
        for v, p in act_in:
            if isinstance(p, tuple):
                # Branch fan-in (expert stacks): the consumed tensor is the
                # concatenation of the branch outputs — one edge per branch,
                # words split evenly across the members.
                w = max(1, _words(v.aval) // len(p))
                for member in p:
                    node.inputs[member] = max(node.inputs.get(member, 0), w)
                continue
            if not isinstance(p, int):
                continue  # graph-input operand: no producer node to fuse with
            w = _words(v.aval)
            node.inputs[p] = max(node.inputs.get(p, 0), w)
        self.nodes.append(node)
        return len(self.nodes) - 1

    def _ext_words(self, act_in) -> int:
        """Words of operands read straight off a graph input — DRAM traffic
        in every grouping (deduped per input var: two views of one input
        are one read)."""
        by_src: dict[Any, int] = {}
        for v, p in act_in:
            if not isinstance(p, (int, tuple)):
                by_src[p] = max(by_src.get(p, 0), _words(v.aval))
        return sum(by_src.values())

    def _check_geometry(self, spec: LayerSpec, out_shape, *, what: str) -> None:
        c, h, w = _chw(tuple(out_shape))
        if (spec.n_out, spec.h_out, spec.w_out) != (c, h, w):
            raise UnsupportedOpError(
                f"{self.name}: traced {what} {spec.name} derives "
                f"{spec.n_out}x{spec.h_out}x{spec.w_out} but the jaxpr "
                f"produces {c}x{h}x{w} — only SAME-padding geometry "
                f"(out = in // stride) is representable"
            )

    # ---- primitive lowering ------------------------------------------------
    def eqn_conv(self, eqn, act_in) -> None:
        lhs, rhs = eqn.invars[0], eqn.invars[1]
        if rhs in self.producer:
            raise UnsupportedOpError(
                f"{self.name}: conv with an activation kernel operand is "
                "not supported (use dot_general for activation products)"
            )
        # act_in is non-empty and rhs is not activation, so lhs is.
        p = eqn.params
        dn = p["dimension_numbers"]
        if p["lhs_dilation"] != (1,) * len(p["lhs_dilation"]) or p[
            "rhs_dilation"
        ] != (1,) * len(p["rhs_dilation"]):
            raise UnsupportedOpError(f"{self.name}: dilated convolutions unsupported")
        lshape, rshape = lhs.aval.shape, rhs.aval.shape
        if lshape[dn.lhs_spec[0]] != 1:
            raise UnsupportedOpError(f"{self.name}: trace with batch size 1")
        n_in = int(lshape[dn.lhs_spec[1]])
        spatial = [int(lshape[i]) for i in dn.lhs_spec[2:]]
        h_in, w_in = (spatial + [1])[:2]
        n_out = int(rshape[dn.rhs_spec[0]])
        ks = [int(rshape[i]) for i in dn.rhs_spec[2:]]
        kh, kw = (ks + [1])[:2]
        strides = tuple(int(s) for s in p["window_strides"])
        if len(set(strides)) != 1:
            raise UnsupportedOpError(f"{self.name}: anisotropic conv strides unsupported")
        groups = int(p["feature_group_count"])
        spec = LayerSpec(
            f"conv{len(self.nodes)}", "conv", n_in, n_out, h_in, w_in,
            kh, kw, strides[0], groups=groups,
        )
        out = eqn.outvars[0]
        out_spatial = [int(out.aval.shape[i]) for i in dn.out_spec[2:]]
        oh, ow = (out_spatial + [1])[:2]
        if (spec.h_out, spec.w_out) != (oh, ow):
            raise UnsupportedOpError(
                f"{self.name}: conv {spec.name} derives {spec.h_out}x{spec.w_out} "
                f"but the jaxpr produces {oh}x{ow} — only SAME-padding geometry "
                "(out = in // stride) is representable"
            )
        self.producer[out] = self._add_node(spec, [(lhs, self.producer[lhs])])

    def eqn_dot(self, eqn, act_in) -> None:
        lhs, rhs = eqn.invars[0], eqn.invars[1]
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lshape, rshape = lhs.aval.shape, rhs.aval.shape
        # Batch dims are pairwise equal-sized in both operands (jax checks);
        # B == 1 is the plain unbatched product.
        B = int(math.prod(lshape[d] for d in lb))
        k = int(math.prod(lshape[d] for d in lc))
        l_free = int(
            math.prod(lshape[d] for d in range(len(lshape)) if d not in lc and d not in lb)
        )
        r_free = int(
            math.prod(rshape[d] for d in range(len(rshape)) if d not in rc and d not in rb)
        )
        out = eqn.outvars[0]
        lhs_is_act = lhs in self.producer
        if len(act_in) == 2:
            if self.producer[lhs] == self.producer[rhs] and isinstance(
                self.producer[lhs], (int, tuple)
            ):
                # Both operands are views of ONE dataflow source (MoE's
                # combine-weights einsum: dispatch one-hots x gates, both
                # derived from the router) — a rearrangement, not a compute
                # node.  Minting an actmul here would price B * k bogus
                # MACs per output word.
                self.producer[out] = self.producer[lhs]
                return
            # Attention-style activation product: the batch axes (heads,
            # routing groups) fold into the contraction/output so one node
            # prices them all; as groups, each batch index's own.
            kind, k, m, n = "actmul", B * k, l_free, B * r_free
            groups = B if self.batch_groups else 1
        elif B > 1:
            # One activation against a stacked weight tensor (MoE expert
            # einsums, (E, d, ff)): E independent matmuls — expand into B
            # branch nodes so each expert's routed capacity words become a
            # real edge.  The out producer is the tuple of branch ids.
            av = lhs if lhs_is_act else rhs
            m = l_free if lhs_is_act else r_free
            n = r_free if lhs_is_act else l_free
            kind = "fc" if m == 1 else "matmul"
            if _words(out.aval) != B * m * n:
                raise UnsupportedOpError(
                    f"{self.name}: batched dot_general output has "
                    f"{_words(out.aval)} words, expected {B}*{m}*{n}"
                )
            p_act = self.producer[av]
            if isinstance(p_act, tuple) and len(p_act) != B:
                raise UnsupportedOpError(
                    f"{self.name}: {len(p_act)}-branch operand into a "
                    f"{B}-batched dot_general"
                )
            branch_words = max(1, _words(av.aval) // B)
            ext = 0 if isinstance(p_act, (int, tuple)) else branch_words
            ids = []
            for b in range(B):
                spec = LayerSpec(
                    f"{kind}{len(self.nodes)}", kind, k, n, m, 1,
                    ext_in_words=ext,
                )
                node = _PendingNode(spec=spec, inputs={})
                if isinstance(p_act, tuple):
                    node.inputs[p_act[b]] = branch_words  # branch b feeds b
                elif isinstance(p_act, int):
                    node.inputs[p_act] = branch_words  # fan-out (dispatch)
                self.nodes.append(node)
                ids.append(len(self.nodes) - 1)
            self.producer[out] = tuple(ids)
            return
        else:
            m, n = (l_free, r_free) if lhs_is_act else (r_free, l_free)
            kind = "fc" if m == 1 else "matmul"
            groups = 1
        # A graph-input operand of a non-source node (e.g. actmul of a
        # projected query against the raw input) has no edge to fuse over:
        # its words stream from DRAM in every grouping.  Source nodes
        # already count all operands via in_words.
        has_edge = any(isinstance(p, (int, tuple)) for _, p in act_in)
        ext = self._ext_words(act_in) if has_edge else 0
        spec = LayerSpec(
            f"{kind}{len(self.nodes)}", kind, k, n, m, 1, groups=groups,
            ext_in_words=ext,
        )
        if _words(out.aval) != m * n:
            raise UnsupportedOpError(
                f"{self.name}: dot_general output has {_words(out.aval)} words, "
                f"expected {m}*{n}"
            )
        self.producer[out] = self._add_node(spec, act_in)

    def eqn_reduce_window(self, eqn, act_in) -> None:
        (v, p_id) = act_in[0]
        shape = v.aval.shape
        window = tuple(int(d) for d in eqn.params["window_dimensions"])
        strides = tuple(int(s) for s in eqn.params["window_strides"])
        if len(shape) != 4 or window[0] != 1 or window[3] != 1:
            raise UnsupportedOpError(
                f"{self.name}: reduce_window expects NHWC with a spatial "
                f"window, got shape {shape} window {window}"
            )
        if shape[0] != 1:
            raise UnsupportedOpError(f"{self.name}: trace with batch size 1")
        kh, kw = window[1], window[2]
        sh, sw = strides[1], strides[2]
        if sh != sw:
            raise UnsupportedOpError(f"{self.name}: anisotropic pool strides unsupported")
        c, h_in, w_in = int(shape[3]), int(shape[1]), int(shape[2])
        out = eqn.outvars[0]
        if (
            self.fold_pool
            and isinstance(p_id, int)
            and self.nodes[p_id].spec.kind == "conv"
            and self.nodes[p_id].spec.pool_after == 1
            and (kh, kw) == (sh, sw)
            and self._use_count[v] == 1
        ):
            # Absorb into the producing conv's inline pool unit (Fig. 1).
            spec = dataclasses.replace(self.nodes[p_id].spec, pool_after=sh)
            self._check_geometry(spec, out.aval.shape, what="absorbed pool")
            self.nodes[p_id].spec = spec
            self.producer[out] = p_id
            return
        spec = LayerSpec(
            f"pool{len(self.nodes)}", "pool", c, c, h_in, w_in, kh, kw, sh
        )
        self._check_geometry(spec, out.aval.shape, what="pool")
        self.producer[out] = self._add_node(spec, act_in)

    def eqn_spatial_reduce(self, eqn, act_in) -> bool:
        """Global spatial reduction (``jnp.mean(x, (1, 2))``) -> pool node.
        Returns False when the reduction is not spatial-pool shaped (the
        caller then raises: folding a shape-changing reduction would break
        the producer-frame / edge-words consistency)."""
        (v, p_id) = act_in[0]
        shape = v.aval.shape
        axes = tuple(sorted(int(a) for a in eqn.params["axes"]))
        if len(shape) != 4 or axes != (1, 2) or shape[1] != shape[2]:
            return False
        if shape[0] != 1:
            raise UnsupportedOpError(f"{self.name}: trace with batch size 1")
        c, hw = int(shape[3]), int(shape[1])
        spec = LayerSpec(
            f"pool{len(self.nodes)}", "pool", c, c, hw, hw, hw, hw, hw
        )
        self.producer[eqn.outvars[0]] = self._add_node(spec, act_in)
        return True

    def eqn_scan(self, eqn, act_in) -> None:
        """``lax.scan`` -> one recurrent ``scan`` node.  The carry operands'
        words become ``state_words`` (summed over *all* carries by position
        — an initial state built as ``jnp.zeros`` inside the traced fn is a
        constant, not an activation, but still occupies the SRAM).  The node
        frame is the largest stacked output (the per-step ys restacked over
        the scan axis), so edge words stay consistent with consumers."""
        p = eqn.params
        nc, nk = int(p["num_consts"]), int(p["num_carry"])
        state = int(
            sum(_words(v.aval) for v in eqn.invars[nc : nc + nk])
        )
        ys = list(eqn.outvars[nk:]) or list(eqn.outvars[:1])
        big = max(ys, key=lambda o: _words(o.aval))
        c, h, w = _chw(tuple(big.aval.shape))
        has_edge = any(isinstance(pp, (int, tuple)) for _, pp in act_in)
        ext = self._ext_words(act_in) if has_edge else 0
        spec = LayerSpec(
            f"scan{len(self.nodes)}", "scan", c, c, h, w,
            ext_in_words=ext, state_words=state,
        )
        node = self._add_node(spec, act_in)
        for o in eqn.outvars:
            self.producer[o] = node

    def eqn_default(self, eqn, act_in) -> None:
        """Fold, or join >= 2 distinct sources into an ``elementwise`` node
        (the graph input counts as a source, so a residual add of the raw
        input still surfaces as a join).  Operands read straight from the
        graph input have no producer edge to fuse over, so their words
        become the join's ``ext_in_words`` — DRAM traffic in every
        grouping.  An op over >= 2 equal-length *tuple* producers (the
        expert-branch gate: silu(w1_e) * w3_e) stays branched — one
        ``elementwise`` node per member, pairwise — so the expert fan-out
        topology survives until a real combine joins it."""
        distinct = {p for _, p in act_in}
        if len(distinct) >= 2:
            out = eqn.outvars[0]
            c, h, w = _chw(tuple(out.aval.shape))
            if all(isinstance(p, tuple) for p in distinct) and (
                len({len(p) for p in distinct}) == 1
            ):
                branches = sorted(distinct)
                nb = len(branches[0])
                total = _words(out.aval)
                bw = max(1, total // nb)
                hb = max(1, bw // c)
                ids = []
                for b in range(nb):
                    spec = LayerSpec(
                        f"gate{len(self.nodes)}", "elementwise", c, c, hb, 1
                    )
                    node = _PendingNode(spec=spec, inputs={})
                    for t in branches:
                        node.inputs[t[b]] = max(node.inputs.get(t[b], 0), bw)
                    self.nodes.append(node)
                    ids.append(len(self.nodes) - 1)
                for o in eqn.outvars:
                    self.producer[o] = tuple(ids)
                return
            ext = self._ext_words(act_in)
            if not any(isinstance(p, (int, tuple)) for p in distinct):
                # All operands are raw inputs: the node is a *source* and
                # already reads in_words (one frame) — ext carries only the
                # frames beyond that.
                ext = max(0, ext - c * h * w)
            spec = LayerSpec(
                f"join{len(self.nodes)}", "elementwise", c, c, h, w,
                ext_in_words=int(ext),
            )
            node = self._add_node(spec, act_in)
            for o in eqn.outvars:
                self.producer[o] = node
            return
        p = distinct.pop() if distinct else None
        for o in eqn.outvars:
            self.producer[o] = p

    # ---- driver ------------------------------------------------------------
    def run(self, steps) -> GraphIR:
        """Lower ``steps``, (jaxpr, activation invars) pairs, in order into
        one graph.  The first step's activation invars are graph inputs,
        each its own source; a later step's take the previous step's
        outputs, in order (one sublayer's residual stream into the next).
        One jaxpr may stand for several steps: each walk rebinds every
        activation var before reading it, so it lowers as copies would."""
        outs = None
        for jaxpr, act_vars in steps:
            for i, v in enumerate(act_vars):
                self.producer[v] = v if outs is None else outs[i]
            self._walk(jaxpr)
            outs = [None if isinstance(v, jex_core.Literal)
                    else self.producer.get(v) for v in jaxpr.outvars]
        live = self._live_nodes(outs or ())
        if not live:
            raise UnsupportedOpError(f"{self.name}: no layers traced")
        new_id = {old: new for new, old in enumerate(live)}
        specs = []
        for old in live:
            spec = self.nodes[old].spec
            if len(live) < len(self.nodes):  # renumbered: rename to match
                stem = spec.name.rstrip("0123456789")
                spec = dataclasses.replace(spec, name=f"{stem}{new_id[old]}")
            specs.append(spec)
        edges = tuple(
            EdgeSpec(new_id[src], new_id[dst], words)
            for dst in live
            for src, words in sorted(self.nodes[dst].inputs.items())
        )
        return GraphIR(self.name, tuple(specs), edges)

    def _walk(self, jaxpr) -> None:
        self._use_count: dict[Any, int] = {}
        for eqn in jaxpr.eqns:
            for v in eqn.invars:
                if not isinstance(v, jex_core.Literal):
                    self._use_count[v] = self._use_count.get(v, 0) + 1
        for v in jaxpr.outvars:
            if not isinstance(v, jex_core.Literal):
                self._use_count[v] = self._use_count.get(v, 0) + 1
        for eqn in jaxpr.eqns:
            act_in = self._act_inputs(eqn)
            if not act_in:
                continue  # weights/constants only: nothing reaches the IR
            prim = eqn.primitive.name
            if prim == "conv_general_dilated":
                self.eqn_conv(eqn, act_in)
            elif prim == "dot_general":
                self.eqn_dot(eqn, act_in)
            elif prim in _REDUCE_WINDOW_PRIMS:
                self.eqn_reduce_window(eqn, act_in)
            elif prim in _SPATIAL_REDUCE_PRIMS:
                # Only an NHWC reduction over *both* spatial axes is
                # pool-shaped; everything else (softmax / rmsnorm statistics
                # over the channel axis, MoE routing sums over arbitrary
                # axes) is a normalisation-style statistic that folds or
                # joins like any elementwise op.
                shape = eqn.invars[0].aval.shape
                axes = tuple(sorted(int(a) for a in eqn.params["axes"]))
                if len(shape) == 4 and axes == (1, 2):
                    if not self.eqn_spatial_reduce(eqn, act_in):
                        # A rectangular global reduction would emit a pool
                        # whose SAME-geometry frame disagrees with the
                        # traced output — refuse.
                        raise UnsupportedOpError(
                            f"{self.name}: {prim} over axes "
                            f"{tuple(eqn.params['axes'])} on shape "
                            f"{eqn.invars[0].aval.shape} is not representable "
                            "(only square NHWC global spatial reductions map "
                            "to pool nodes)"
                        )
                else:
                    self.eqn_default(eqn, act_in)
            elif prim == "scan":
                self.eqn_scan(eqn, act_in)
            else:
                self.eqn_default(eqn, act_in)

    def _live_nodes(self, outs) -> list[int]:
        """Ids (ascending) of the nodes from which a traced output, given
        by its source, can be reached.  ``make_jaxpr`` keeps dead equations
        (a loss term the function computes and drops); a node that feeds no
        output would only add a sink write to every grouping, so it is left
        out."""
        stack = []
        for p in outs:
            stack.extend(p if isinstance(p, tuple) else
                         [p] if isinstance(p, int) else [])
        live: set[int] = set()
        while stack:
            i = stack.pop()
            if i not in live:
                live.add(i)
                stack.extend(self.nodes[i].inputs)
        return sorted(live)


def trace(
    fn: Callable,
    *args,
    name: str = "traced",
    activation_argnums: Sequence[int] | None = None,
    fold_pool: bool = False,
    names: Sequence[str] | None = None,
    batch_groups: bool = False,
) -> GraphIR:
    """Trace ``fn(*args)`` into a :class:`GraphIR`.

    ``args`` are pytrees of arrays or ``jax.ShapeDtypeStruct`` (weights are
    never materialised).  ``activation_argnums`` marks which arguments are
    activation inputs (default: the last one, matching ``forward(params,
    x)``); activations must be traced with batch size 1.  ``fold_pool``
    absorbs a window == stride pooling into its producing conv's
    ``pool_after`` when the pooled tensor has no other consumer.  ``names``
    optionally renames the nodes (length-checked).  ``batch_groups`` prices
    a product of two activations with batch axes (attention heads, MoE
    routing groups) as ``groups`` = B, at the MACs of B products; the
    default folds the batch axes into both the contraction and the output,
    B times that (ROADMAP Reach 2b), and stays until the benchmark's
    reference tables move with it.

    Example — a gated MLP, weights as shape structs only::

        >>> import jax, jax.numpy as jnp
        >>> from repro.core import frontend as F
        >>> from repro.models import layers as L
        >>> sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
        >>> params = {"w1": sds(256, 1024), "w3": sds(256, 1024),
        ...           "w2": sds(1024, 256)}
        >>> g = F.trace(lambda p, x: L.mlp_block(p, x, "swiglu"),
        ...             params, sds(128, 256), name="mlp")
        >>> [n.kind for n in g.nodes]
        ['matmul', 'matmul', 'elementwise', 'matmul']
        >>> g.n_edges  # w1 -> gate, w3 -> gate, gate -> w2
        3

    Failures are typed: anything the layer abstraction cannot represent
    raises :class:`repro.core.errors.UnsupportedOpError` (a subclass of
    ``ValueError``), never a raw ``KeyError``/``IndexError`` — the planning
    service's admission path relies on this contract.

    Nodes from which no traced output can be reached (a loss term the
    function computes and drops) are left out.  Runs in a
    ``frontend.trace`` span whose ``work`` is the node count.
    """
    with spans.span("frontend.trace") as sp:
        g = _trace(fn, args, name, activation_argnums, fold_pool, names,
                   batch_groups)
        sp.work = g.n_nodes
    return g


def _trace(fn, args, name, activation_argnums, fold_pool, names,
           batch_groups) -> GraphIR:
    if not args:
        raise UnsupportedOpError("trace() needs at least one example argument")
    nums = (
        {len(args) - 1}
        if activation_argnums is None
        else {a % len(args) for a in activation_argnums}
    )
    closed = _make_jaxpr(fn, args, name)
    act_vars = []
    invars = iter(closed.jaxpr.invars)
    for i, arg in enumerate(args):
        for _ in jax.tree_util.tree_leaves(arg):
            v = next(invars)
            if i in nums:
                act_vars.append(v)
    tr = _Tracer(name=name, fold_pool=fold_pool, batch_groups=batch_groups)
    g = _lower(tr, [(closed.jaxpr, act_vars)])
    if names is not None:
        g = rename_nodes(g, names)
    return g


def _make_jaxpr(fn, args, name):
    try:
        return jax.make_jaxpr(fn)(*args)
    except (UnsupportedOpError, GraphValidationError):
        raise
    except Exception as e:
        # jax itself rejected the function (rank/shape errors surface as
        # raw ValueError/IndexError/TypeError while *building* the jaxpr)
        # — the trace boundary converts them to the typed taxonomy.
        raise UnsupportedOpError(
            f"{name}: fn is not traceable to a jaxpr "
            f"({type(e).__name__}: {e})"
        ) from e


def _lower(tr: _Tracer, steps) -> GraphIR:
    # Lowering must fail *typed*: an unlowerable jaxpr is an
    # UnsupportedOpError and a lowered-but-invalid IR a
    # GraphValidationError — never a raw KeyError/IndexError from a
    # degenerate primitive the lowering rules did not anticipate (the
    # contract the service admission path and the fuzz tests rely on).
    try:
        return tr.run(steps)
    except (GraphValidationError, UnsupportedOpError):
        raise
    except (KeyError, IndexError, AttributeError, TypeError,
            ZeroDivisionError, AssertionError) as e:
        raise UnsupportedOpError(
            f"{tr.name}: jaxpr is not lowerable to the layer abstraction "
            f"({type(e).__name__}: {e})"
        ) from e


def rename_nodes(g: GraphIR, names: Sequence[str]) -> GraphIR:
    """Rename every node (length-checked) — traced graphs get the
    historical hand-builder names this way."""
    if len(names) != len(g.nodes):
        raise UnsupportedOpError(
            f"{g.name}: {len(names)} names for {len(g.nodes)} nodes "
            f"(traced: {[n.name for n in g.nodes]})"
        )
    nodes = tuple(
        dataclasses.replace(n, name=nm) for n, nm in zip(g.nodes, names)
    )
    return GraphIR(g.name, nodes, g.edges)


def to_chain(g: GraphIR, name: str | None = None) -> NetworkIR:
    """Collapse a chain-shaped trace back to the legacy :class:`NetworkIR`."""
    if not g.is_chain:
        raise UnsupportedOpError(f"{g.name} is not a chain ({g.n_edges} edges)")
    return NetworkIR(name or g.name, g.nodes)


# ---------------------------------------------------------------------------
# Canonical model builders (the thin wrappers `repro.core.ir` re-exports)
# ---------------------------------------------------------------------------


def _sds(*shape, dtype=None):
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(tuple(shape), dtype or jnp.float32)


def vgg16_network(
    *, pool_mode: str = "separate", include_fc: bool = False
) -> NetworkIR:
    """VGG-16 traced from :mod:`repro.models.vgg` (the paper's Sec. III
    workload) — ``pool_mode="absorbed"`` folds each 2x2 pool into its conv."""
    from ..models import vgg

    if pool_mode not in ("separate", "absorbed"):
        raise UnsupportedOpError(pool_mode)
    g = trace(
        vgg.forward,
        vgg.param_specs(),
        _sds(1, 224, 224, 3),
        name="vgg16",
        fold_pool=(pool_mode == "absorbed"),
    )
    names: list[str] = []
    for lname, _n_in, _n_out, _hw, pooled in VGG16_CONV_PLAN:
        names.append(lname)
        if pooled and pool_mode == "separate":
            names.append(f"pool{lname[4]}")
    n_feature = len(names)
    names += ["fc6", "fc7", "fc8"]
    net = to_chain(rename_nodes(g, names), "vgg16")
    if not include_fc:
        net = NetworkIR("vgg16", net.layers[:n_feature])
    return net


def resnet18_graph(*, input_hw: int = 224) -> GraphIR:
    """ResNet-18 traced from :mod:`repro.models.resnet` — the skip adds come
    out as real join nodes with two incoming edges."""
    from ..models import resnet

    g = trace(
        resnet.forward,
        resnet.param_specs(),
        _sds(1, input_hw, input_hw, 3),
        name="resnet18",
    )
    names = ["conv1", "pool1"]
    c_in = 64
    for stage, n_blocks, c_out, stride0 in RESNET18_STAGE_PLAN:
        for b in range(n_blocks):
            stride = stride0 if b == 0 else 1
            cin_blk = c_in if b == 0 else c_out
            tag = f"s{stage}b{b}"
            names += [f"{tag}.conv_a", f"{tag}.conv_b"]
            if stride != 1 or cin_blk != c_out:
                names.append(f"{tag}.downsample")
            names.append(f"{tag}.add")
        c_in = c_out
    names += ["avgpool", "fc"]
    return rename_nodes(g, names)


def mobilenet_graph(
    *, input_hw: int = 112, plan: tuple | None = None
) -> GraphIR:
    """MobileNet-style inverted-residual stack traced from
    :mod:`repro.models.mobilenet` — depthwise convs carry ``groups`` and
    stride-1 blocks contribute skip joins."""
    from ..models import mobilenet

    plan = mobilenet.MOBILENET_PLAN if plan is None else plan
    g = trace(
        lambda p, x: mobilenet.forward(p, x, plan=plan),
        mobilenet.param_specs(plan=plan),
        _sds(1, input_hw, input_hw, 3),
        name="mobilenet",
    )
    names = ["stem"]
    for i, (c_in, c_out, stride, expand) in enumerate(plan):
        if expand != 1:
            names.append(f"b{i}.expand")
        names += [f"b{i}.dw", f"b{i}.project"]
        if stride == 1 and c_in == c_out:
            names.append(f"b{i}.add")
    return rename_nodes(g, names)


def mlp_block_graph(
    *,
    d_model: int = 256,
    d_ff: int = 1024,
    seq_len: int = 128,
    act: str = "swiglu",
    name: str = "mlp",
) -> GraphIR:
    """One transformer MLP block traced from
    :func:`repro.models.layers.mlp_block` — gated activations (swiglu/geglu)
    fan the input out to two projections and join them in an elementwise
    product, a topology the chain IR could not express."""
    from ..models import layers as L

    params = {"w1": _sds(d_model, d_ff), "w2": _sds(d_ff, d_model)}
    gated = act in L.GATED_ACTS
    if gated:
        params["w3"] = _sds(d_model, d_ff)
    g = trace(
        lambda p, x: L.mlp_block(p, x, act),
        params,
        _sds(seq_len, d_model),
        name=name,
    )
    names = (
        [f"{name}.w1", f"{name}.w3", f"{name}.gate", f"{name}.w2"]
        if gated
        else [f"{name}.w1", f"{name}.w2"]
    )
    return rename_nodes(g, names)


# ---------------------------------------------------------------------------
# Config-zoo builders: trace the real production-shape model blocks
# ---------------------------------------------------------------------------


def _zoo_seq_len(cfg, seq_len: int) -> int:
    """Clamp/validate a trace sequence length against the config's MoE
    group-limited routing (tokens must tile into routing groups)."""
    if cfg.n_experts > 1:
        sg = min(cfg.moe_group_size, seq_len)
        if seq_len % sg:
            raise UnsupportedOpError(
                f"{cfg.name}: seq_len {seq_len} does not tile into MoE "
                f"routing groups of {sg}"
            )
    return seq_len


def transformer_graph(cfg, *, seq_len: int = 512,
                      n_sublayers: int | None = None,
                      name: str | None = None,
                      batch_groups: bool = False) -> GraphIR:
    """One superblock (``cfg.pattern_period`` sublayers) of the config's
    decoder trunk, traced from the real :mod:`repro.models.transformer`
    forward pass via :func:`~repro.models.transformer.block_forward`.

    Attention sublayers lower to the actmul pair (QK^T -> folded softmax ->
    PV) with the O(S^2) score matrix as an explicit edge; mamba sublayers
    contribute a recurrent ``scan`` node carrying ``d_inner x d_state``
    ``state_words``; MoE sublayers expand into router + E expert branches +
    combine.  ``n_sublayers`` overrides the traced depth (default: one full
    pattern period, so jamba's 1:7 attn:mamba interleave and llama4's
    alternating dense/MoE both appear once).  ``batch_groups`` is
    :func:`trace`'s: the attention and routing products at the MACs of one
    product per head or routing group.

    The period is traced once per distinct (mixer, is_moe) sublayer kind,
    a one-sublayer ``block_forward`` each, and lowered sublayer by
    sublayer in the period's order, each sublayer's residual input the
    previous one's output: the graph is the one :func:`trace` gives of the
    whole period's ``block_forward``, node for node and edge for edge.
    Runs in a ``frontend.trace`` span (``work``: the node count) with one
    ``frontend.jaxpr`` child per kind (its param specs and jaxpr; ``work``:
    the sublayers of the period it stands for)."""
    from ..configs.base import RunConfig
    from ..models import transformer as T

    count = cfg.pattern_period if n_sublayers is None else n_sublayers
    kinds = cfg.sublayer_kinds(0, count)
    seq_len = _zoo_seq_len(cfg, seq_len)
    name = name or f"{cfg.name}.block"
    x = _sds(1, seq_len, cfg.d_model)
    rc = RunConfig()
    with spans.span("frontend.trace") as sp:
        jaxprs = {}
        for kind in dict.fromkeys(kinds):
            with spans.span("frontend.jaxpr", work=kinds.count(kind)):
                params = T.sublayer_param_specs(cfg, (kind,))
                jaxprs[kind] = _make_jaxpr(
                    lambda p, x, kind=kind: T.block_forward(
                        p, x, cfg, (kind,), rc=rc, attn_impl="reference"),
                    (params, x), name).jaxpr
        tr = _Tracer(name=name, fold_pool=False, batch_groups=batch_groups)
        g = _lower(tr, [(jaxprs[k], jaxprs[k].invars[-1:]) for k in kinds])
        sp.work = g.n_nodes
    return g


def mamba_graph(cfg, *, seq_len: int = 512, chunks: int = 1,
                name: str | None = None) -> GraphIR:
    """One mamba mixer block traced from
    :func:`repro.models.ssm.mamba_block` (chunk-recurrent selective scan).

    ``chunks > 1`` splits the sequence and threads the SSM cache between
    the calls — the ``(d_inner, d_state)`` carry hand-off and the
    ``(conv-1)``-token convolution tail both surface as real edges, so the
    fusion search sees the chunk boundary as a cut point."""
    import jax.numpy as jnp

    from ..models import ssm as SSM

    if "mamba" not in cfg.layer_pattern:
        raise UnsupportedOpError(f"{cfg.name}: no mamba sublayers in pattern")
    if chunks < 1 or seq_len % chunks:
        raise UnsupportedOpError(
            f"{cfg.name}: seq_len {seq_len} does not split into "
            f"{chunks} chunks"
        )
    params = SSM.mamba_param_specs(cfg)
    step = seq_len // chunks

    def fn(p, x):
        if chunks == 1:
            return SSM.mamba_block(p, x, cfg, impl="chunked", chunk=step)[0]
        cache = {
            "conv": jnp.zeros((1, cfg.ssm_conv - 1, cfg.d_inner), x.dtype),
            "h": jnp.zeros((1, cfg.d_inner, cfg.ssm_state), jnp.float32),
        }
        outs = []
        for i in range(chunks):
            xi = jax.lax.slice_in_dim(x, i * step, (i + 1) * step, axis=1)
            y, cache = SSM.mamba_block(p, xi, cfg, cache, impl="chunked",
                                       chunk=step)
            outs.append(y)
        return jnp.concatenate(outs, axis=1)

    return trace(
        fn, params, _sds(1, seq_len, cfg.d_model),
        name=name or f"{cfg.name}.mamba",
    )


def moe_block_graph(cfg, *, seq_len: int = 512,
                    name: str | None = None) -> GraphIR:
    """One MoE FFN traced from :func:`repro.models.moe.moe_block`: a router
    ``matmul``, a dispatch ``actmul`` whose routed one-hots descend from the
    router, ``E`` expert branches whose incoming edges carry the routed
    capacity words (``C = moe._capacity`` — ``capacity_factor``-scaled), and
    a combine ``actmul`` joining the branches against the router's combine
    weights (arctic's parallel dense-residual MLP appears alongside)."""
    from ..models import moe as MOE

    if cfg.n_experts <= 1:
        raise UnsupportedOpError(f"{cfg.name}: config has no MoE layers")
    seq_len = _zoo_seq_len(cfg, seq_len)
    params = MOE.moe_param_specs(cfg)
    return trace(
        lambda p, x: MOE.moe_block(p, x, cfg)[0],
        params,
        _sds(1, seq_len, cfg.d_model),
        name=name or f"{cfg.name}.moe",
    )
