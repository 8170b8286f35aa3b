"""Mixtral-8x7B: one decoder period (GQA attention + 8-expert top-2 SwiGLU
MoE) as the program traces it from its JAX transformer, and the plain
reference's own layer and edge table of that period, written from the
published sizes."""
from __future__ import annotations

import math

import numpy as np


def program_config(model: dict):
    """The program's model config with the published sizes of ``model``."""
    import dataclasses

    from repro.configs import REGISTRY

    return dataclasses.replace(
        REGISTRY["mixtral-8x7b"],
        n_layers=model["num_hidden_layers"], d_model=model["hidden_size"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        head_dim=model["hidden_size"] // model["num_attention_heads"],
        d_ff=model["intermediate_size"], vocab_size=model["vocab_size"],
        n_experts=model["num_local_experts"],
        top_k=model["num_experts_per_tok"], ffn_act="swiglu")


def program_graph(model: dict, seq_len: int = 512):
    """One decoder period traced at prefill length ``seq_len``, batch 1."""
    from repro.core.frontend import transformer_graph

    return transformer_graph(program_config(model), seq_len=seq_len)


def reference_graph(config: dict, ref, seq_len: int = 512, **_shape):
    """One decoder period at prefill length ``seq_len``, batch 1, written
    node by node from ``config["model"]`` and ``config["routing"]``; the
    program's trace is not read.

    The cost model's notation: a node is (kind, n_in, n_out, h_in) with
    w_in 1; a matmul contracts n_in against a weight to n_out features at
    h_in rows; an activation product (``actmul``) folds its batch axes, the
    heads or the routing groups, into the contraction and the output, so
    one node prices them all; an elementwise op of one operand (norms,
    rotary, softmax, SiLU) folds into the node that produces its input, and
    one that joins two activations is a node of its own.  Keys and values
    reach the score and value products repeated to every query head.  An
    edge carries the words its consumer reads.
    """
    m, r = config["model"], config["routing"]
    S = seq_len
    d, ff = m["hidden_size"], m["intermediate_size"]
    H, KV = m["num_attention_heads"], m["num_key_value_heads"]
    hd = d // H
    E, k = m["num_local_experts"], m["num_experts_per_tok"]
    G = min(r["group_size"], S)  # tokens routed together
    n_groups = S // G
    C = math.ceil(k * G / E * r["capacity_factor"])  # slots an expert a group
    T = n_groups * C  # rows an expert computes
    L = ref.Layer
    layers = [
        L("matmul", d, H * hd, S, 1),  # 0 query projection
        L("matmul", d, KV * hd, S, 1),  # 1 key projection
        L("matmul", d, KV * hd, S, 1),  # 2 value projection
        L("actmul", H * hd, H * S, S, 1),  # 3 scores Q K^T, softmax folded
        L("actmul", H * S, H * S, hd, 1),  # 4 scores x V
        L("matmul", H * hd, d, S, 1),  # 5 output projection
        L("elementwise", d, d, S, 1, ext_in_words=S * d),  # 6 residual
        L("matmul", d, E, S, 1),  # 7 router
        L("actmul", S, n_groups * d, E * C, 1),  # 8 dispatch to the slots
    ]
    w1 = len(layers)  # E gate projections, then E up projections
    layers += [L("matmul", d, ff, T, 1)] * (2 * E)
    gate = len(layers)  # SiLU(gate) x up
    layers += [L("elementwise", ff, ff, T, 1)] * E
    w2 = len(layers)  # down projections
    layers += [L("matmul", ff, d, T, 1)] * E
    combine = len(layers)
    layers += [L("actmul", n_groups * E * C, n_groups * d, G, 1),
               L("elementwise", d, d, S, 1)]  # residual
    q_words = S * H * hd
    edges = [(0, 3, q_words), (1, 3, q_words), (2, 4, q_words),
             (3, 4, H * S * S), (4, 5, q_words), (5, 6, S * d),
             (6, 7, S * d), (6, 8, S * d), (6, combine + 1, S * d),
             (7, 8, S * E * C), (7, combine, S * E * C),
             (combine, combine + 1, S * d)]
    for e in range(E):
        edges += [(8, w1 + e, T * d), (8, w1 + E + e, T * d),
                  (w1 + e, gate + e, T * ff), (w1 + E + e, gate + e, T * ff),
                  (gate + e, w2 + e, T * ff), (w2 + e, combine, T * d)]
    return ref.PlainGraph(f"mixtral-8x7b.s{S}", tuple(layers),
                          np.asarray(sorted(edges), np.int64))


def trace_diffs(config: dict, program_graph, ref_graph, ref, **_shape) -> int:
    """Fields in which the program's trace differs from the reference's
    table: every node's geometry and every edge's endpoints and words."""
    return ref.graph_diffs(ref_graph, ref.plain_from_program(program_graph))
