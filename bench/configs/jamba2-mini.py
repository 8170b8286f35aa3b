"""AI21-Jamba2-Mini: one period of 8 sublayers (7 Mamba-1 mixers and one GQA
attention mixer; 4 dense SwiGLU MLPs and 4 16-expert top-2 SwiGLU MoE FFNs)
as the program traces it from its JAX transformer, and the plain reference's
own layer and edge table of that period, written from the published sizes.

Departures of the table from the published equations, all shared with the
program's cost model:

* an elementwise step with one operand folds into the node that produces
  its input: the Mamba block's depthwise causal convolution (d_conv 4) and
  its SiLU into the input projection, exp(dt x A) into the dt projection,
  the RMSNorms and Jamba's norms on dt, B and C into their producers;
* the attention products, and the MoE dispatch and combine, fold their
  batch axes (the heads, the routing groups) into contraction and output
  as ``groups``: the MACs are those of one product per head or group, but
  the second operand's words count every group's contraction against
  every group's output (the cost model's ``in_words`` of an ``actmul``),
  H times the keys or values an attention product reads;
* Jamba's attention has no positional encoding; the program's rotary step
  is elementwise and folds, so the table has none either;
* the MoE router's load-balancing loss is no dataflow of the block's
  output and has no node;
* tokens are routed in groups, at a capacity the published config does not
  give (``routing`` in the JSON file).
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


def program_config(model: dict):
    """The program's registered ``jamba2-mini`` config with the published
    sizes of ``model``."""
    from repro.configs import REGISTRY

    period, offset = model["attn_layer_period"], model["attn_layer_offset"]
    return dataclasses.replace(
        REGISTRY["jamba2-mini"],
        n_layers=model["num_hidden_layers"], d_model=model["hidden_size"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        head_dim=model["hidden_size"] // model["num_attention_heads"],
        d_ff=model["intermediate_size"], vocab_size=model["vocab_size"],
        layer_pattern=tuple("attn" if i == offset else "mamba"
                            for i in range(period)),
        n_experts=model["num_experts"], top_k=model["num_experts_per_tok"],
        moe_every=model["expert_layer_period"],
        moe_offset=model["expert_layer_offset"],
        ssm_state=model["mamba_d_state"], ssm_conv=model["mamba_d_conv"],
        ssm_expand=model["mamba_expand"], ssm_dt_rank=model["mamba_dt_rank"],
        ffn_act="swiglu", rmsnorm_eps=model["rms_norm_eps"])


def program_graph(model: dict, seq_len: int = 4096):
    """One period traced at prefill length ``seq_len``, batch 1, its
    products of two activations priced per head or routing group."""
    from repro.core.frontend import transformer_graph

    return transformer_graph(program_config(model), seq_len=seq_len,
                             batch_groups=True)


def reference_graph(config: dict, ref, seq_len: int = 4096, **_shape):
    """One period at prefill length ``seq_len``, batch 1, written node by
    node from ``config["model"]``, ``config["routing"]`` and
    ``config["scan"]``; the program's trace is not read.

    The cost model's notation, as in ``mixtral-8x7b.py``: a node is (kind,
    n_in, n_out, h_in, w_in); a matmul contracts n_in to n_out features at
    h_in rows; an ``actmul`` folds its batch axes into contraction and
    output, as that many ``groups``; an op that joins two activations is an
    ``elementwise`` node; a ``scan`` carries ``state_words`` of SRAM in
    every grouping.  An edge
    carries the words its consumer reads.  Sublayer i has the attention
    mixer iff i % period == offset and the MoE FFN iff i % expert period ==
    expert offset; the first sublayer's input is the graph input, read
    from DRAM.
    """
    m, r = config["model"], config["routing"]
    S = seq_len
    d, ff = m["hidden_size"], m["intermediate_size"]
    H, KV = m["num_attention_heads"], m["num_key_value_heads"]
    hd = d // H
    E, k = m["num_experts"], m["num_experts_per_tok"]
    di = m["mamba_expand"] * d  # Mamba inner width
    N, R = m["mamba_d_state"], m["mamba_dt_rank"]
    chunk = config["scan"]["chunk"]
    G = min(r["group_size"], S)  # tokens routed together
    n_groups = S // G
    C = math.ceil(k * G / E * r["capacity_factor"])  # slots an expert a group
    T = n_groups * C  # rows an expert computes
    L = ref.Layer
    layers, edges = [], []

    def node(layer, *ins):
        """Append ``layer`` fed by (producer, words) pairs; a producer of
        None is the graph input, which the node reads from DRAM."""
        i = len(layers)
        ext = sum(w for p, w in ins if p is None)
        if ext and len(ins) == 1:  # a source reads its input frame anyway
            ext = 0
        layers.append(dataclasses.replace(layer, ext_in_words=ext))
        edges.extend((p, i, w) for p, w in ins if p is not None)
        return i

    def mamba(x):
        a = node(L("matmul", d, 2 * di, S, 1), (x, S * d))  # in_proj, conv
        b = node(L("matmul", di, R + 2 * N, S, 1), (a, S * di))  # x_proj
        c = node(L("matmul", R, di, S, 1), (b, S * R))  # dt_proj, exp(dt A)
        db = node(L("elementwise", N, N, S, di), (b, S * N), (c, S * di))
        dbx = node(L("elementwise", N, N, S, di), (a, S * di),
                   (db, S * di * N))
        y = node(L("scan", di, di, S // chunk, chunk, state_words=di * N),
                 (b, S * N), (c, S * di * N), (dbx, S * di * N))
        y = node(L("elementwise", di, di, S, 1), (a, S * di), (y, S * di))
        y = node(L("elementwise", di, di, S, 1), (a, S * di), (y, S * di))
        return node(L("matmul", di, d, S, 1), (y, S * di))  # out_proj

    def attention(x):
        q_words = S * H * hd  # keys and values repeated to every query head
        q = node(L("matmul", d, H * hd, S, 1), (x, S * d))
        kk = node(L("matmul", d, KV * hd, S, 1), (x, S * d))
        v = node(L("matmul", d, KV * hd, S, 1), (x, S * d))
        s = node(L("actmul", H * hd, H * S, S, 1, groups=H), (q, q_words),
                 (kk, q_words))  # Q K^T, softmax folded
        o = node(L("actmul", H * S, H * S, hd, 1, groups=H), (v, q_words),
                 (s, H * S * S))
        return node(L("matmul", H * hd, d, S, 1), (o, q_words))

    def dense(x):
        w1 = node(L("matmul", d, ff, S, 1), (x, S * d))
        w3 = node(L("matmul", d, ff, S, 1), (x, S * d))
        g = node(L("elementwise", ff, ff, S, 1), (w1, S * ff), (w3, S * ff))
        return node(L("matmul", ff, d, S, 1), (g, S * ff))

    def moe(x):
        router = node(L("matmul", d, E, S, 1), (x, S * d))
        disp = node(L("actmul", S, n_groups * d, E * C, 1, groups=n_groups),
                    (x, S * d), (router, S * E * C))
        w1 = [node(L("matmul", d, ff, T, 1), (disp, T * d)) for _ in range(E)]
        w3 = [node(L("matmul", d, ff, T, 1), (disp, T * d)) for _ in range(E)]
        g = [node(L("elementwise", ff, ff, T, 1), (w1[e], T * ff),
                  (w3[e], T * ff)) for e in range(E)]
        w2 = [node(L("matmul", ff, d, T, 1), (g[e], T * ff))
              for e in range(E)]
        return node(L("actmul", n_groups * E * C, n_groups * d, G, 1,
                      groups=n_groups),
                    (router, S * E * C), *((w, T * d) for w in w2))

    x = None  # the graph input
    for i in range(m["num_hidden_layers"]):
        mixer = attention if i % m["attn_layer_period"] == \
            m["attn_layer_offset"] else mamba
        x = node(L("elementwise", d, d, S, 1), (x, S * d), (mixer(x), S * d))
        ffn = moe if i % m["expert_layer_period"] == m["expert_layer_offset"] \
            else dense
        x = node(L("elementwise", d, d, S, 1), (x, S * d), (ffn(x), S * d))
    return ref.PlainGraph(f"jamba2-mini.s{S}", tuple(layers),
                          np.asarray(sorted(edges), np.int64))


def trace_diffs(config: dict, program_graph, ref_graph, ref, **_shape) -> int:
    """Fields in which the program's trace differs from the reference's
    table: every node's geometry and every edge's endpoints and words."""
    return ref.graph_diffs(ref_graph, ref.plain_from_program(program_graph))
