"""Mixtral-8x7B: one decoder period (GQA attention + 8-expert top-2 SwiGLU
MoE) as the program traces it from its JAX transformer, and the checks the
reference makes of that trace against the published sizes."""
from __future__ import annotations


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


def reference_graph(model: dict, ref, program_graph, **_shape):
    """The reference evaluates the trace's layer geometry and edges; it
    recounts every cost itself (``trace_diffs`` holds the trace to the
    published sizes)."""
    return ref.plain_from_program(program_graph)


def trace_diffs(config: dict, program_graph, ref_graph, ref,
                seq_len: int = 512) -> int:
    """How far the trace departs from the published layer: the weight
    words of one decoder layer (norms aside), the expert and router
    shapes, and the traced prefill length."""
    m = config["model"]
    d, ff = m["hidden_size"], m["intermediate_size"]
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = d // heads
    experts = m["num_local_experts"]
    want_w = (d * heads * hd + 2 * d * kv * hd + heads * hd * d
              + d * experts + experts * 3 * d * ff)
    layers = ref_graph.layers
    weighted = [l for l in layers if l.kind in ref.WEIGHTED]
    got_w = sum(l.n_in // l.groups * l.kh * l.kw * l.n_out for l in weighted)
    n_expert = sum(1 for l in weighted if {l.n_in, l.n_out} == {d, ff})
    n_router = sum(1 for l in weighted if (l.n_in, l.n_out) == (d, experts))
    diffs = int(got_w != want_w) + abs(n_expert - 3 * experts) \
        + abs(n_router - 1)
    diffs += int(max(l.h_in for l in layers) < seq_len)
    return diffs + ref.graph_diffs(ref_graph,
                                   ref.plain_from_program(program_graph))
