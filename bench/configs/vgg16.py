"""VGG-16 (configuration D) feature extractor: the program's traced graph
and the plain reference's own layer table, both from ``vgg16.json``."""
from __future__ import annotations


def program_graph(model: dict, **_shape):
    """The graph as the program traces it from its JAX VGG-16."""
    from repro.core.ir import as_graph, vgg16_ir

    return as_graph(vgg16_ir(pool_mode=model["pool_mode"],
                             include_fc=model["include_fc"]))


def reference_graph(model: dict, ref, program_graph=None, **_shape):
    """Configuration D written out layer by layer: 3x3 SAME convolutions at
    stride 1, each stage closed by a 2x2 max-pool of stride 2.  Built from
    the published table alone; the program's trace is not read."""
    hw, c_in = model["input_hw"], model["input_channels"]
    k, p = model["conv_kernel"], model["pool_window"]
    layers = []
    for stage in model["conv_stages"]:
        for c_out in stage:
            layers.append(ref.Layer("conv", c_in, c_out, hw, hw, k, k))
            c_in = c_out
        layers.append(ref.Layer("pool", c_in, c_in, hw, hw, p, p, stride=p))
        hw //= p
    return ref.chain("vgg16", layers)


def trace_diffs(config: dict, program_graph, ref_graph, ref, **_shape) -> int:
    """Fields in which the program's trace differs from configuration D."""
    return ref.graph_diffs(ref_graph, ref.plain_from_program(program_graph))
