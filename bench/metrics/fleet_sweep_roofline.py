"""The fleet sweep kernel's share of its HBM roofline: the least time of
the bytes its shapes require (``roofline.sweep_bytes``) at the chip's
published HBM bandwidth, over the sweep program's device time.  Bound
named: HBM.  No published peak exists for emulated float64 arithmetic, so
the share has no compute bound."""
from readers import sweep_device_s
from roofline import sweep_bytes


def read(ctx):
    s = sweep_device_s(ctx)
    calls = ctx.counters.get("calls")
    shape = ctx.counters.get("shape")
    if s is None or not calls or shape is None or ctx.peaks is None:
        return None
    n_nodes, n_edges, n_hw, n_cuts = shape
    total = len(calls) * sweep_bytes(n_nodes, n_edges, n_hw, n_cuts)
    return 100.0 * (total / ctx.peaks["hbm_bytes_per_s"]) / s
