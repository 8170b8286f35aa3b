"""The fleet sweep program's share of its HBM floor: the least time of the
bytes any implementation must move (``roofline.sweep_bytes``: the inputs
once and the answer the call returns) at the chip's published HBM
bandwidth, over the sweep program's device time.  The sweep is bound by
emulated float64 arithmetic, which has no published peak, so the share
has no compute bound; it is a floor that no valid implementation can
exceed, whether it writes the candidate plane or reduces it on the
device."""
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
