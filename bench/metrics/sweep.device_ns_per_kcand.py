"""Device time of the fleet sweep program per thousand candidates swept
(chip-nanoseconds, summed over the cell's chips)."""
from readers import sweep_device_s


def read(ctx):
    s = sweep_device_s(ctx)
    cand = ctx.counters.get("candidates")
    if s is None or not cand:
        return None
    return s * 1e9 / (cand / 1000.0)
