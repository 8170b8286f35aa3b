"""Rate of the device-to-host copy of what the sweep returns (the pruned
program's summary, or the raw plane): the bytes of the program's
``fleet.fetch`` spans over their time, summed over the ``fleet.call`` spans
that began in the window."""
from program_spans import stage_per_call


def read(ctx):
    got = stage_per_call(ctx, "fleet.fetch")
    if got is None:
        return None
    seconds = sum(s for s, _ in got)
    return sum(w for _, w in got) / seconds / 1e9 if seconds > 0 else None
