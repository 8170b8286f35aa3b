"""Time of one ``run_fleet`` call copying what the sweep returns (the
pruned program's summary, or the raw plane) from the device to the host
(the program's ``fleet.fetch`` spans, mean per ``fleet.call``)."""
from program_spans import stage_ms_per_call


def read(ctx):
    return stage_ms_per_call(ctx, "fleet.fetch")
