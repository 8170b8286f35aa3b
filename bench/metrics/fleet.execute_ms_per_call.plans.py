"""Time of a plan's ``run_fleet`` call from starting the sweep program until
the device is done with it: input transfer, dispatch and kernel (the
program's ``fleet.execute`` spans, mean per ``fleet.call``), in a cell of
the plans kind, where each plan makes one call."""
from program_spans import stage_ms_per_call


def read(ctx):
    return stage_ms_per_call(ctx, "fleet.execute")
