"""Host time of a plan's ``run_fleet`` call before the sweep: grouping
resolution, padding, argument stacking and the float64 exactness checks
(the program's ``fleet.prepare`` span, mean per ``fleet.call``), in a cell
of the plans kind, where each plan makes one call."""
from program_spans import stage_ms_per_call


def read(ctx):
    return stage_ms_per_call(ctx, "fleet.prepare")
