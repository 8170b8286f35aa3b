"""Host time of one ``run_fleet`` call before the sweep: grouping resolution,
padding, SRAM prefilter, argument stacking and the float64 exactness checks
(the program's ``fleet.prepare`` span, mean per ``fleet.call``)."""
from program_spans import stage_ms_per_call


def read(ctx):
    return stage_ms_per_call(ctx, "fleet.prepare")
