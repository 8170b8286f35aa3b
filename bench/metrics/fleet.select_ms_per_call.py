"""Host time of one ``run_fleet`` call selecting each graph's point:
feasibility, min-energy argmin, tie order and Pareto front (the program's
``fleet.select`` span, mean per ``fleet.call``)."""
from program_spans import stage_ms_per_call


def read(ctx):
    return stage_ms_per_call(ctx, "fleet.select")
