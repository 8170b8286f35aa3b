"""Host time of one ``run_fleet`` call composing energy from the raw plane,
Eq. (3) in numpy (the program's ``fleet.compose`` span, mean per
``fleet.call``)."""
from program_spans import stage_ms_per_call


def read(ctx):
    return stage_ms_per_call(ctx, "fleet.compose")
