"""Host time of one ``run_fleet`` call in the finite guard: the poison mask
over the raw plane and the quarantine of poisoned cells (the program's
``fleet.guard`` span, mean per ``fleet.call``)."""
from program_spans import stage_ms_per_call


def read(ctx):
    return stage_ms_per_call(ctx, "fleet.guard")
