"""Candidate rows one ``run_fleet`` call's host selection read: the
``work`` of the program's ``fleet.select`` spans, mean per ``fleet.call``.
Where the sweep is pruned on the device that is the survivor rows; on the
full path it is every (hardware point x grouping) row.  A program whose
select spans carry no ``work`` gives nothing to read."""
import statistics

from program_spans import program_spans, stage_per_call


def read(ctx):
    got = stage_per_call(ctx, "fleet.select")
    if got is None:
        return None
    selects = program_spans().records("fleet.select", ctx.window[0])
    if any(r.work is None for r in selects):
        return None
    return statistics.fmean(w for _, w in got)
