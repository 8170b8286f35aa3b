"""Sublayers of a traced period that one jaxpr stands for: the work (the
sublayers of its kind) of the program's ``frontend.jaxpr`` spans that began
in the window over their count; None where the program records no such
span."""
from program_spans import program_spans


def read(ctx):
    spans = program_spans()
    if spans is None:
        return None
    got = spans.records("frontend.jaxpr", *ctx.window)
    if not got:
        return None
    return sum(r.work or 0 for r in got) / len(got)
