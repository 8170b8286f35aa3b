"""Number of sweep programs compiled inside the window: the program's
``fleet.compile`` spans (one per executable-cache miss) that began in it,
read where a ``fleet.call`` began in the window."""
from program_spans import ROOT_SPAN, program_spans


def read(ctx):
    spans = program_spans()
    if spans is None or not spans.records(ROOT_SPAN, *ctx.window):
        return None
    return len(spans.records("fleet.compile", *ctx.window))
