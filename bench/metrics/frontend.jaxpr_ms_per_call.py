"""Mean time one trace of a model spends building the jaxprs of its
distinct sublayer kinds, param specs included (the program's
``frontend.jaxpr`` spans under each ``frontend.trace`` span that began in
the window); None where the program records no such span."""
import statistics

from program_spans import program_spans


def read(ctx):
    spans = program_spans()
    if spans is None:
        return None
    got = spans.per_call("frontend.trace", "frontend.jaxpr", *ctx.window)
    if not any(s for s, _ in got):
        return None
    return 1e3 * statistics.fmean(s for s, _ in got)
