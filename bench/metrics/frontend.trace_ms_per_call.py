"""Mean time of one trace of a model into the graph IR, dead dataflow
removed (the program's own ``frontend.trace`` spans that began in the
window); None where the program records no such span."""
import statistics

from program_spans import program_spans


def read(ctx):
    spans = program_spans()
    if spans is None:
        return None
    d = [r.seconds for r in spans.records("frontend.trace", *ctx.window)]
    return 1e3 * statistics.fmean(d) if d else None
