"""Mean time of one grouping search, ``fusion.optimal_cuts`` (the program's
own ``fusion.search`` spans that began in the window); None where the
program records no such span."""
import statistics

from program_spans import program_spans


def read(ctx):
    spans = program_spans()
    if spans is None:
        return None
    d = [r.seconds for r in spans.records("fusion.search", *ctx.window)]
    return 1e3 * statistics.fmean(d) if d else None
