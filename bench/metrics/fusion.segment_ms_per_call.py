"""Mean time one grouping search spends solving its segments or finding
them in the segment memo (the program's ``fusion.segment`` spans under each
``fusion.search`` span that began in the window); None where the program
records no such span."""
import statistics

from program_spans import program_spans


def read(ctx):
    spans = program_spans()
    if spans is None:
        return None
    got = spans.per_call("fusion.search", "fusion.segment", *ctx.window)
    if not any(s for s, _ in got):
        return None
    return 1e3 * statistics.fmean(s for s, _ in got)
