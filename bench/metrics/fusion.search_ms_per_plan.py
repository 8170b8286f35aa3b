"""Mean time of one grouping search (the harness's ``bench.search`` span
around ``flow.groupings_batch(g, "search")``)."""
from readers import span_ms


def read(ctx):
    return span_ms(ctx, "bench.search")
