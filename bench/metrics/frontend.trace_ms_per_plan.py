"""Mean time of one trace of the model into the graph IR (the harness's
``bench.trace`` span around the frontend)."""
from readers import span_ms


def read(ctx):
    return span_ms(ctx, "bench.trace")
