"""Share of the planning service's responses whose grouping came from a
rung of the deadline ladder below the exact search
(``PlanResponse.degraded``)."""


def read(ctx):
    rs = ctx.counters.get("responses")
    if not rs:
        return None
    return 100.0 * sum(bool(r.degraded) for r in rs) / len(rs)
