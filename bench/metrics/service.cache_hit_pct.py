"""Share of the planning service's responses answered from its plan
cache (``PlanResponse.from_cache``)."""


def read(ctx):
    rs = ctx.counters.get("responses")
    if not rs:
        return None
    return 100.0 * sum(bool(r.from_cache) for r in rs) / len(rs)
