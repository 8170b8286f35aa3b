"""95th percentile of how late the load generator submitted a request,
measured from its due time (a starved generator would read as a fast
server)."""
from harness import percentile


def read(ctx):
    late = ctx.counters.get("late_s")
    if not late:
        return None
    return 1e3 * percentile(late, 0.95)
