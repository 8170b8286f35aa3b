"""Host time of one ``run_fleet`` call outside compile and the timed
execute: prefilter, padding, composition of energy, the finite guard and
the selection (wall time of the call, less the program's own
``compile_seconds`` and ``sweep_seconds``)."""
import statistics


def read(ctx):
    calls = ctx.counters.get("calls")
    if not calls or "shape" not in ctx.counters:
        return None
    return 1e3 * statistics.fmean(
        c["wall"] - c["compile_s"] - c["sweep_s"] for c in calls)
