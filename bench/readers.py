"""Arithmetic the per-layer metric readers in ``metrics/`` share."""
from __future__ import annotations

import statistics

# The fleet sweep's program, as its name appears in the device trace.
SWEEP_PROGRAM = "evaluate_fleet_graph"


def sweep_device_s(ctx) -> float | None:
    """Chip-seconds the sweep program ran in the traced window."""
    if ctx.trace is None:
        return None
    s = sum(v for k, v in ctx.trace["programs"].items()
            if SWEEP_PROGRAM in k)
    return s if s > 0 else None


def idle_pct(ctx) -> float | None:
    """Share of the traced window in which no operation ran on the
    device, averaged over the cell's chips."""
    t = ctx.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def span_ms(ctx, name: str) -> float | None:
    """Mean duration (ms) of the harness's ``name`` spans in the window."""
    d = ctx.spans.durations(name, *ctx.window)
    return 1e3 * statistics.fmean(d) if d else None
