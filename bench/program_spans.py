"""What the per-layer metrics in ``metrics/`` read of the program's own
stage spans (``repro.core.spans``): the ``fleet.*`` spans under each
``fleet.call`` span that began in the window.  A program that records no
spans gives nothing to read, and the readers then return None."""
from __future__ import annotations

import statistics

ROOT_SPAN = "fleet.call"


def program_spans():
    """The program's span recorder, or None where it has none."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    return spans


def stage_per_call(ctx, name: str) -> list | None:
    """(seconds, work) of the ``name`` spans of each ``fleet.call`` that
    began in the window; None when none began there."""
    spans = program_spans()
    if spans is None:
        return None
    return spans.per_call(ROOT_SPAN, name, *ctx.window) or None


def stage_ms_per_call(ctx, name: str) -> float | None:
    """Mean time (ms) a ``fleet.call`` spent in stage ``name``."""
    got = stage_per_call(ctx, name)
    return None if got is None else 1e3 * statistics.fmean(
        s for s, _ in got)
