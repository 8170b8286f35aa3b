"""Reduce a profiler trace (``.xplane.pb``) to the device's busy and idle
time, the device time of each program and operation, and the idle gaps
named by the harness span the host was in.

Read with ``jax.profiler.ProfileData``.  The window is the harness's own
``bench.window`` span, found on the host plane, so host spans and device
events are read on the trace's one clock.  On a TPU every chip is a plane
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per operation
run, and ``XLA Modules`` one per program run.  Busy time is averaged
over the chips a cell uses; program and operation times are summed over
them (chip-seconds).
"""
from __future__ import annotations

import pathlib
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name: str) -> str:
    """An event's name without its HLO text: the instruction's name (and a
    custom call's target), or a program's name without its id suffix."""
    if " = " in name:
        head = name.split(" = ", 1)[0].lstrip("%")
        t = _TARGET.search(name)
        return f"{head}:{t.group(1)}" if t else head
    return _SUFFIX.sub("", name)


def tpu_lines(plane_name: str, line_name: str) -> str | None:
    """'ops' / 'modules' for the lines a TPU plane times, else None."""
    if not _DEVICE_PLANE.match(plane_name):
        return None
    return {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line_name)


def find_xplane(trace_dir) -> pathlib.Path | None:
    files = sorted(pathlib.Path(trace_dir).glob("**/*.xplane.pb"))
    return files[-1] if files else None


def union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def innermost(spans, t):
    """Name of the innermost harness span open at time ``t``."""
    best = None
    for name, a, b in spans:
        if a <= t <= b and (best is None or a >= best[1]):
            best = (name, a)
    return best[0] if best else "outside"


def reduce_planes(planes, *, n_devices: int = 1, lines=tpu_lines,
                  top: int = 10) -> dict | None:
    """The reduction over ``planes`` (``ProfileData.planes``); None when
    the trace holds no window span or no device events."""
    spans = []
    dev = {}  # plane name -> {"ops": [...], "modules": [...]}
    for plane in planes:
        for line in plane.lines:
            kind = lines(plane.name, line.name)
            for e in line.events:
                a = float(e.start_ns)
                b = a + float(e.duration_ns)
                if kind is not None:
                    dev.setdefault(plane.name, {"ops": [], "modules": []})[
                        kind].append((e.name, a, b))
                elif plane.name.startswith("/host") and \
                        e.name.startswith(SPAN_PREFIX):
                    spans.append((e.name, a, b))
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if not win or not dev:
        return None
    _, w0, w1 = max(win, key=lambda s: s[2] - s[1])
    inner = [s for s in spans if s[0] != WINDOW_SPAN]
    names = sorted(dev, key=lambda n: int(re.sub(r"\D", "", n) or 0))
    names = names[:n_devices]
    busy, programs, ops, gaps = [], {}, {}, []
    for i, name in enumerate(names):
        d = dev[name]
        evs = d["ops"] or d["modules"]
        merged = union(clip([(a, b) for _, a, b in evs], w0, w1))
        busy.append(sum(b - a for a, b in merged))
        for key, table in (("modules", programs), ("ops", ops)):
            for n, a, b in d[key]:
                lo, hi = max(a, w0), min(b, w1)
                if hi > lo:
                    n = short_name(n)
                    table[n] = table.get(n, 0.0) + (hi - lo)
        if i == 0:
            edges = [w0] + [x for ab in merged for x in ab] + [w1]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps.append((b - a, innermost(inner, (a + b) / 2)))
    ns = 1e-9
    gaps.sort(reverse=True)
    return {
        "busy_s": sum(busy) / len(busy) * ns,
        "window_s": (w1 - w0) * ns,
        "programs": {k: v * ns for k, v in programs.items()},
        "ops": {k: v * ns for k, v in ops.items()},
        "breakdown": {
            "device_ops": [[k, v * ns] for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[n, g * ns] for g, n in gaps[:top]],
        },
    }


def reduce_trace(trace_dir, *, n_devices: int = 1,
                 lines=tpu_lines) -> dict | None:
    """:func:`reduce_planes` of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce_planes(ProfileData.from_file(str(path)).planes,
                         n_devices=n_devices, lines=lines)
