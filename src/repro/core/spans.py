"""Host spans of the evaluator's stages, kept in memory and on the profiler.

``with span("fleet.fetch") as s:`` times its body on ``time.perf_counter``
and appends one :class:`Record` to a bounded in-memory buffer when the
body ends, whether it returned or raised.  The body may set ``s.work`` (the
count done at that boundary: bytes, candidates); after the ``with`` block
``s.record`` holds the closed record.  Each span is also a
``jax.profiler.TraceAnnotation``, so while a profiler session runs it lands
on the profiler's host plane, on the same clock as the device's events.

The parent of a span is the innermost span open in the same thread, so
spans of two threads never parent each other.  A span with no parent is a
root and starts a call: it and every descendant carry the root's
``span_id`` as their ``call_id``.  Recording is always on; it costs a few
microseconds a span.  The buffer keeps the newest ``MAX_RECORDS``.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import math
import threading
import time
from typing import NamedTuple

import jax

MAX_RECORDS = 1 << 16


class Record(NamedTuple):
    """One closed span, on ``time.perf_counter``'s clock (seconds)."""

    name: str
    t0: float
    t1: float
    span_id: int
    parent_id: int | None
    call_id: int
    work: float | None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Span:
    """An open span: the body may set ``work``; ``record`` is set on close,
    and ``children`` holds the records of its closed child spans."""

    __slots__ = ("name", "span_id", "parent_id", "call_id", "work", "record",
                 "children")

    def __init__(self, name, span_id, parent, work):
        self.name = name
        self.span_id = span_id
        self.parent_id = None if parent is None else parent.span_id
        self.call_id = span_id if parent is None else parent.call_id
        self.work = work
        self.record: Record | None = None
        self.children: list[Record] = []


_BUFFER: collections.deque = collections.deque(maxlen=MAX_RECORDS)
_IDS = itertools.count(1)
_OPEN = threading.local()  # .stack: the thread's open spans, innermost last


def _stack() -> list:
    stack = getattr(_OPEN, "stack", None)
    if stack is None:
        stack = _OPEN.stack = []
    return stack


def current() -> Span | None:
    """The innermost span open in this thread, if any."""
    stack = _stack()
    return stack[-1] if stack else None


@contextlib.contextmanager
def span(name: str, work: float | None = None):
    """Record the body as span ``name`` (also usable as a decorator)."""
    stack = _stack()
    s = Span(name, next(_IDS), stack[-1] if stack else None, work)
    stack.append(s)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield s
    finally:
        t1 = time.perf_counter()
        stack.pop()
        s.record = Record(name, t0, t1, s.span_id, s.parent_id, s.call_id,
                          s.work)
        if stack:
            stack[-1].children.append(s.record)
        _BUFFER.append(s.record)


def records(name: str | None = None, t_from: float = -math.inf,
            t_to: float = math.inf) -> list[Record]:
    """Buffered records (of ``name``, if given) that began in
    ``[t_from, t_to]``, in the order they closed."""
    return [r for r in tuple(_BUFFER)
            if (name is None or r.name == name) and t_from <= r.t0 <= t_to]


def per_call(root: str, name: str, t_from: float = -math.inf,
             t_to: float = math.inf) -> list[tuple[float, float]]:
    """For each ``root`` span that began in ``[t_from, t_to]``, in start
    order: (seconds, work) summed over its ``name`` descendants, (0, 0)
    where it has none.  Work that was not given counts 0."""
    snap = tuple(_BUFFER)
    by_id = {r.span_id: r for r in snap}
    roots = sorted((r for r in snap
                    if r.name == root and t_from <= r.t0 <= t_to),
                   key=lambda r: r.t0)
    sums = {r.span_id: [0.0, 0.0] for r in roots}
    for r in snap:
        if r.name != name:
            continue
        up = by_id.get(r.parent_id)
        while up is not None and up.name != root:
            up = by_id.get(up.parent_id)
        if up is not None and up.span_id in sums:
            acc = sums[up.span_id]
            acc[0] += r.seconds
            acc[1] += r.work or 0
    return [tuple(sums[r.span_id]) for r in roots]


def self_s(record: Record) -> float:
    """``record``'s duration less what its child spans cover."""
    return record.seconds - sum(r.seconds for r in tuple(_BUFFER)
                                if r.parent_id == record.span_id)
