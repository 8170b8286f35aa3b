"""The span recorder (:mod:`repro.core.spans`) and the stage spans of
``run_fleet``: nesting, threads, the bounded buffer, spans that raise, the
stages of one call, the timing fields read from them, the chunked path, and
the spans' place on the profiler's host plane."""
import math
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import flow, spans
from repro.core import metrics as M
from repro.core.arch import Constraints, config_space_grid
from repro.core.errors import RetryPolicy
from repro.core.ir import as_graph, residual_block_ir

RELAXED = Constraints(*[float("inf")] * 4)
GRID = config_space_grid(
    f1s=(2, 4), f2s=(2, 4), f3s=(2, 4), f4s=(2, 4),
    bus_widths=(2, 4), sram_splits=("unified",),
)  # 48 hardware points
STAGES = ("fleet.prepare", "fleet.execute", "fleet.fetch", "fleet.compose",
          "fleet.guard", "fleet.select")


def _fleet(**kw):
    g = as_graph(residual_block_ir())
    batch = np.stack([np.ones(g.n_edges, bool), np.zeros(g.n_edges, bool)])
    return flow.run_fleet([g], config_space=GRID, constraints=RELAXED,
                          groupings=[batch], **kw)


def _calls(t0):
    """The fleet.call records that began after ``t0``, and their stages."""
    out = []
    for root in spans.records("fleet.call", t0):
        kids = [r for r in spans.records(t_from=t0)
                if r.call_id == root.call_id and r is not root]
        out.append((root, kids))
    return out


def _names(kids):
    return sorted(r.name for r in kids)


def test_nested_spans_self_time():
    with spans.span("t.outer") as outer:
        time.sleep(0.01)
        with spans.span("t.inner") as inner:
            assert spans.current() is inner
            time.sleep(0.02)
        with spans.span("t.inner") as inner2:
            pass
        assert spans.current() is outer
    o, i, i2 = outer.record, inner.record, inner2.record
    assert outer.children == [i, i2]
    assert i.parent_id == i2.parent_id == o.span_id
    assert i.call_id == i2.call_id == o.call_id == o.span_id
    assert o.parent_id is None
    assert spans.self_s(o) == pytest.approx(
        o.seconds - i.seconds - i2.seconds, abs=1e-12)
    assert 0.01 <= spans.self_s(o) < o.seconds - 0.02
    assert spans.self_s(i) == i.seconds  # a leaf
    assert spans.per_call("t.outer", "t.inner", o.t0, o.t0) == [
        (i.seconds + i2.seconds, 0.0)]


def test_threads_never_parent_each_other():
    got = {}
    started, release = threading.Event(), threading.Event()

    def worker():
        with spans.span("t.worker") as w:
            started.set()
            release.wait(5)
            with spans.span("t.worker_child") as c:
                pass
        got["w"], got["c"] = w.record, c.record

    with spans.span("t.main") as m:
        th = threading.Thread(target=worker)
        th.start()
        assert started.wait(5)
        with spans.span("t.main_child") as mc:
            release.set()
            th.join(5)
    assert not th.is_alive()
    assert got["w"].parent_id is None  # a root of its own thread
    assert got["w"].call_id != m.record.call_id
    assert got["c"].parent_id == got["w"].span_id
    assert mc.record.parent_id == m.record.span_id


def test_bounded_buffer_drops_oldest(monkeypatch):
    import collections

    monkeypatch.setattr(spans, "_BUFFER", collections.deque(maxlen=4))
    for k in range(6):
        with spans.span(f"t.b{k}"):
            pass
    assert [r.name for r in spans.records()] == ["t.b2", "t.b3", "t.b4",
                                                 "t.b5"]


def test_span_is_recorded_when_its_body_raises():
    with pytest.raises(ValueError):
        with spans.span("t.outer") as outer:
            with spans.span("t.raises", work=3) as s:
                raise ValueError("boom")
    assert s.record is not None and s.record.work == 3
    assert s.record.parent_id == outer.record.span_id
    assert s.record in spans.records("t.raises")
    # the thread's stack is unwound: the next span is a root again
    with spans.span("t.after") as after:
        pass
    assert after.record.parent_id is None


def test_run_fleet_records_one_call_with_each_stage():
    flow.clear_sweep_cache()
    t0 = time.perf_counter()
    fl = _fleet()
    fl2 = _fleet()
    (root, kids), (root2, kids2) = _calls(t0)
    assert _names(kids) == sorted(STAGES + ("fleet.compile",))
    assert _names(kids2) == sorted(STAGES)  # an executable-cache hit
    assert all(r.parent_id == root.span_id for r in kids)
    fetch = next(r for r in kids if r.name == "fleet.fetch")
    # the pruned program's summary, not the raw (1, 48, 4, 5) f64 plane:
    # four int64 counts and K survivor rows of (h, c, sure, 5 f64 words)
    G, K = 1, M.PRUNE_ROWS
    assert fetch.work == G * (4 * 8 + K * (4 + 4 + 1 + 5 * 8))
    by = {r.name: r for r in kids}
    assert fl.compile_seconds == by["fleet.compile"].seconds
    assert fl.sweep_seconds == (by["fleet.execute"].seconds
                                + by["fleet.fetch"].seconds)
    by2 = {r.name: r for r in kids2}
    assert fl2.compile_seconds == 0.0
    assert fl2.sweep_seconds == (by2["fleet.execute"].seconds
                                 + by2["fleet.fetch"].seconds)
    # the stages follow each other and lie inside the call
    ordered = sorted(kids, key=lambda r: r.t0)
    assert [r.name for r in ordered] == [
        "fleet.prepare", "fleet.compile", "fleet.execute", "fleet.fetch",
        "fleet.compose", "fleet.guard", "fleet.select"]
    for a, b in zip(ordered, ordered[1:]):
        assert a.t1 <= b.t0
    assert root.t0 <= ordered[0].t0 and ordered[-1].t1 <= root.t1


def test_run_flow_emits_the_sweep_stages():
    flow.clear_sweep_cache()
    t0 = time.perf_counter()
    res = flow.run_flow(residual_block_ir(), config_space=GRID,
                        constraints=RELAXED)
    [(root, kids)] = _calls(t0)  # run_flow is a one-graph run_fleet
    assert _names(kids) == sorted(STAGES + ("fleet.compile",))
    assert all(r.parent_id == root.span_id for r in kids)
    by = {r.name: r for r in kids}
    assert res.compile_seconds == by["fleet.compile"].seconds
    assert res.sweep_seconds == (by["fleet.execute"].seconds
                                 + by["fleet.fetch"].seconds)


def test_chunked_path_records_execute_and_fetch_per_chunk(monkeypatch):
    from repro.runtime import fault_tolerance

    seen = []

    class Recording(fault_tolerance.StragglerDetector):
        def observe(self, dt):
            seen.append(dt)
            super().observe(dt)

    monkeypatch.setattr(fault_tolerance, "StragglerDetector", Recording)
    t0 = time.perf_counter()
    fl = _fleet(hw_chunk=8)
    [(root, kids)] = _calls(t0)
    n = -(-len(GRID) // 8)
    ex = [r for r in kids if r.name == "fleet.execute"]
    fe = [r for r in kids if r.name == "fleet.fetch"]
    assert len(ex) == len(fe) == n == fl.chunks_computed
    assert all(r.parent_id == root.span_id for r in kids)
    # the straggler detector's chunk wall time: execute + fetch
    assert seen == [a.seconds + b.seconds for a, b in zip(ex, fe)]
    assert fl.sweep_seconds == pytest.approx(sum(seen), rel=1e-12)
    assert sum(r.work for r in fe) == math.prod(
        (1, len(GRID), flow.CUT_BUCKET_FLOOR, 5)) * 8


def test_straggler_wall_time_includes_a_retried_attempt(monkeypatch):
    from repro.runtime import fault_tolerance

    seen = []

    class Recording(fault_tolerance.StragglerDetector):
        def observe(self, dt):
            seen.append(dt)
            super().observe(dt)

    monkeypatch.setattr(fault_tolerance, "StragglerDetector", Recording)
    _fleet(hw_chunk=16)  # warm: no compile in the measured call
    real = jax.block_until_ready
    failed = []

    def flaky(x):
        if not failed:
            failed.append(1)
            raise RuntimeError("transient")
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", flaky)
    seen.clear()
    t0 = time.perf_counter()
    fl = _fleet(hw_chunk=16, retry_policy=RetryPolicy(backoff_seconds=0.0))
    [(root, kids)] = _calls(t0)
    ex = [r for r in kids if r.name == "fleet.execute"]
    fe = [r for r in kids if r.name == "fleet.fetch"]
    n = -(-len(GRID) // 16)
    assert len(ex) == n + 1 and len(fe) == n  # chunk 0 ran execute twice
    assert seen[0] == ex[0].seconds + ex[1].seconds + fe[0].seconds
    assert seen[1:] == [a.seconds + b.seconds for a, b in zip(ex[2:], fe[1:])]
    # sweep_seconds counts the attempts that answered, as before
    assert fl.sweep_seconds == pytest.approx(
        sum(a.seconds + b.seconds for a, b in zip(ex[1:], fe)), rel=1e-12)


def test_fleet_spans_sit_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData

    _fleet()  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.enclosing"):
            _fleet()
    finally:
        jax.profiler.stop_trace()
    [path] = tmp_path.glob("**/*.xplane.pb")
    events = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "test.enclosing" or e.name.startswith("fleet."):
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    assert set(events) == {"test.enclosing", "fleet.call"} | set(STAGES)
    [(a, b)] = events["test.enclosing"]
    for name, ivs in events.items():
        for s, t in ivs:
            assert a <= s and t <= b, name
