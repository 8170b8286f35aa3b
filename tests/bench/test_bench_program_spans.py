"""The per-layer metrics that read the program's stage spans: each gives a
number over CPU ``run_fleet`` calls in the window, leaves out calls made
before it, and gives nothing where no call began in it or where the program
records no spans."""
import sys
import time

import numpy as np
import pytest

import benchkit  # noqa: F401  (puts bench/ on sys.path)
from harness import Context, Spans, load_metric

from repro.core import flow, spans
from repro.core.arch import Constraints, config_space_grid
from repro.core.ir import as_graph, vgg16_ir

STAGES = ("prepare", "execute", "fetch", "compose", "guard", "select")
READERS = [f"fleet.{s}_ms_per_call" for s in STAGES] + [
    "fleet.fetch_gb_per_s", "fleet.compiles_in_window"]
GRID = config_space_grid(
    f1s=(2, 4), f2s=(2, 4), f3s=(2, 4), f4s=(2, 4),
    bus_widths=(2, 4), sram_splits=("unified",),
)


def _call():
    g = as_graph(vgg16_ir(pool_mode="separate"))
    cuts = np.random.default_rng(3).random((4096, g.n_edges)) < 0.5
    return flow.run_fleet([g], config_space=GRID,
                          constraints=Constraints(*[float("inf")] * 4),
                          groupings=[cuts])


def _ctx(window):
    return Context(spans=Spans(), counters={}, trace=None, window=window,
                   peaks=None)


@pytest.fixture(scope="module")
def window():
    """Two warm-up calls (the first compiles), then three in the window."""
    flow.clear_sweep_cache()
    _call()
    _call()
    t0 = time.perf_counter()
    results = [_call() for _ in range(3)]
    return (t0, time.perf_counter()), results


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_a_number(window, name):
    v = load_metric(name).read(_ctx(window[0]))
    assert isinstance(v, (int, float)) and v >= 0
    if name != "fleet.compiles_in_window":
        assert v > 0


def test_warmup_calls_are_left_out(window):
    (t0, t1), results = window
    ctx = _ctx((t0, t1))
    # the compile was paid in the warm-up
    assert load_metric("fleet.compiles_in_window").read(ctx) == 0
    whole = _ctx((-np.inf, t1))
    assert load_metric("fleet.compiles_in_window").read(whole) >= 1
    assert len(spans.per_call("fleet.call", "fleet.fetch", t0, t1)) == 3
    # execute + fetch is each call's sweep_seconds
    want = np.mean([fl.sweep_seconds for fl in results]) * 1e3
    got = (load_metric("fleet.execute_ms_per_call").read(ctx)
           + load_metric("fleet.fetch_ms_per_call").read(ctx))
    assert got == pytest.approx(want, rel=1e-9)
    # each call copies what the sweep returns: some bytes, and far fewer
    # than the (graph x point x grouping) plane of five float64 words
    plane = 1 * len(GRID) * 4096 * 5 * 8
    fetches = spans.per_call("fleet.call", "fleet.fetch", t0, t1)
    assert all(0 < w < plane / 100 for _, w in fetches)
    assert load_metric("fleet.fetch_gb_per_s").read(ctx) == pytest.approx(
        sum(w for _, w in fetches) / 1e9 / sum(s for s, _ in fetches))


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_nothing_without_a_call_in_the_window(window, name):
    t1 = window[0][1]
    assert load_metric(name).read(_ctx((t1 + 1e3, t1 + 2e3))) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_nothing_where_the_program_has_no_spans(
        window, monkeypatch, name):
    import repro.core

    monkeypatch.delattr(repro.core, "spans")
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert load_metric(name).read(_ctx(window[0])) is None


def test_stages_add_up_to_the_call(window):
    (t0, t1), _ = window
    for root in spans.records("fleet.call", t0, t1):
        kids = [r for r in spans.records(t_from=t0)
                if r.parent_id == root.span_id]
        assert {r.name for r in kids} == {f"fleet.{s}" for s in STAGES}
        covered = sum(r.seconds for r in kids)
        assert covered == pytest.approx(root.seconds, rel=0.05)
        # what no stage covers is the call's own glue
        assert spans.self_s(root) == pytest.approx(root.seconds - covered,
                                                   abs=1e-9)
