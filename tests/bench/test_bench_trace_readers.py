"""The per-layer metrics that read the trace's ``frontend.jaxpr`` spans:
the time a trace spends building its sublayer kinds' jaxprs, and the
sublayers one jaxpr stands for.  Each gives a number over CPU traces in
the window and in both plan cells at a CPU size, and nothing where no
trace began in the window, where a trace records no such span, or where
the program records no spans."""
import json
import sys
import time

import pytest

import benchkit
from harness import Context, Spans, load_metric, run_cell

from repro.configs import REGISTRY
from repro.core import frontend as F
from repro.core import spans

READERS = ["frontend.jaxpr_ms_per_call", "frontend.sublayers_per_jaxpr"]
PLAN_CELLS = {
    # cell: (config, traffic, seq_lens, sublayers per jaxpr)
    "mixtral-8x7b.search": ("mixtral-8x7b", "search", [512, 1024], 1.0),
    "jamba2-mini.search_long": ("jamba2-mini", "search_long", [4096, 8192],
                                8 / 3),
}


def _ctx(window):
    return Context(spans=Spans(), counters={}, trace=None, window=window,
                   peaks=None)


def _window(fn):
    t0 = time.perf_counter()
    fn()
    return t0, time.perf_counter()


@pytest.fixture(scope="module")
def windows():
    """Two Jamba periods, one Mixtral period, and one trace of a single
    block that builds its jaxpr outside any sublayer kind."""
    jamba = REGISTRY["jamba2-mini"]
    return {
        "jamba": _window(lambda: [F.transformer_graph(jamba, seq_len=n)
                                  for n in (4096, 8192)]),
        "mixtral": _window(lambda: F.transformer_graph(
            REGISTRY["mixtral-8x7b"], seq_len=512)),
        "block": _window(lambda: F.mlp_block_graph()),
    }


def test_jaxpr_time_is_part_of_each_trace(windows):
    t0, t1 = windows["jamba"]
    got = load_metric("frontend.jaxpr_ms_per_call").read(_ctx((t0, t1)))
    traces = spans.records("frontend.trace", t0, t1)
    assert len(traces) == 2
    trace_ms = 1e3 * sum(r.seconds for r in traces) / len(traces)
    assert 0 < got < trace_ms


@pytest.mark.parametrize("window,want", [("jamba", 8 / 3), ("mixtral", 1.0)])
def test_sublayers_per_jaxpr_is_the_period_over_its_kinds(windows, window,
                                                          want):
    got = load_metric("frontend.sublayers_per_jaxpr").read(
        _ctx(windows[window]))
    assert got == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_nothing_without_a_trace_in_the_window(windows, name):
    t1 = windows["block"][1]
    assert load_metric(name).read(_ctx((t1 + 1e3, t1 + 2e3))) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_nothing_where_a_trace_has_no_jaxpr_span(windows, name):
    """A trace that records ``frontend.trace`` alone, as a program from
    before the per-kind trace does."""
    t0, t1 = windows["block"]
    assert spans.records("frontend.trace", t0, t1)
    assert load_metric(name).read(_ctx((t0, t1))) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_nothing_where_the_program_has_no_spans(
        windows, monkeypatch, name):
    import repro.core

    monkeypatch.delattr(repro.core, "spans")
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert load_metric(name).read(_ctx(windows["jamba"])) is None


@pytest.mark.parametrize("cell", sorted(PLAN_CELLS))
def test_plan_cell_reports_both_readers(tmp_path, cell):
    """A traced run of the plan cell at a CPU size reports both readers,
    the sublayers per jaxpr exactly its period over its kinds."""
    config, traffic, seq_lens, want = PLAN_CELLS[cell]
    root = benchkit.small_root(tmp_path)
    path = root / "traffic" / f"{traffic}.json"
    t = json.loads(path.read_text())
    t.update(seq_lens=seq_lens, sample_cells_per_call=16)
    path.write_text(json.dumps(t))
    metric = {"unit": "u", "better": "lower", "source": "host_clock",
              "workloads": [cell]}
    spec = {
        "workloads": [{"name": cell, "config": config, "traffic": traffic,
                       "chips": 1, "why": "test"}],
        "end_to_end": [dict(metric, name="plans_per_s", bound=0.1),
                       dict(metric, name="setup_s", bound=0.1)],
        "per_layer": [dict(metric, name=n, layer="test", moves="plans_per_s")
                      for n in READERS],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    res = run_cell(cell, 2147483891, 1.5, True, t_start=time.perf_counter(),
                   bench_json=tmp_path / "BENCHMARK.json", root=root,
                   require_tpu=False, compile_cache=False)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == set(READERS)
    assert res["metrics"]["frontend.jaxpr_ms_per_call"]["value"] > 0
    assert res["metrics"]["frontend.sublayers_per_jaxpr"]["value"] == (
        pytest.approx(want, rel=1e-12))
