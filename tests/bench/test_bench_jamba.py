"""The AI21-Jamba2-Mini plan cell at a CPU size: the reference's own table
equals the program's trace, a sound run is correct and reads the search's
and the trace's spans, and the comparison fails on the float32 control and
on a trace whose SSM carry is priced at half its words."""
import dataclasses
import json
import time

import pytest

import benchkit
import reference as R
from harness import load_config, run_cell

CELL = "jamba2-mini.search_long"
PER_LAYER = ["frontend.trace_ms_per_call", "fusion.search_ms_per_call",
             "fusion.segment_ms_per_call"]


def run_jamba(tmp_path, **kw):
    """One run of the cell against :func:`benchkit.small_root`, planning
    the period at 4096 and 8192 tokens."""
    root = benchkit.small_root(tmp_path)
    traffic = root / "traffic" / "search_long.json"
    t = json.loads(traffic.read_text())
    t.update(seq_lens=[4096, 8192], sample_cells_per_call=16)
    traffic.write_text(json.dumps(t))
    metric = {"unit": "u", "better": "lower", "source": "host_clock",
              "workloads": [CELL]}
    spec = {
        "workloads": [{"name": CELL, "config": "jamba2-mini",
                       "traffic": "search_long", "chips": 1, "why": "test"}],
        "end_to_end": [dict(metric, name="plans_per_s", bound=0.1),
                       dict(metric, name="setup_s", bound=0.1)],
        "per_layer": [dict(metric, name=n, layer="test", moves="plans_per_s")
                      for n in PER_LAYER],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return run_cell(CELL, 7, 1.5, kw.pop("trace", False),
                    t_start=time.perf_counter(),
                    bench_json=tmp_path / "BENCHMARK.json", root=root,
                    require_tpu=False, compile_cache=False, **kw)


@pytest.mark.parametrize("seq_len", [4096, 8192, 131072])
def test_reference_table_equals_the_trace(seq_len):
    cfg, mod = load_config("jamba2-mini")
    g = mod.program_graph(cfg["model"], seq_len=seq_len)
    ref = mod.reference_graph(cfg, R, seq_len=seq_len)
    assert (ref.n_nodes, ref.n_edges) == (369, 563)
    assert mod.trace_diffs(cfg, g, ref, R, seq_len=seq_len) == 0


def test_sound_traced_run_is_correct_and_reads_its_spans(tmp_path):
    res = run_jamba(tmp_path, trace=True)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == set(PER_LAYER)
    assert all(m["value"] > 0 for m in res["metrics"].values())


def test_float32_control_is_not_correct(tmp_path):
    res = run_jamba(tmp_path, control=True)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_halved_scan_state_is_not_correct(tmp_path, monkeypatch):
    """A trace whose first SSM carry is priced at half its words."""
    from repro.core import frontend

    real = frontend.transformer_graph

    def broken(*args, **kw):
        g = real(*args, **kw)
        i = next(i for i, n in enumerate(g.nodes) if n.kind == "scan")
        nodes = list(g.nodes)
        nodes[i] = dataclasses.replace(nodes[i],
                                       state_words=nodes[i].state_words // 2)
        return dataclasses.replace(g, nodes=tuple(nodes))

    monkeypatch.setattr(frontend, "transformer_graph", broken)
    res = run_jamba(tmp_path)
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["inputs_diffs"]["value"] > 0


def test_longest_prefill_is_priced_exactly_on_the_real_space():
    """At the cell's longest prefill, over the configuration's whole design
    space, every cell prices under 2^53 (none quarantined), so the program
    and the reference agree on every pick and front."""
    res = run_cell(CELL, 2147616999, 1.0, False, t_start=time.perf_counter(),
                   require_tpu=False, compile_cache=False,
                   overrides={"seq_lens": [131072]})
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
