"""The comparison that decides ``correct`` has to fail: with the plain
reference in float32 put in the program's place (the control), and with
the timed path broken underneath a run, once for each fault a cell can
have.  The harness's look for a chip is skipped; the rest of a run is
driven as on the chip, at a CPU size."""
import os
import subprocess
import sys

import numpy as np
import pytest

import benchkit
from benchkit import ROOT

CELLS = ["vgg16.exhaustive", "mixtral-8x7b.search", "mixtral-8x7b.serve"]


@pytest.mark.parametrize("cell", CELLS)
def test_float32_control_is_not_correct(tmp_path, cell):
    res = benchkit.run_small(tmp_path, cell, seconds=1.5, control=True)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


class Fault:
    """Breaks what the device sweep returns, where it is produced."""

    def __init__(self, kind):
        self.kind = kind
        self.last = {}

    def __call__(self, run_sweep):
        def broken(exe, args):
            plane, dt = run_sweep(exe, args)
            plane = np.array(plane)
            H = plane.shape[1]
            if self.kind == "stale":  # the previous answer, unchanged
                prev = self.last.get(plane.shape)
                self.last[plane.shape] = plane
                if prev is not None:
                    plane = prev
            elif self.kind == "half":  # half the points left out
                plane[:, H // 2:] = plane[:, :H - H // 2]
            elif self.kind == "exchange":  # three shards never gathered
                plane[:, H // 4:] = 0.0
            elif self.kind == "altered":  # one cycle more everywhere
                plane[..., 1] += 1.0
            return plane, dt
        return broken


FAULTS = [(c, k) for c in CELLS[:2] for k in ("stale", "half", "altered")]
FAULTS += [("mixtral-8x7b.serve", k) for k in ("stale", "altered")]


@pytest.mark.parametrize("cell,kind", FAULTS)
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, cell, kind):
    from repro.core import flow

    monkeypatch.setattr(flow, "_run_sweep", Fault(kind)(flow._run_sweep))
    res = benchkit.run_small(tmp_path, cell, seconds=1.5)
    assert res["correct"] is False, res["checks"]


def score_edge_halved(trace):
    """The traced graph with its largest edge (attention's S x S scores)
    carrying half its words."""
    import dataclasses

    from repro.core.ir import EdgeSpec

    def broken(*args, **kw):
        g = trace(*args, **kw)
        big = max(g.edges, key=lambda e: e.words)
        edges = tuple(EdgeSpec(e.src, e.dst, e.words // 2) if e is big else e
                      for e in g.edges)
        return dataclasses.replace(g, edges=edges)
    return broken


@pytest.mark.parametrize("cell", ["mixtral-8x7b.search", "mixtral-8x7b.serve"])
def test_broken_trace_is_not_correct(tmp_path, monkeypatch, cell):
    from repro.core import frontend

    monkeypatch.setattr(frontend, "transformer_graph",
                        score_edge_halved(frontend.transformer_graph))
    res = benchkit.run_small(tmp_path, cell, seconds=1.5)
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["inputs_diffs"]["value"] > 0


FOUR_CHIP = r"""
import sys, tempfile
sys.path.insert(0, sys.argv[1])
import benchkit
from repro.core import flow
from test_bench_correct import Fault
kind = sys.argv[2]
if kind != "sound":
    flow._run_sweep = Fault(kind)(flow._run_sweep)
with tempfile.TemporaryDirectory() as d:
    r = benchkit.run_small(d, "vgg16.exhaustive.4chip", seconds=1.5)
print("CORRECT", r["correct"], r["checks"])
"""


@pytest.mark.parametrize("kind", ["sound", "exchange"])
def test_four_device_cell(kind):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    p = subprocess.run([sys.executable, "-c", FOUR_CHIP,
                        str(ROOT / "tests" / "bench"), kind],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    want = "True" if kind == "sound" else "False"
    assert f"CORRECT {want}" in p.stdout, p.stdout[-2000:] + p.stderr[-2000:]
