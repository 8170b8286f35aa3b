"""``fleet.select_rows_per_call``: the candidate rows the host selection of
a ``run_fleet`` call read, mean per call in the window.  A sweep pruned on
the device reads its few survivor rows, the full path every row; the reader
gives nothing where no call began in the window, where the program records
no spans, or where its select spans carry no count."""
import sys
import time

import numpy as np
import pytest

import benchkit  # noqa: F401  (puts bench/ on sys.path)
from harness import Context, Spans, load_metric

from repro.core import flow, spans
from repro.core import metrics as M
from repro.core.arch import Constraints, config_space_grid
from repro.core.ir import as_graph, vgg16_ir

GRID = config_space_grid(
    f1s=(2, 4), f2s=(2, 4), f3s=(2, 4), f4s=(2, 4),
    bus_widths=(2, 4), sram_splits=("unified",),
)
C = 1024


class Full:
    """Returns a host copy of the plane, which takes the full path."""

    def poison_plane(self, plane, h0):
        return np.array(plane)


def _call(hooks=None):
    g = as_graph(vgg16_ir(pool_mode="separate"))
    cuts = np.random.default_rng(3).random((C, g.n_edges)) < 0.5
    return flow.run_fleet([g], config_space=GRID, constraints=Constraints(),
                          groupings=[cuts], hooks=hooks)


def _read(window):
    ctx = Context(spans=Spans(), counters={}, trace=None, window=window,
                  peaks=None)
    return load_metric("fleet.select_rows_per_call").read(ctx)


def _window(fn, n=2):
    fn()  # warm: the first call compiles
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return t0, time.perf_counter()


def _unrecorded_select():
    """A call whose select span carries no count, as older programs
    recorded it."""
    with spans.span("fleet.call"):
        with spans.span("fleet.select"):
            pass


@pytest.mark.parametrize("case", ["pruned", "full", "no_call", "no_spans",
                                  "no_count"])
def test_select_rows_per_call(monkeypatch, case):
    if case == "pruned":
        v = _read(_window(_call))
        assert 1 <= v <= M.PRUNE_ROWS
    elif case == "full":
        assert _read(_window(lambda: _call(Full()))) == len(GRID) * C
    elif case == "no_call":
        t1 = _window(_call)[1]
        assert _read((t1 + 1e3, t1 + 2e3)) is None
    elif case == "no_spans":
        window = _window(_call)
        import repro.core

        monkeypatch.delattr(repro.core, "spans")
        monkeypatch.setitem(sys.modules, "repro.core.spans", None)
        assert _read(window) is None
    else:
        assert _read(_window(_unrecorded_select)) is None
