"""``run_fleet`` prunes the candidate plane on the device and finishes the
pick on the host from a few survivor rows.  Every case here holds that path
to the full path (a hook that returns a host copy of the plane forces it):
the same FlowResult fields, or the same error.  Crafted planes reach the
pruned program through its kernel, so ties, energies closer than the
pruning bound, cells at the limits, overflow, poison and padded rows are
each placed where they are meant to be."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import flow, spans
from repro.core import metrics as M
from repro.core.arch import Constraints, config_space_grid
from repro.core.errors import InfeasibleConstraintsError
from repro.core.ir import as_graph, residual_block_ir, vgg16_ir
from repro.testing.faults import FaultInjector

GRID = config_space_grid(
    f1s=(2, 4), f2s=(2, 4), f3s=(2, 4), f4s=(2, 4),
    bus_widths=(2, 4), sram_splits=("unified",),
)  # 48 points, e_dram 1.0, e_sram 0.1, e_pb 0.01 at each
H = len(GRID)
INF = float("inf")
K = M.PRUNE_ROWS
L = 2.0 ** 24  # the limit the crafted columns sit at


class Full:
    """Forces the full path: returns a host copy of the plane."""

    def poison_plane(self, plane, h0):
        return np.array(plane)


def _graphs(n=1):
    return [as_graph(residual_block_ir()) for _ in range(n)]


def _batches(graphs, counts, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.random((c, g.n_edges)) < 0.5 for g, c in zip(graphs, counts)]


def _select_work():
    """The ``fleet.select`` rows read by the newest ``run_fleet`` call."""
    return spans.per_call("fleet.call", "fleet.select")[-1][1]


def _outcome(fn):
    """Each graph's FlowResult fields, or the error raised."""
    try:
        fl = fn()
    except Exception as e:  # compared, type and message, across paths
        return ("raised", type(e), str(e))
    return tuple(
        (r.best_hw, r.best_cuts.tolist(), r.best_metrics, r.n_feasible,
         r.group_sizes, r.n_candidates, r.n_pruned, r.search_engine,
         repr(r.quarantine))
        for r in fl.results
    ) + (fl.n_candidates, repr(fl.quarantine))


def _both(graphs, batches, constraints, **kw):
    """(pruned outcome, rows its select read, full outcome)."""
    run = lambda hooks: flow.run_fleet(  # noqa: E731
        graphs, config_space=GRID, constraints=constraints,
        groupings=batches, hooks=hooks, **kw)
    pruned = _outcome(lambda: run(None))
    work = _select_work()
    return pruned, work, _outcome(lambda: run(Full()))


@pytest.fixture
def crafted(monkeypatch):
    """Puts a crafted (G, H, C, 5) raw plane in the fleet kernel's place,
    so the pruned program summarises it on the device."""

    def put(plane):
        plane = np.asarray(plane, np.float64)
        def pruned(*args):  # a new function, so that jit traces anew
            return M._evaluate_fleet_graph_pruned(*args)

        monkeypatch.setattr(M, "_evaluate_fleet_graph",
                            lambda *args: jnp.asarray(plane))
        monkeypatch.setattr(M, "_jit_fleet_graph_pruned", jax.jit(pruned))
        flow.clear_sweep_cache()

    yield put
    flow.clear_sweep_cache()  # no crafted executable outlives the test


def _plane(rng, G, C, bw, lat=None, area=None):
    """Raw rows whose energy is their bandwidth (no SRAM or PE counts)."""
    plane = np.zeros((G, H, C, 5))
    plane[..., 0] = bw
    plane[..., 1] = rng.integers(1, 1000, (G, H, C)) if lat is None else lat
    plane[..., 4] = rng.integers(1, 1000, (G, H, C)) if area is None else area
    return plane


def _random(rng):
    plane = np.zeros((1, H, 8, 5))
    plane[..., :2] = rng.integers(1, 4096, (1, H, 8, 2))
    plane[..., 2:4] = rng.integers(0, 4096, (1, H, 8, 2))
    plane[..., 4] = rng.integers(1, 4096, (1, H, 8))
    limits = Constraints(3000.0, 3000.0, 3000.0, 3000.0)
    return plane, [8], limits


def _ties(rng):
    # bandwidth 100 at a few cells: energy ties broken by lat, area, h, c;
    # (bw 99, c_pb 100) gives the same energy with a smaller bandwidth
    plane = _plane(rng, 1, 8, rng.integers(200, 1000, (1, H, 8)))
    tied = [(3, 1), (3, 2), (7, 0), (11, 5), (40, 7), (2, 6)]
    for h, c in tied:
        plane[0, h, c] = (100, 50, 0, 0, 10)
    plane[0, 7, 0, 1] = 40  # the smaller latency
    plane[0, 11, 5, 4] = 9  # the smaller area, same latency as the rest
    plane[0, 40, 7, [0, 3]] = (99, 100)  # the smaller bandwidth
    assert M.compose_metrics(plane[:, 40:41, 7:8], np.stack(
        [GRID[40].as_row()]))[0, 0, 0, 2] == 100.0
    return plane, [8], Constraints(INF, INF, INF, INF)


def _identical_ties(rng):
    # fully identical rows: the lowest (h, c) wins
    plane = _plane(rng, 1, 8, rng.integers(200, 1000, (1, H, 8)))
    for h, c in [(30, 1), (5, 6), (5, 2), (29, 0)]:
        plane[0, h, c] = (100, 50, 0, 0, 10)
    return plane, [8], Constraints(INF, INF, INF, INF)


def _closer_than_delta(rng):
    # energies 2^30 + j: the float32 key cannot order them, the host must
    base = 2.0 ** 30
    plane = _plane(rng, 1, 8, base + 5000 + rng.integers(0, 100, (1, H, 8)))
    js = rng.permutation(40)
    cells = rng.choice(H * 8, 40, replace=False)
    for j, cell in zip(js, cells):
        plane[0, cell // 8, cell % 8, 0] = base + j
    return plane, [8], Constraints(INF, INF, INF, INF)


# Multiples of the limit around it: inside, at and outside the pruning
# bound (2^-20) on both sides, and exactly at it.
NEAR = [1 - 2.0 ** -18, 1 - 2.0 ** -21, 1 - 2.0 ** -24, 1.0,
        1 + 2.0 ** -24, 1 + 2.0 ** -21, 1 + 2.0 ** -18]


def _near_limit(column):
    def make(rng):
        # the cheapest cells sit at the limit of ``column``; the cells
        # clearly within it cost more
        plane = _plane(rng, 1, 8, 4000 + rng.integers(0, 100, (1, H, 8)))
        cells = rng.choice(H * 8, 3 * len(NEAR), replace=False)
        for i, cell in enumerate(cells):
            h, c = cell // 8, cell % 8
            v = L * NEAR[i % len(NEAR)]
            if column == 2:  # energy is bandwidth here
                plane[0, h, c, 0] = v
            else:
                plane[0, h, c, 0] = 1000 + i
                plane[0, h, c, {0: 0, 1: 1, 3: 4}[column]] = v
        lim = [1e12, 1e12, 1e12, 1e12]
        lim[column] = L
        if column == 2:  # keep cheaper rows within the energy limit
            plane[0, :, :, 0] = np.where(plane[0, :, :, 0] < L / 2,
                                         L / 2 + plane[0, :, :, 0],
                                         plane[0, :, :, 0])
        return plane, [8], Constraints(*lim)
    return make


def _overflow(rng):
    # more tied rows than the summary holds: the full path decides
    plane = _plane(rng, 1, 8, 100.0, lat=50.0, area=10.0)
    return plane, [8], Constraints(INF, INF, INF, INF)


def _undecided_overflow(rng):
    # more undecided rows than the summary holds
    plane = _plane(rng, 1, 8, L, lat=50.0, area=10.0)
    plane[0, 0, 0, 0] = 10.0
    return plane, [8], Constraints(L, INF, INF, INF)


def _poisoned(rng):
    plane, counts, limits = _random(rng)
    for (h, c), v in zip([(1, 2), (9, 0), (20, 7), (33, 3)],
                         [np.nan, np.inf, -1.0, 2.0 ** 60]):
        plane[0, h, c, 2] = v
    return plane, counts, limits


def _infeasible(rng):
    plane, counts, _ = _random(rng)
    return plane, counts, Constraints(0.5, INF, INF, INF)


def _no_sure_cell(rng):
    # every feasible cell is within the bound of a limit: undecided only
    plane = _plane(rng, 1, 8, L, lat=50.0, area=10.0)
    plane[0, :3, :, 0] = L * (1 + 2.0 ** -18)
    return plane, [8], Constraints(L, INF, INF, INF)


def _service_form(rng):
    # G = 3 with uneven counts; padded cut rows hold NaN and the least
    # energies, which must neither quarantine nor win
    counts = [3, 7, 5]
    plane = _plane(rng, 3, 8, 500 + rng.integers(0, 400, (3, H, 8)))
    for gi, n in enumerate(counts):
        plane[gi, :, n:, 0] = 1.0
        plane[gi, ::5, n:, 2] = np.nan
    plane[1, 4, 2] = plane[1, 9, 6] = (300, 50, 0, 0, 10)
    return plane, counts, Constraints(INF, INF, INF, 900)


CASES = {
    "random": _random,
    "ties": _ties,
    "identical_ties": _identical_ties,
    "closer_than_delta": _closer_than_delta,
    "near_bandwidth_limit": _near_limit(0),
    "near_latency_limit": _near_limit(1),
    "near_energy_limit": _near_limit(2),
    "near_area_limit": _near_limit(3),
    "overflow": _overflow,
    "undecided_overflow": _undecided_overflow,
    "poisoned": _poisoned,
    "infeasible": _infeasible,
    "no_sure_cell": _no_sure_cell,
    "service_form": _service_form,
}
FALLS_BACK = {"overflow", "undecided_overflow", "poisoned", "infeasible",
              "no_sure_cell"}
# (h, c) of the winner the tie order gives
WINNER = {"ties": (40, 7), "identical_ties": (5, 2), "overflow": (0, 0)}


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("case", sorted(CASES))
def test_pruned_pick_equals_the_full_path(crafted, case, seed):
    rng = np.random.default_rng([seed, len(case)])
    plane, counts, limits = CASES[case](rng)
    crafted(plane)
    graphs = _graphs(len(counts))
    batches = _batches(graphs, counts)
    pruned, work, full = _both(graphs, batches, limits)
    assert pruned == full
    if case in WINNER:
        h, c = WINNER[case]
        assert pruned[0][:2] == (GRID[h], batches[0][c].tolist())
    if case in FALLS_BACK:
        assert work == H * sum(counts)
    else:
        assert work <= K * len(counts)
    if case == "infeasible":
        assert pruned[1] is InfeasibleConstraintsError
    if case == "poisoned":
        assert pruned[-1].count("QuarantinedCell(") == 4


@pytest.mark.parametrize("case,limits", [
    ("vgg16_paper", Constraints()),
    ("vgg16_relaxed", Constraints(*[INF] * 4)),
    ("residual_tight", Constraints(INF, 1.0e6, INF, 1.2e6)),
])
def test_pruned_pick_equals_the_full_path_on_the_kernel(case, limits):
    g = as_graph(vgg16_ir(pool_mode="separate") if case.startswith("vgg16")
                 else residual_block_ir())
    cuts = [np.unique(_batches([g], [300], seed=7)[0], axis=0)]
    pruned, work, full = _both([g], cuts, limits)
    assert pruned == full
    assert work <= K


def test_each_other_path_reads_every_row():
    g = _graphs()
    cuts = _batches(g, [4])
    loose = Constraints(*[INF] * 4)
    base = _outcome(lambda: flow.run_fleet(
        g, config_space=GRID, constraints=loose, groupings=cuts))
    assert _select_work() <= K
    for kw in ({"pareto": True}, {"hw_chunk": 16}):
        fl = flow.run_fleet(g, config_space=GRID, constraints=loose,
                            groupings=cuts, **kw)
        assert _select_work() == H * 4, kw
        fields = _outcome(lambda: fl)
        assert fields == base, kw


def test_a_mesh_prunes_too():
    """A sharded sweep prunes on the mesh: its select reads the survivors
    only, and its pick is the single-device pick."""
    g = _graphs()
    cuts = _batches(g, [4])
    loose = Constraints(*[INF] * 4)
    base = _outcome(lambda: flow.run_fleet(
        g, config_space=GRID, constraints=loose, groupings=cuts))
    fl = flow.run_fleet(g, config_space=GRID, constraints=loose,
                        groupings=cuts, devices=1)
    assert fl.device_count == 1
    assert _select_work() <= K
    assert _outcome(lambda: fl) == base


def test_hook_reads_the_device_plane_bit_for_bit():
    """A PlaneTap-style read of 64 cells, outside enable_x64, equals the
    host copy of the plane; the hook runs once and its view is released."""
    g = as_graph(vgg16_ir(pool_mode="separate"))
    cuts = _batches([g], [200], seed=3)
    seen = []

    class Tap:
        def poison_plane(self, plane, h0):
            rng = np.random.default_rng(5)
            h, c = rng.integers(0, H, 64), rng.integers(0, 200, 64)
            got = np.array(plane[0, h, c])
            whole = np.array(plane)
            seen.append((plane, got, whole[0, h, c], whole))
            assert plane.shape == whole.shape and plane.dtype == np.float64
            return plane

    flow.run_fleet([g], config_space=GRID, constraints=Constraints(),
                   groupings=cuts, hooks=Tap())
    [(view, got, want, whole)] = seen
    assert got.dtype == np.float64
    assert np.array_equal(got.view(np.uint64), want.view(np.uint64))
    assert _select_work() <= K  # the tap kept the pruned path
    assert view._array is None  # no device plane outlives the call
    # the same cells as a sweep of the plain kernel
    plain = flow.run_fleet([g], config_space=GRID, constraints=Constraints(),
                           groupings=cuts, hooks=Full())
    assert plain.results[0].best_metrics == _best_of(whole, cuts[0], g)


def _best_of(whole, cuts, g):
    hw = np.stack([c.as_row() for c in GRID])
    out = M.compose_metrics(whole[0, :, :len(cuts)], hw)
    return flow._best_flow_result(
        out, cuts, g, GRID, Constraints(), n_pruned=0, compile_seconds=0.0,
        sweep_seconds=0.0, candidates_per_second=0.0).best_metrics


@pytest.mark.parametrize("cell", [(0, 3, 1), (0, 47, 0)])
def test_fault_injector_still_quarantines(cell):
    g = _graphs()
    cuts = _batches(g, [4])
    faults = FaultInjector(poison_cell=cell)
    fl = flow.run_fleet(g, config_space=GRID, constraints=Constraints(
        *[INF] * 4), groupings=cuts, hooks=faults)
    assert faults.counts["poisoned_cells"] == 1
    [q] = fl.quarantine.cells
    assert (q.graph, q.hw, q.cut, q.reason) == (*cell, "nan")
    assert _select_work() == H * 4  # the changed plane took the full path


@pytest.mark.parametrize("limit", [
    0.0, 1.0, -3.0, 2.0 ** 24 + 1, 1e-45, 65e6, 1e39, 1e300, INF, -INF])
def test_prune_limits_widen_each_limit_by_the_bound(limit):
    lo, hi = M.prune_limits(np.full(4, limit))
    assert lo.dtype == hi.dtype == np.float32
    lo64, hi64 = lo.astype(np.float64), hi.astype(np.float64)
    if np.isinf(limit):
        assert (lo64 == limit).all() and (hi64 == limit).all()
        return
    slack = M.PRUNE_DELTA * abs(limit)
    assert (lo64 <= limit - slack).all() and (hi64 >= limit + slack).all()


@pytest.mark.parametrize("field,value,applies", [
    ("e_dram_nj", 1.0, True),
    ("e_pb_nj", 0.0, True),
    ("e_sram_nj", -0.1, False),
    ("e_pb_nj", 1e-30, False),
    ("e_dram_nj", 1e30, False),
])
def test_pruning_applies_only_where_its_bound_holds(field, value, applies):
    space = [dataclasses.replace(c, **{field: value}) for c in GRID]
    hw = np.stack([c.as_row() for c in space])
    assert M.prune_applies(hw, M.area_consts_of_space(space)) is applies
    g = _graphs()
    cuts = np.unique(_batches(g, [300])[0], axis=0)  # all 16 groupings
    fl = flow.run_fleet(g, config_space=space, constraints=Constraints(
        *[INF] * 4), groupings=[cuts])
    work = _select_work()
    assert work <= K if applies else work == H * 16
    assert fl.results[0].n_feasible == H * 16
