"""The paper's optimisation flow (Sec. II-C), generalised to graph IRs.

For each (hardware configuration x fusion grouping) candidate, estimate the
four metrics, reject candidates violating the user constraints, and return
the feasible candidate with minimum energy.  The cross-product is evaluated
as a single jitted/vmapped XLA program
(:func:`repro.core.metrics.evaluate_fleet_graph`), which is the JAX-native
realisation of the paper's exhaustive sweep — the benchmark reports
candidates/second.  Groupings are boolean cut vectors over the graph's
edges; chains (``NetworkIR``) are embedded losslessly via
:func:`repro.core.ir.as_graph`.

Two serving-system moves keep the cold path cheap: argument shapes are
rounded up to power-of-two *shape buckets* and evaluated through masked
kernels (padded rows exactly inert), so distinct graphs share one compiled
executable instead of each paying XLA compilation per exact ``(L, E, C)``
signature; and :func:`run_fleet` stacks many padded graphs along a leading
axis to evaluate the whole ``(G, H, C)`` cross-product — the entire model
fleet — in one program.  :func:`run_flow` is its one-graph form.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Sequence

import jax
import numpy as np
from jax import enable_x64

from . import fusion
from . import metrics as M
from . import spans
from ..parallel.sharding import hardware_mesh, mesh_fingerprint
from .arch import Constraints, DLAConfig, default_config_space
from .errors import (
    InfeasibleBudgetError,
    InfeasibleConstraintsError,
    PoisonedResultError,
    RetryPolicy,
    TransientFailure,
)
from .ir import (
    GraphIR,
    NetworkIR,
    as_graph,
    bucket_size,
    pad_cuts_batch,
    pad_graph,
)

# Shape-bucket floors: (L, E, C) are rounded up to the next power of two, but
# never below these, so every in-repo workload (VGG-16 18/17, ResNet-18
# 31/38, MobileNet 17/18, MLP block 4/3, encoder-decoder 19/21, residual
# block 4/4) lands in the SAME (32, 64) bucket and one cached executable
# serves the whole model fleet.  The padded rows are exactly inert (masked
# kernels), so bucketing never changes a metric — it only kills recompiles.
NODE_BUCKET_FLOOR = 32
EDGE_BUCKET_FLOOR = 64
CUT_BUCKET_FLOOR = 4


@dataclasses.dataclass(frozen=True)
class FlowResult:
    """One graph's sweep outcome: the argmin (hw, cuts, metrics), the
    candidate/feasibility accounting, timing split, and provenance."""

    best_hw: DLAConfig
    best_cuts: np.ndarray
    best_metrics: M.Metrics
    group_sizes: tuple[int, ...]
    n_candidates: int
    n_feasible: int
    n_pruned: int  # groupings dropped by the SRAM prefilter before the sweep
    compile_seconds: float  # XLA compile paid by this call (0 on cache hit)
    # The sweep's execution (input transfer, dispatch, kernel) plus the
    # device-to-host fetch of its result, the raw plane or the pruned
    # program's summary: fleet.execute + fleet.fetch.
    sweep_seconds: float
    candidates_per_second: float
    # Provenance of the grouping candidates: "exhaustive" / "pool" /
    # "explicit", or — for groupings="search"/"dp" — the engine that
    # produced the search optimum ("chain_dp" / "frontier_dp" / "beam"),
    # so callers know whether the swept optimum is certified exact.
    search_engine: str = ""
    # (architecture x fusion plan) Pareto front over the feasible sweep,
    # populated when the flow is asked for it (``pareto=True``).
    pareto: "ParetoFront | None" = None
    # Cells the finite guard excluded (None when the sweep was clean).
    quarantine: "QuarantineReport | None" = None

    def describe(self) -> str:
        """One-line summary: best hw, group sizes, and the four metrics."""
        return (
            f"best={self.best_hw.describe()} groups={list(self.group_sizes)} "
            f"BW={self.best_metrics.bandwidth_words/1e6:.2f}M words "
            f"lat={self.best_metrics.latency_cycles/1e6:.2f}M cyc "
            f"E={self.best_metrics.energy_nj/1e6:.2f} mJ "
            f"A={self.best_metrics.area_um2/1e6:.2f} mm^2 "
            f"({self.n_feasible}/{self.n_candidates} feasible, "
            f"{self.n_pruned} pruned, "
            f"{self.candidates_per_second:,.0f} cand/s, "
            f"compile {self.compile_seconds*1e3:.0f} ms, "
            f"groupings={self.search_engine})"
        )


# AOT-compiled evaluator executables keyed by (kernel, argument shapes), so
# a run_fleet call executes the sweep exactly once: the first call with a
# new shape signature pays (and reports) the XLA compile, repeats reuse the
# executable and report compile_seconds == 0.  The cache is a
# bounded LRU: a hit refreshes the entry, and at capacity only the
# least-recently-used executable is evicted (never a wholesale clear, which
# would drop every hot executable at once).
_COMPILED_SWEEPS: "collections.OrderedDict[tuple, object]" = (
    collections.OrderedDict()
)
SWEEP_CACHE_CAPACITY = 64
_SWEEP_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}
# One lock covers the OrderedDict *and* its stats dict: the planning
# service's admission path touches the cache from whatever thread submits,
# and an unguarded move_to_end/popitem pair can corrupt the LRU order (or
# the hit/miss/eviction accounting) under interleaving.
_SWEEP_CACHE_LOCK = threading.Lock()


def _sweep_cache_get(key: tuple):
    """LRU lookup: a hit moves the entry to the most-recently-used end."""
    with _SWEEP_CACHE_LOCK:
        exe = _COMPILED_SWEEPS.get(key)
        if exe is not None:
            _COMPILED_SWEEPS.move_to_end(key)
            _SWEEP_CACHE_STATS["hits"] += 1
        return exe


def _sweep_cache_put(key: tuple, exe) -> None:
    """LRU insert: evicts oldest entries only, one at a time, at capacity."""
    with _SWEEP_CACHE_LOCK:
        _SWEEP_CACHE_STATS["misses"] += 1
        while len(_COMPILED_SWEEPS) >= SWEEP_CACHE_CAPACITY:
            _COMPILED_SWEEPS.popitem(last=False)
            _SWEEP_CACHE_STATS["evictions"] += 1
        _COMPILED_SWEEPS[key] = exe


# Mesh component of every cache key.  A sweep compiled for one device
# layout must never be served to another: an 8-device shard_mapped program
# and the single-device program have identical argument shapes, so shapes
# alone cannot tell them apart.
_SINGLE_MESH_KEY = ("single", 1)


def _cache_entry_info(key: tuple) -> dict:
    """{kernel, mesh_axis, device_count} view of one cache key (tolerant of
    synthetic short keys used by unit tests)."""
    kernel = key[0] if key else "?"
    mesh = (
        key[1]
        if len(key) > 1 and isinstance(key[1], tuple) and len(key[1]) >= 2
        else _SINGLE_MESH_KEY
    )
    return {
        "kernel": kernel,
        "mesh_axis": mesh[0],
        "device_count": int(mesh[1]),
    }


def sweep_cache_stats() -> dict:
    """Executable-cache accounting: {size, hits, misses, evictions,
    entries}.  ``misses`` counts XLA compilations actually paid — the tests
    assert a whole multi-model sweep costs exactly one.
    ``entries`` lists each cached executable's {kernel, mesh_axis,
    device_count}, so the device-layout split of the key space is
    observable (a 1-device sweep and an 8-device sweep are distinct
    entries even at identical shapes).  Snapshotted under the cache lock,
    so concurrent readers never see a half-updated accounting."""
    with _SWEEP_CACHE_LOCK:
        return dict(
            _SWEEP_CACHE_STATS,
            size=len(_COMPILED_SWEEPS),
            entries=[_cache_entry_info(k) for k in _COMPILED_SWEEPS],
        )


def clear_sweep_cache() -> None:
    """Drop every cached sweep executable and zero the hit/miss stats."""
    with _SWEEP_CACHE_LOCK:
        _COMPILED_SWEEPS.clear()
        for k in _SWEEP_CACHE_STATS:
            _SWEEP_CACHE_STATS[k] = 0


def _compiled_sweep(
    fn, args, mesh_key: tuple = _SINGLE_MESH_KEY
) -> tuple[object, float]:
    """(executable, compile_seconds_this_call) for a jitted metric kernel.

    Lowered under scoped ``enable_x64`` with float64 numpy arguments, so
    the sweep is exact (bit-identical to the scalar oracles) without
    touching the process-global JAX precision config.  ``mesh_key``
    (:data:`_SINGLE_MESH_KEY` or a sharded mesh fingerprint) is part of
    the cache key: device layout changes the compiled program even at
    identical argument shapes.  A cache miss is the ``fleet.compile``
    span, whose duration is the compile time returned."""
    key = (getattr(fn, "__name__", str(fn)), mesh_key) + tuple(
        (a.shape, a.dtype.str) for a in args  # str(dtype) costs ~7 us
    )
    exe = _sweep_cache_get(key)
    if exe is not None:
        return exe, 0.0
    with spans.span("fleet.compile") as sp:
        with enable_x64(True):
            exe = fn.lower(*args).compile()
    _sweep_cache_put(key, exe)
    return exe, sp.record.seconds


class DevicePlane:
    """Read-only view of a sweep's raw (G, H, C, 5) float64 plane while it
    is still on the device, with the pruned program's
    :class:`~repro.core.metrics.PlaneSummary` already on the host.

    ``plane[idx]`` gathers the indexed cells on the device and returns a
    numpy array; ``np.asarray(plane)`` copies the whole plane.  Both work
    outside ``enable_x64``.  :func:`run_fleet` releases the device array
    before it returns, so a view kept past its call reads nothing."""

    def __init__(self, array: jax.Array, summary: M.PlaneSummary):
        """View ``array``, the plane the program returned with ``summary``,
        and note whether the summary alone decides every graph's pick."""
        self._array = array
        self.summary = summary
        self.decides = summary.decides()

    @property
    def shape(self) -> tuple[int, ...]:
        """The plane's (G, H, C, 5) shape."""
        return self._array.shape

    @property
    def dtype(self) -> np.dtype:
        """The plane's dtype, float64."""
        return np.dtype(self._array.dtype)

    def __getitem__(self, idx) -> np.ndarray:
        with enable_x64(True):
            return np.asarray(self._array[idx])

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        out = np.asarray(self._array)
        return out if dtype is None else out.astype(dtype)

    def release(self) -> None:
        """Drop the reference to the device array."""
        self._array = None


def _run_sweep(exe, args) -> tuple[np.ndarray | DevicePlane, float]:
    """(raw plane, sweep_seconds): one execution of an AOT executable
    (inside ``enable_x64`` — the executable's avals are float64).

    Two spans: ``fleet.execute`` runs the program until the device is
    done with it (input transfer, dispatch, kernel), ``fleet.fetch``
    copies its result to the host, and its ``work`` is the bytes copied;
    sweep_seconds is the sum of their durations.  A plain sweep's plane is
    copied whole.  The pruned program (:func:`~repro.core.metrics.
    _evaluate_fleet_graph_pruned`) returns the plane and a summary: only
    the summary is copied, and the plane comes back as a
    :class:`DevicePlane`."""
    with spans.span("fleet.execute") as ex:
        with enable_x64(True):
            dev = jax.block_until_ready(exe(*args))
    with spans.span("fleet.fetch") as fe:
        if isinstance(dev, tuple):
            plane, summary = dev
            out = DevicePlane(plane, jax.device_get(summary))
            fe.work = sum(a.nbytes for a in out.summary)
        else:
            out = np.asarray(dev)
            fe.work = out.nbytes
    return out, ex.record.seconds + fe.record.seconds


def _metrics_from_row(row: np.ndarray) -> M.Metrics:
    return M.Metrics(
        bandwidth_words=float(row[0]),
        latency_cycles=float(row[1]),
        energy_nj=float(row[2]),
        area_um2=float(row[3]),
    )


# ---------------------------------------------------------------------------
# Poison quarantine — the finite guard over raw sweep planes
# ---------------------------------------------------------------------------

# Column names of the raw (…, 5) kernel rows, for quarantine provenance.
RAW_COLUMNS = (
    "bandwidth_words",
    "latency_cycles",
    "sram_accesses",
    "pb_accesses",
    "area_um2",
)


@dataclasses.dataclass(frozen=True)
class QuarantinedCell:
    """Provenance of one poisoned sweep cell: which (graph, hw, cut)
    candidate was excluded, which raw column tripped the finite guard,
    the offending value, and why (``nan``/``inf``/``negative``/
    ``overflow`` — overflow meaning above 2^53, where integer word
    counts stop being exact in f64)."""

    graph: int
    hw: int
    cut: int
    column: str
    value: float
    reason: str


@dataclasses.dataclass(frozen=True)
class QuarantineReport:
    """Every cell the finite guard excluded from one sweep's selection.

    Quarantined cells can never win the argmin or enter a Pareto front —
    they are removed from the feasible set *before* selection — but the
    rest of the sweep still answers; only a graph whose ENTIRE candidate
    set is poisoned raises :class:`~repro.core.errors.PoisonedResultError`.
    """

    cells: tuple[QuarantinedCell, ...]

    @property
    def n_cells(self) -> int:
        """Number of quarantined (graph, hw, cut) cells."""
        return len(self.cells)

    def describe(self, limit: int = 8) -> str:
        """Multi-line summary: cell count plus the first ``limit`` cells."""
        lines = [f"quarantined {self.n_cells} poisoned cells"]
        for cell in self.cells[:limit]:
            lines.append(
                f"  (g={cell.graph}, h={cell.hw}, c={cell.cut}) "
                f"{cell.column}={cell.value!r} [{cell.reason}]"
            )
        if self.n_cells > limit:
            lines.append(f"  ... {self.n_cells - limit} more")
        return "\n".join(lines)


def _poison_reason(v: float) -> str:
    """Finite-guard verdict for one offending raw value."""
    if np.isnan(v):
        return "nan"
    if np.isinf(v):
        return "inf"
    if v < 0.0:
        return "negative"
    return "overflow"


def _quarantine_cells(
    raw: np.ndarray,  # (H, C, 5) one graph's raw plane, real rows only
    poison: np.ndarray,  # (H, C) bool, from metrics.poison_mask
    *,
    graph: int,
) -> tuple[QuarantinedCell, ...]:
    """Provenance records for one graph's poisoned cells, naming the first
    offending raw column of each."""
    cells = []
    for h, c in np.argwhere(poison):
        row = raw[h, c]
        bad = ~np.isfinite(row) | (row < 0.0) | (row > M.MAX_EXACT_WORDS)
        k = int(np.flatnonzero(bad)[0])
        v = float(row[k])
        cells.append(
            QuarantinedCell(
                graph=int(graph), hw=int(h), cut=int(c),
                column=RAW_COLUMNS[k], value=v, reason=_poison_reason(v),
            )
        )
    return tuple(cells)


@dataclasses.dataclass(frozen=True)
class ParetoFront:
    """Non-dominated (architecture x fusion plan) points of one workload's
    feasible sweep, minimising (bandwidth, latency, energy, area) jointly —
    the design-space-exploration output the single min-energy point throws
    away.  Points are sorted by (energy, bandwidth, latency, area, h, c);
    exact-duplicate metric rows keep their lowest-index representative
    (:func:`repro.core.metrics.pareto_front_mask`), so the front is
    deterministic and device-count invariant like the argmin."""

    metrics: np.ndarray  # (P, 4) [bw, lat, energy, area]
    hw_indices: np.ndarray  # (P,) into the sweep's config_space
    cut_indices: np.ndarray  # (P,) into the surviving cut batch
    configs: tuple[DLAConfig, ...]  # (P,) the actual design points
    cuts: np.ndarray  # (P, E) the fusion plan of each point
    n_feasible: int  # candidates the front was extracted from
    search_engine: str = ""  # grouping provenance, as FlowResult

    @property
    def size(self) -> int:
        """Number of non-dominated points on the front."""
        return int(self.metrics.shape[0])

    def describe(self, limit: int = 8) -> str:
        """Multi-line summary: front size plus the first ``limit`` rows."""
        lines = [
            f"pareto front: {self.size} of {self.n_feasible} feasible "
            f"(groupings={self.search_engine})"
        ]
        for i in range(min(self.size, limit)):
            bw, lat, e, a = self.metrics[i]
            lines.append(
                f"  {self.configs[i].describe():40s} "
                f"BW={bw/1e6:7.2f}M lat={lat/1e6:7.2f}M "
                f"E={e/1e6:6.2f}mJ A={a/1e6:5.2f}mm^2"
            )
        if self.size > limit:
            lines.append(f"  ... {self.size - limit} more")
        return "\n".join(lines)


def _pareto_front(
    out: np.ndarray,  # (H, C, 4) real candidate rows
    feasible: np.ndarray,  # (H, C) bool
    cuts_batch: np.ndarray,  # (C, E)
    config_space: Sequence[DLAConfig],
    search_engine: str,
) -> ParetoFront:
    """Extract the feasible sweep's Pareto front in deterministic order."""
    idx = np.argwhere(feasible)  # (N, 2) in (h, c) lexicographic order
    rows = out[feasible]  # row-major: matches idx order
    keep = M.pareto_front_mask(rows)
    sel_rows, sel_idx = rows[keep], idx[keep]
    order = np.lexsort(
        (
            sel_idx[:, 1],
            sel_idx[:, 0],
            sel_rows[:, 3],
            sel_rows[:, 1],
            sel_rows[:, 0],
            sel_rows[:, 2],
        )
    )
    sel_rows, sel_idx = sel_rows[order], sel_idx[order]
    return ParetoFront(
        metrics=sel_rows,
        hw_indices=sel_idx[:, 0],
        cut_indices=sel_idx[:, 1],
        configs=tuple(config_space[h] for h in sel_idx[:, 0]),
        cuts=cuts_batch[sel_idx[:, 1]],
        n_feasible=int(rows.shape[0]),
        search_engine=search_engine,
    )


def _best_flow_result(
    out: np.ndarray,  # (H, C, 4) — real candidate rows only, padding sliced
    cuts_batch: np.ndarray,  # (C, E) — real cut rows, real edge columns
    g: GraphIR,
    config_space: Sequence[DLAConfig],
    constraints: Constraints,
    *,
    n_pruned: int,
    compile_seconds: float,
    sweep_seconds: float,
    candidates_per_second: float,
    search_engine: str = "",
    err_prefix: str = "",
    pareto: bool = False,
    poison: np.ndarray | None = None,
    quarantine: "QuarantineReport | None" = None,
) -> FlowResult:
    """Constraint filter + min-energy argmin over one graph's sweep output —
    run_fleet's whole-plane selection, with the tie-break rule
    (:func:`_pick`) its survivor path shares, so the two paths can never
    pick differently.

    Tie-breaking is deterministic: among equal-energy feasible candidates
    the winner is the lexicographic minimum of (bandwidth, latency, area,
    h, c).  The selected *metrics* are therefore invariant to any
    permutation of the hardware axis, and the selected *config* is
    invariant up to fully-identical metric rows, where the lowest (h, c)
    index wins — so padding H to a device-count multiple or resharding the
    sweep can never flip the reported best point (asserted at 1/2/8 host
    devices in tests/test_multidevice.py).

    ``poison`` is the finite guard's (H, C) quarantine mask: poisoned
    cells are excluded from feasibility before any selection, so a NaN /
    Inf / negative / overflowed cost row can neither win the argmin nor
    enter the Pareto front.  A fully-poisoned candidate set raises
    :class:`PoisonedResultError` with the ``quarantine`` provenance.
    """
    limits = constraints.as_row()  # (4,)
    feasible = np.all(out <= limits[None, None, :], axis=-1)  # (H, C)
    if poison is not None:
        if poison.all():
            raise PoisonedResultError(
                f"{err_prefix}all {poison.size} candidates were poisoned "
                "(NaN/Inf/negative/overflowed cost rows) — nothing is left "
                "to select from",
                quarantined=(
                    quarantine.cells if quarantine is not None else ()
                ),
            )
        feasible &= ~poison
    n_feas = int(feasible.sum())
    if n_feas == 0:
        raise InfeasibleConstraintsError(
            f"{err_prefix}no candidate meets the constraints"
        )
    energy = np.where(feasible, out[:, :, 2], np.inf)
    ties = np.argwhere(energy == energy.min())  # (h, c) lexicographic order
    h, c = ties[_pick(out[ties[:, 0], ties[:, 1]], ties[:, 0], ties[:, 1])]
    return _flow_result(
        g, cuts_batch, config_space, h, c, out[h, c],
        n_candidates=out.shape[0] * out.shape[1],
        n_feasible=n_feas,
        n_pruned=n_pruned,
        compile_seconds=compile_seconds,
        sweep_seconds=sweep_seconds,
        candidates_per_second=candidates_per_second,
        search_engine=search_engine,
        pareto=(
            _pareto_front(out, feasible, cuts_batch, config_space,
                          search_engine)
            if pareto
            else None
        ),
        quarantine=quarantine,
    )


def _pick(rows: np.ndarray, hs: np.ndarray, cs: np.ndarray) -> int:
    """Index of the winner among feasible (N, 4) metric rows at hardware
    indices ``hs`` and cut indices ``cs``: the least energy, ties broken
    by the lexicographic minimum of (bandwidth, latency, area, h, c)."""
    ties = np.flatnonzero(rows[:, 2] == rows[:, 2].min())
    if len(ties) > 1:
        t = rows[ties]
        ties = ties[
            np.lexsort((cs[ties], hs[ties], t[:, 3], t[:, 1], t[:, 0]))
        ]
    return int(ties[0])


def _flow_result(
    g: GraphIR,
    cuts_batch: np.ndarray,
    config_space: Sequence[DLAConfig],
    h: int,
    c: int,
    row: np.ndarray,  # (4,) the winner's metrics
    **fields,
) -> FlowResult:
    """The FlowResult of the winner (h, c): its point, cuts and groups."""
    labels = fusion.cut_group_labels(g, cuts_batch[c])
    sizes = tuple(len(grp) for grp in fusion.groups_from_labels(labels))
    return FlowResult(
        best_hw=config_space[h],
        best_cuts=cuts_batch[c],
        best_metrics=_metrics_from_row(row),
        group_sizes=sizes,
        **fields,
    )


def _compose_survivors(
    summary: M.PlaneSummary, hw_rows: np.ndarray
) -> list[tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]]:
    """Each graph's survivor rows from the pruned program's summary, as
    (metrics (n, 4), h (n,), c (n,), surely feasible (n,)).  Composing is
    elementwise, so each row's energy is bit-identical to its cell of the
    composed plane."""
    out = []
    for gi, n in enumerate(summary.n_survivors):
        hs, cs = summary.h[gi, :n], summary.c[gi, :n]
        rows = M.compose_metrics(summary.rows[gi, :n, None, :],
                                 hw_rows[hs])[:, 0]
        out.append((rows, hs, cs, summary.sure[gi, :n]))
    return out


def _survivor_flow_result(
    survivors: tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray],
    n_sure: int,
    cuts_batch: np.ndarray,
    g: GraphIR,
    config_space: Sequence[DLAConfig],
    constraints: Constraints,
    **fields,
) -> FlowResult:
    """:func:`_best_flow_result` over one graph's survivor rows: the
    undecided rows are decided here exactly, and every row the device
    classed surely feasible is feasible here too, so ``n_feasible`` is
    ``n_sure`` plus the undecided rows within the limits."""
    rows, hs, cs, sure = survivors
    feasible = np.all(rows <= constraints.as_row()[None, :], axis=-1)
    idx = np.flatnonzero(feasible)
    i = idx[_pick(rows[idx], hs[idx], cs[idx])]
    return _flow_result(
        g, cuts_batch, config_space, hs[i], cs[i], rows[i],
        n_feasible=int(n_sure) + int(np.sum(feasible & ~sure)),
        **fields,
    )


def groupings_batch(
    g: GraphIR,
    groupings: str | np.ndarray,
    *,
    sram_budget_words: float = float("inf"),
    with_provenance: bool = False,
) -> np.ndarray | tuple[np.ndarray, str]:
    """Resolve a groupings spec to a (C, E) boolean cut batch.

    ``"exhaustive"`` — all valid edge cuts (2^(L-1) on a chain);
    ``"pool"``       — the paper's pool-boundary policy + layer-by-layer;
    ``"search"``/``"dp"`` — the grouping search optimum (chain DP fast path,
    frontier DP — exact even on ResNet-scale DAGs — or beam fallback) +
    layer-by-layer + pool boundaries;
    or an explicit (C, E) bool array.  ``sram_budget_words`` is threaded
    into the search strategies so a budget-constrained flow searches under
    the same budget its prefilter enforces (a budget-blind optimum would
    just be pruned afterwards).  With ``with_provenance`` the batch comes
    back paired with the grouping provenance string (for "search"/"dp"
    the engine that produced the optimum, see
    :attr:`repro.core.fusion.DPResult.engine`).
    """

    def _ret(batch: np.ndarray, provenance: str):
        return (batch, provenance) if with_provenance else batch

    if not isinstance(groupings, str):
        return _ret(
            np.atleast_2d(np.asarray(groupings, dtype=bool)), "explicit"
        )
    if groupings == "exhaustive":
        try:
            return _ret(fusion.enumerate_valid_edge_cuts(g), "exhaustive")
        except ValueError as e:
            raise ValueError(
                f"{g.name}: {e}; pass groupings='search' for large graphs"
            ) from None
    if groupings == "pool":
        # np.unique-dedupe like the "search" path: on graphs where the pool
        # policy degenerates to layer-by-layer (e.g. every producer ends a
        # pooling stage) the duplicate row must not be scored twice.
        return _ret(
            np.unique(
                np.stack(
                    [g.pool_boundary_cuts(), fusion.layer_by_layer_cuts(g)]
                ),
                axis=0,
            ),
            "pool",
        )
    if groupings in ("dp", "search"):
        best = fusion.optimal_cuts(g, sram_budget_words=sram_budget_words)
        rows = [
            best.cuts,
            fusion.layer_by_layer_cuts(g),
            g.pool_boundary_cuts(),
        ]
        return _ret(np.unique(np.stack(rows), axis=0), best.engine)
    raise ValueError(groupings)


def run_flow(
    ir: NetworkIR | GraphIR,
    *,
    config_space: Sequence[DLAConfig] | None = None,
    constraints: Constraints = Constraints(),
    groupings: str | np.ndarray = "exhaustive",
    sram_budget_words: float = float("inf"),
    pareto: bool = False,
) -> FlowResult:
    """Sweep (hw x grouping), filter by constraints, return min-energy point.

    A one-graph :func:`run_fleet`: ``groupings`` is resolved by
    :func:`groupings_batch`, a finite ``sram_budget_words`` drops
    buffer-infeasible groupings before the sweep, and the graph is padded
    to its shape bucket, so graphs that share a bucket share one compiled
    executable.  ``compile_seconds`` is the XLA compile this call paid (0
    on an executable-cache hit, the ``fleet.compile`` span) and
    ``sweep_seconds`` the execution plus the device-to-host fetch (the
    ``fleet.execute`` and ``fleet.fetch`` spans).

    ``pareto=True`` additionally extracts the feasible sweep's
    (bandwidth, latency, energy, area) Pareto front into
    ``FlowResult.pareto`` (:class:`ParetoFront`).
    """
    fleet = run_fleet(
        [ir], config_space=config_space, constraints=constraints,
        groupings=groupings, sram_budget_words=sram_budget_words,
        pareto=pareto,
    )
    return dataclasses.replace(
        fleet.results[0], compile_seconds=fleet.compile_seconds
    )


@dataclasses.dataclass(frozen=True)
class FleetResult:
    """One multi-graph sweep: per-graph best points + shared-compile split."""

    results: tuple[FlowResult, ...]  # one FlowResult per input graph
    n_graphs: int
    n_candidates: int  # real (graph, hw, cut) triples across the fleet
    compile_seconds: float  # ONE compile amortised across the whole fleet
    # The (G, H, C) execution plus the device-to-host fetch of its result
    # (fleet.execute + fleet.fetch, summed over hw chunks).
    sweep_seconds: float
    candidates_per_second: float
    # Device layout the sweep ran on: 1 for the single-device program,
    # else the size of the 1-D `hardware` mesh the H axis was sharded over.
    device_count: int = 1
    # Fleet-wide finite-guard report (None when every raw cell was clean).
    quarantine: "QuarantineReport | None" = None
    # Salvage/resume accounting: chunks actually computed this call vs
    # restored from a sweep checkpoint (1/0 for an unchunked sweep), chunk
    # indices the straggler detector flagged, and whether a sick mesh was
    # degraded to the single-device program mid-call.
    chunks_computed: int = 1
    chunks_restored: int = 0
    straggler_chunks: tuple[int, ...] = ()
    mesh_degraded: bool = False

    def describe(self) -> str:
        """One-line summary of the fleet sweep (incl. mesh, if sharded)."""
        mesh = (
            f", {self.device_count}-device hardware mesh"
            if self.device_count > 1
            else ""
        )
        if self.mesh_degraded:
            mesh = ", mesh degraded to single-device"
        salvage = (
            f", {self.chunks_restored} chunks restored"
            if self.chunks_restored
            else ""
        )
        lines = [
            f"fleet of {self.n_graphs}: {self.n_candidates} candidates in "
            f"{self.sweep_seconds*1e3:.2f} ms "
            f"({self.candidates_per_second:,.0f} cand/s, one compile "
            f"{self.compile_seconds*1e3:.0f} ms{mesh}{salvage})"
        ]
        lines += [f"  {r.describe()}" for r in self.results]
        return "\n".join(lines)


@spans.span("fleet.call")
def run_fleet(
    irs: Sequence[NetworkIR | GraphIR],
    *,
    config_space: Sequence[DLAConfig] | None = None,
    constraints: Constraints = Constraints(),
    groupings: str | np.ndarray | Sequence[np.ndarray] = "search",
    sram_budget_words: float = float("inf"),
    devices=None,
    pareto: bool = False,
    hw_chunk: int | None = None,
    abort_check=None,
    retry_policy: RetryPolicy | None = None,
    checkpoint_dir=None,
    hooks=None,
) -> FleetResult:
    """Sweep many graphs' (hw x grouping) cross-products in ONE XLA program.

    Every graph is zero-padded to the fleet-wide ``(L, E, C)`` bucket
    (power-of-two, never below ``NODE_BUCKET_FLOOR`` etc.), stacked along
    a new leading axis, and evaluated by a single vmapped executable
    (:func:`repro.core.metrics.evaluate_fleet_graph`) — the whole fleet
    pays at most one XLA compile (0 on a bucket-cache hit), which is the
    serving-system move the per-model cold path cannot make.  Per-graph
    metrics are bit-identical to the unpadded sweep
    (:func:`repro.core.metrics.evaluate_batch_graph`; padded rows are
    exactly inert and sliced off before feasibility/argmin; asserted in
    tests).

    ``groupings`` / ``sram_budget_words`` / ``constraints`` apply to every
    graph — except that ``groupings`` may also be a *sequence* of explicit
    per-graph cut batches (one (C_i, E_i) bool array per input graph), the
    form the planning service uses to sweep a micro-batch of requests
    whose deadline ladders resolved to different engines.  The SRAM
    prefilter runs per graph on the padded cut rows
    (:func:`repro.core.fusion.padded_feasible_mask_batch`).  Returns a
    :class:`FleetResult` whose ``results[i]`` is graph ``i``'s
    :class:`FlowResult`; the shared compile is reported fleet-level, so
    per-graph ``compile_seconds`` is 0, and per-graph ``sweep_seconds`` /
    ``candidates_per_second`` describe the one shared execution (every
    member reports the fleet-wide throughput, not its own slice of it).

    ``devices`` shards the sweep's hardware axis over a 1-D ``hardware``
    mesh (:func:`repro.parallel.sharding.hardware_mesh`): ``None`` keeps
    the single-device program; an int takes the first N visible devices;
    a device sequence is used as given.  H is padded to a device-count
    multiple with copies of config 0 — inert rows sliced off before
    metrics composition, the PR 4 padding idiom on the hardware axis — and
    each device evaluates its H-shard locally.  Where the sweep prunes
    (below), each device also classes its shard and only one summary of
    the whole plane crosses the mesh
    (:func:`repro.core.metrics.sharded_pruned_fleet_kernel`); otherwise
    the (G, H, C, 5) raw plane comes back in one cross-device gather and
    the per-graph argmin/Pareto run on the host over the whole plane.
    Either way sharded results are **bit-identical** at any device count
    (asserted at 1/2/3/4/8 host devices in tests/test_multidevice.py).
    The executable cache keys on the mesh fingerprint, so per-layout
    programs never collide (``sweep_cache_stats()["entries"]``).

    Where only each graph's pick is asked for — ``pareto=False`` and no
    ``hw_chunk``, on one device or a mesh — and the float32 bound holds
    (:func:`repro.core.metrics.prune_applies`), the sweep program also
    prunes the plane on the device (:func:`repro.core.metrics.
    _evaluate_fleet_graph_pruned`) and only its summary is fetched: the
    counts, and at most :data:`~repro.core.metrics.PRUNE_ROWS` raw rows a
    graph that can still win.  The host composes their energy in float64
    and picks with the same rule as the whole-plane path, so the result
    is bit-identical to it.  A plane with a cell the finite guard could
    flag, more survivors than the summary holds, or a graph with no
    surely-feasible cell is fetched whole and takes the whole-plane path.

    ``pareto=True`` extracts each workload's feasible-sweep Pareto front
    over (bandwidth, latency, energy, area) into ``results[i].pareto`` —
    with a :func:`repro.core.arch.config_space_grid` design space this is
    the LoopTree-style explorer output: thousands of
    (architecture x fusion plan) points scored per workload, reduced to
    the non-dominated set.

    Example — two workloads, default space, per-workload fronts::

        >>> from repro.core import flow
        >>> from repro.core.ir import residual_block_ir, resnet18_ir
        >>> fl = flow.run_fleet([residual_block_ir(), resnet18_ir()],
        ...                     groupings="search", pareto=True)
        >>> len(fl.results), fl.device_count
        (2, 1)
        >>> r = fl.results[1]                    # resnet18's FlowResult
        >>> r.search_engine, r.best_cuts.dtype.name
        ('frontier_dp', 'bool')
        >>> r.pareto.metrics.shape[1]            # (bw, latency, energy, area)
        4

    ``hw_chunk`` splits the sweep into resumable slices of the hardware
    axis: the fleet program runs once per ≤``hw_chunk``-row slice of the
    config space and the raw (G, h, C, 5) planes are reassembled before
    metrics composition.  Every raw row is an exact per-candidate f64
    quantity (energy is composed on the host, in numpy), so the chunked
    sweep is **bit-identical** to the unchunked one — chunking only creates
    preemption points.  ``abort_check`` (a zero-arg callable) is invoked
    before each chunk; raising from it abandons the remaining chunks,
    which is how the planning service implements cooperative cancellation
    and deadline enforcement at sweep-chunk granularity without ever
    killing a kernel mid-flight.  ``hw_chunk`` cannot be combined with
    ``devices`` (the sharded program already splits H across the mesh).

    Each call is one ``fleet.call`` span (:mod:`repro.core.spans`); its
    stages are child spans that cover it end to end: ``fleet.prepare``,
    ``fleet.compile`` (executable-cache misses only), ``fleet.execute``
    and ``fleet.fetch`` (once per hw chunk), ``fleet.compose``,
    ``fleet.guard`` and ``fleet.select``.  ``compile_seconds`` and
    ``sweep_seconds`` are the durations of those spans.  ``fleet.fetch``'s
    ``work`` is the bytes it copied (a second fetch of the whole plane
    where the pruned path falls back), and ``fleet.select``'s the
    candidate rows the host selection read: the survivors, or H x C a
    graph on the whole-plane path.

    Fault tolerance (all off by default):

    * ``retry_policy`` (:class:`repro.core.errors.RetryPolicy`) retries
      each chunk's compile+execute on non-evaluator failures with
      exponential backoff; exhaustion raises a typed
      :class:`~repro.core.errors.TransientFailure`.  On the sharded
      (``devices=``) path, exhaustion instead *degrades*: the sweep falls
      back down :func:`repro.runtime.elastic.sweep_degradation_ladder`
      to the single-device program — bit-identical results, only slower
      (``FleetResult.mesh_degraded`` records it).
    * ``checkpoint_dir`` (requires ``hw_chunk``) persists every completed
      chunk's raw plane through the journal's bit-exact codecs
      (:class:`repro.checkpoint.SweepCheckpoint`); a killed sweep re-run
      with the same arguments restores completed chunks and recomputes
      only the missing ones (``chunks_restored``/``chunks_computed``) —
      the resumed :class:`FleetResult` is bit-identical to an unkilled
      run.  The checkpoint is keyed by a fingerprint of the full argument
      set, so a different sweep can never splice in stale planes.
    * Per-chunk wall times feed a running-median straggler detector
      (:class:`repro.runtime.fault_tolerance.StragglerDetector`); flagged
      chunk indices are reported in ``FleetResult.straggler_chunks``.
    * ``hooks`` is a duck-typed fault seam (``before_chunk_compute(i,
      device_count=...)`` may raise to simulate a shard/compile failure;
      ``poison_plane(plane, h0)`` may corrupt a raw plane) used by
      :class:`repro.testing.faults.FaultInjector`; every raw plane then
      passes the finite guard, so injected NaN/Inf/negative/overflow
      cells are quarantined with (g, h, c) provenance
      (``FleetResult.quarantine``) and can never win the argmin or enter
      a Pareto front.  On the pruned path the hook is handed the plane
      still on the device, as a read-only :class:`DevicePlane`; returning
      that same object keeps the pruned path, and returning anything else
      runs the whole-plane path on what was returned.

    Example — per-graph explicit cut batches (the service/bench form) and
    a sharded hardware axis::

        >>> import numpy as np
        >>> gs = [residual_block_ir(), resnet18_ir()]
        >>> batches = [np.stack([np.ones(g.n_edges, bool),    # layer-by-layer
        ...                      np.zeros(g.n_edges, bool)])  # fully fused
        ...            for g in gs]
        >>> fl = flow.run_fleet(gs, groupings=batches, devices=1)
        >>> [len(r.group_sizes) for r in fl.results]  # groups of best cuts
        [1, 1]
    """
    if not irs:
        raise ValueError("empty fleet")
    if hw_chunk is not None:
        if devices is not None:
            raise ValueError(
                "hw_chunk cannot be combined with devices: the sharded "
                "program already splits the hardware axis across the mesh"
            )
        if hw_chunk <= 0:
            raise ValueError(f"hw_chunk must be positive, got {hw_chunk}")
    if checkpoint_dir is not None and hw_chunk is None:
        raise ValueError(
            "checkpoint_dir requires hw_chunk: completed hardware-axis "
            "chunks are the checkpoint grain"
        )
    if config_space is None:
        config_space = default_config_space()
    with spans.span("fleet.prepare"):
        graphs = [as_graph(ir) for ir in irs]

        # ``groupings`` may be one spec shared by the whole fleet, or a
        # per-graph sequence of explicit (C_i, E_i) cut batches (the planning
        # service resolves each request's grouping through its deadline ladder
        # and sweeps the mixed batch as one fleet program).
        if isinstance(groupings, (list, tuple)):
            if len(groupings) != len(graphs):
                raise ValueError(
                    f"{len(groupings)} grouping specs for {len(graphs)} graphs"
                )
            specs = list(groupings)
        else:
            specs = [groupings] * len(graphs)

        # Per-graph grouping resolution + SRAM prefilter (padded-E cut rows).
        edge_bucket = bucket_size(
            max(g.n_edges for g in graphs), EDGE_BUCKET_FLOOR
        )
        node_bucket = bucket_size(
            max(g.n_nodes for g in graphs), NODE_BUCKET_FLOOR
        )
        padded = [pad_graph(g, n_nodes=node_bucket, n_edges=edge_bucket)
                  for g in graphs]
        cuts: list[np.ndarray] = []
        pruned: list[int] = []
        provenances: list[str] = []
        for g, pg, spec in zip(graphs, padded, specs):
            cb, provenance = groupings_batch(
                g, spec, sram_budget_words=sram_budget_words,
                with_provenance=True,
            )
            cb = pad_cuts_batch(cb, edge_bucket)
            provenances.append(provenance)
            n_pruned = 0
            if np.isfinite(sram_budget_words):
                max_int = fusion.padded_max_intermediate_batch(pg, cb)
                keep = max_int <= sram_budget_words
                n_pruned = int(cb.shape[0] - keep.sum())
                if not keep.any():
                    raise InfeasibleBudgetError(
                        f"{g.name}: no grouping fits the SRAM budget "
                        f"({sram_budget_words:.0f} words; the cheapest "
                        f"offered grouping needs {max_int.min():.0f})",
                        min_feasible_budget_words=float(max_int.min()),
                    )
                cb = cb[keep]
            cuts.append(cb)
            pruned.append(n_pruned)
        counts = [cb.shape[0] for cb in cuts]
        cut_bucket = bucket_size(max(counts), CUT_BUCKET_FLOOR)
        cuts = [pad_cuts_batch(cb, edge_bucket, cut_bucket) for cb in cuts]

        hw_rows = np.stack([c.as_row() for c in config_space])
        area_consts = M.area_consts_of_space(config_space)
        H = hw_rows.shape[0]

        # Device layout: single-device vmapped program, or the same kernel
        # shard_mapped over a 1-D `hardware` mesh with H padded to a
        # device-count multiple (padded rows are copies of config 0 — fully
        # valid arithmetic, sliced off below before metrics composition).
        mesh_key = _SINGLE_MESH_KEY
        hw_swept = hw_rows
        # Prune on the device where only the pick is asked for: the Pareto
        # front needs every feasible row, and chunked sweeps keep their
        # raw planes.
        prune = (not pareto and hw_chunk is None
                 and M.prune_applies(hw_rows, area_consts))
        if devices is None:
            kernel = M._jit_fleet_graph_pruned if prune else M._jit_fleet_graph
        else:
            mesh = hardware_mesh(devices)
            kernel = (M.sharded_pruned_fleet_kernel(mesh) if prune
                      else M.sharded_fleet_kernel(mesh))
            mesh_key = mesh_fingerprint(mesh)
            D = int(mesh.devices.size)
            H_padded = -(-H // D) * D
            if H_padded > H:
                hw_swept = np.concatenate(
                    [hw_rows, np.repeat(hw_rows[:1], H_padded - H, axis=0)]
                )

        args = (
            np.stack([pg.feat for pg in padded]),
            np.stack([pg.esrc for pg in padded]),
            np.stack([pg.edst for pg in padded]),
            np.stack([pg.ewords for pg in padded]),
            np.stack([pg.src_mask for pg in padded]),
            np.stack([pg.sink_mask for pg in padded]),
            np.stack(cuts),
            hw_swept,
            area_consts,
            np.stack([pg.node_mask for pg in padded]),
            np.stack([pg.edge_mask for pg in padded]),
        )
        if prune:
            args += (np.asarray(counts, np.int32),
                     *M.prune_limits(constraints.as_row()))
            if devices is not None:
                args += (np.int32(H),)
        # f64-exactness guard on the giant-config feature tables (llama4 /
        # arctic edge words reach ~1e10 — far below 2^53, but a corrupted or
        # overflowed table must fail loudly before the sweep, not split ulps
        # silently inside it).
        M.assert_exact_f64(args[0], what="fleet feature table")
        M.assert_exact_f64(args[3], what="fleet edge words")
    if abort_check is not None:
        abort_check()

    hook_before = (
        getattr(hooks, "before_chunk_compute", None)
        if hooks is not None else None
    )
    hook_poison = (
        getattr(hooks, "poison_plane", None) if hooks is not None else None
    )
    sweep_device_count = 1 if devices is None else int(mesh.devices.size)

    def _compute(chunk_index, c_args, c_kernel, c_mesh_key, h0, d_count):
        """One chunk's compile+execute, under the retry policy + hooks."""

        def attempt():
            if hook_before is not None:
                hook_before(chunk_index, device_count=d_count)
            exe, dt_c = _compiled_sweep(c_kernel, c_args, mesh_key=c_mesh_key)
            plane, dt_s = _run_sweep(exe, c_args)
            return plane, dt_c, dt_s

        if retry_policy is None:
            plane, dt_c, dt_s = attempt()
        else:
            plane, dt_c, dt_s = retry_policy.call(
                attempt, describe=f"hw chunk {chunk_index}"
            )
        if hook_poison is not None:
            tapped = hook_poison(plane, h0)
            if tapped is not plane and isinstance(plane, DevicePlane):
                plane.release()  # the hook's own plane replaces it
            plane = tapped
        return plane, dt_c, dt_s

    mesh_degraded = False
    chunks_restored = 0
    straggler_chunks: tuple[int, ...] = ()
    if hw_chunk is None:
        chunks_computed = 1
        try:
            raw, compile_seconds, sweep_seconds = _compute(
                0, args, kernel, mesh_key, 0, sweep_device_count
            )
        except TransientFailure:
            from ..runtime.elastic import sweep_degradation_ladder

            ladder = sweep_degradation_ladder(devices)[1:]
            if not ladder:
                raise
            # The mesh is sick (compile/execute kept failing through the
            # retry budget): degrade to the ladder's single-device rung.
            # The sharded kernel is row-parallel with no cross-row
            # reduction, and the sharded pruned program's summary equals
            # the single-device one, so the salvaged result is
            # bit-identical to the mesh sweep — the fallback trades
            # throughput, never answers.
            mesh_degraded = True
            mesh_key = _SINGLE_MESH_KEY
            if prune:  # the single-device program takes no n_hw
                kernel, args = M._jit_fleet_graph_pruned, args[:-1]
            else:
                kernel = M._jit_fleet_graph
            args = args[:7] + (hw_rows,) + args[8:]
            raw, compile_seconds, sweep_seconds = _compute(
                0, args, kernel, mesh_key, 0, 1
            )
    else:
        # Resumable chunked sweep: one program per ≤hw_chunk-row slice of
        # the config space, abort_check between slices.  Raw rows are
        # per-candidate-exact, so the reassembled plane is bit-identical
        # to the single-program sweep.  With ``checkpoint_dir`` every
        # completed plane is durable before the loop advances, so a kill
        # at ANY boundary resumes with exactly-once recomputation.
        from ..runtime.fault_tolerance import StragglerDetector

        restored: dict[int, np.ndarray] = {}
        ckpt = None
        if checkpoint_dir is not None:
            from ..checkpoint import SweepCheckpoint, sweep_fingerprint

            ckpt = SweepCheckpoint(checkpoint_dir)
            restored = ckpt.load(sweep_fingerprint(args, hw_chunk))
        detector = StragglerDetector(min_deadline_s=0.0)
        call = spans.current()  # this call's fleet.call span
        compile_seconds = sweep_seconds = 0.0
        chunks_computed = 0
        stragglers: list[int] = []
        planes = []
        for ci, h0 in enumerate(range(0, H, hw_chunk)):
            if abort_check is not None and h0:
                abort_check()
            plane = restored.get(h0)
            if plane is not None:
                planes.append(plane)
                chunks_restored += 1
                continue
            chunk_args = (
                args[:7] + (hw_rows[h0:h0 + hw_chunk],) + args[8:]
            )
            seen = len(call.children)
            plane, dt_c, dt_s = _compute(
                ci, chunk_args, kernel, mesh_key, h0, sweep_device_count
            )
            # Straggler detection on the chunk's execute + fetch spans,
            # retried attempts included, compile left out (a cold cache
            # is not a sick worker); the detector needs 5 samples before
            # it flags, so early chunks only seed the median.
            dt_wall = sum(r.seconds for r in call.children[seen:]
                          if r.name != "fleet.compile")
            if detector.is_straggler(dt_wall):
                stragglers.append(ci)
            detector.observe(dt_wall)
            if ckpt is not None:
                ckpt.append_chunk(h0, plane)
            planes.append(plane)
            chunks_computed += 1
            compile_seconds += dt_c
            sweep_seconds += dt_s
        straggler_chunks = tuple(stragglers)
        raw = np.concatenate(planes, axis=1)
    n_cand = H * sum(counts)
    survivors = None
    if isinstance(raw, DevicePlane):
        view = raw
        try:
            if view.decides:
                summary = view.summary
                with spans.span("fleet.compose"):
                    survivors = _compose_survivors(summary, hw_rows)
            else:
                # Quarantine needs every poisoned cell's provenance, and an
                # undecided pick needs every row: take the whole plane.
                with spans.span("fleet.fetch") as fe:
                    raw = np.asarray(view)
                    fe.work = raw.nbytes
                sweep_seconds += fe.record.seconds
        finally:
            view.release()
    fleet_cells: list[QuarantinedCell] = []
    g_poisons: list[np.ndarray | None] = [None] * len(graphs)
    g_quars: list[QuarantineReport | None] = [None] * len(graphs)
    if survivors is not None:
        with spans.span("fleet.guard"):
            # The device counted every cell the guard could flag; a plane
            # with any took the whole-plane path.
            assert not summary.n_poison.any()
    else:
        with spans.span("fleet.compose"):
            out = M.compose_metrics(raw[:, :H], hw_rows)  # (G, H, C_b, 4)
        # Finite guard over the whole fleet's raw plane: poisoned cells are
        # quarantined per graph before any argmin/Pareto selection.
        with spans.span("fleet.guard"):
            poison_all = M.poison_mask(raw[:, :H])  # (G, H, C_b)
            if poison_all.any():
                for gi in range(len(graphs)):
                    pm = poison_all[gi, :, :counts[gi]]
                    if pm.any():
                        cells = _quarantine_cells(
                            raw[gi, :H, :counts[gi]], pm, graph=gi
                        )
                        g_quars[gi] = QuarantineReport(cells=cells)
                        fleet_cells.extend(cells)
                        g_poisons[gi] = pm
    fleet_cps = n_cand / max(sweep_seconds, 1e-9)
    with spans.span("fleet.select") as sel:
        sel.work = (n_cand if survivors is None
                    else int(summary.n_survivors.sum()))
        results = []
        for gi, g in enumerate(graphs):
            C = counts[gi]
            fields = dict(
                n_pruned=pruned[gi],
                compile_seconds=0.0,  # the one fleet compile, see above
                sweep_seconds=sweep_seconds,
                candidates_per_second=fleet_cps,  # the shared rate
                search_engine=provenances[gi],
            )
            batch = cuts[gi][:C, : g.n_edges]
            if survivors is not None:
                results.append(_survivor_flow_result(
                    survivors[gi], summary.n_sure[gi], batch, g,
                    config_space, constraints, n_candidates=H * C,
                    **fields))
            else:
                results.append(_best_flow_result(
                    out[gi, :, :C],  # padded candidate rows sliced off
                    batch, g, config_space, constraints,
                    err_prefix=f"{g.name}: ",
                    pareto=pareto,
                    poison=g_poisons[gi],
                    quarantine=g_quars[gi],
                    **fields,
                ))
    return FleetResult(
        results=tuple(results),
        n_graphs=len(graphs),
        n_candidates=n_cand,
        compile_seconds=compile_seconds,
        sweep_seconds=sweep_seconds,
        candidates_per_second=fleet_cps,
        device_count=1 if mesh_degraded else sweep_device_count,
        quarantine=(
            QuarantineReport(cells=tuple(fleet_cells))
            if fleet_cells
            else None
        ),
        chunks_computed=chunks_computed,
        chunks_restored=chunks_restored,
        straggler_chunks=straggler_chunks,
        mesh_degraded=mesh_degraded,
    )


@dataclasses.dataclass(frozen=True)
class FusionComparison:
    """Layer-by-layer vs fused metrics for one (network, hw) — the paper's
    headline Sec. III numbers."""

    lbl: M.Metrics
    fused: M.Metrics
    bw_reduction: float
    latency_reduction: float
    energy_reduction: float

    def describe(self) -> str:
        """Three-line lbl -> fused table with percentage reductions."""
        return (
            f"BW  {self.lbl.bandwidth_words/1e6:8.2f}M -> {self.fused.bandwidth_words/1e6:8.2f}M  (-{self.bw_reduction*100:5.1f}%)\n"
            f"lat {self.lbl.latency_cycles/1e6:8.2f}M -> {self.fused.latency_cycles/1e6:8.2f}M  (-{self.latency_reduction*100:5.1f}%)\n"
            f"E   {self.lbl.energy_nj/1e6:8.2f}mJ-> {self.fused.energy_nj/1e6:8.2f}mJ (-{self.energy_reduction*100:5.1f}%)"
        )


def compare_fusion(
    ir: NetworkIR | GraphIR,
    hw: DLAConfig,
    fused_cuts: np.ndarray | None = None,
) -> FusionComparison:
    """Evaluate the paper's fused-vs-layer-by-layer comparison on ``ir``."""
    g = as_graph(ir)
    if fused_cuts is None:
        fused_cuts = g.pool_boundary_cuts()
    lbl_cuts = fusion.layer_by_layer_cuts(g)
    lbl = M.evaluate_ref(g, lbl_cuts, hw)
    fus = M.evaluate_ref(g, fused_cuts, hw)
    return FusionComparison(
        lbl=lbl,
        fused=fus,
        bw_reduction=1.0 - fus.bandwidth_words / lbl.bandwidth_words,
        latency_reduction=1.0 - fus.latency_cycles / lbl.latency_cycles,
        energy_reduction=1.0 - fus.energy_nj / lbl.energy_nj,
    )
