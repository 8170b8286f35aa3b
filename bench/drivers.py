"""The general traffic generator: one loop per kind of traffic, each driven
by the parameters of a ``traffic/<mix>.json`` file.

* ``slices`` closed loop of exhaustive co-search jobs: every valid grouping
  of the graph x every hardware point, sent through ``flow.run_fleet`` in
  fixed slices of ``c_slice`` groupings, the slices' winners merged;
* ``plans``  closed loop of whole plans: trace -> grouping search ->
  ``run_fleet`` with the Pareto front, one prefill length a plan;
* ``serve``  open loop of plan requests into ``AsyncPlanningService``,
  arrivals fixed by the seed, each request timed from its due time.

Each loop builds its inputs from the seed, warms up exactly the shapes its
window will use, runs the window, and afterwards checks what the window
produced against the plain reference (:mod:`reference`), which imports
nothing of the program.  ``check`` returns the numbers compared; their
limits are in ``limits.json``.
"""
from __future__ import annotations

import gc
import math
import threading
import time

import numpy as np

import reference as R
from harness import BenchError, percentile, say


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------


def rel_gap(got, want) -> float:
    """Largest |got - want| / max(|want|, 1) over all entries."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.size == 0:
        return 0.0
    with np.errstate(invalid="ignore"):
        d = np.abs(got - want) / np.maximum(np.abs(want), 1.0)
    d = np.where(np.isnan(d), np.inf, d)
    return float(d.max())


class Checks:
    """The numbers one run compares, each against its limit in
    ``limits.json``."""

    def __init__(self, cells: bool = True):
        self.v = {"cells_rel_gap": 0.0} if cells else {}
        self.v.update(winner_rel_gap=0.0, picks_wrong=0, inputs_diffs=0)

    def gap(self, name: str, got, want) -> None:
        self.v[name] = max(self.v[name], rel_gap(got, want))

    def count(self, name: str, n: int = 1) -> None:
        self.v[name] += int(n)


class PlaneTap:
    """Read-only use of ``run_fleet``'s ``hooks`` seam: keeps a seeded
    sample of the raw (G, H, C, 5) plane each sweep computed, and returns
    the plane unchanged."""

    def __init__(self, rng, per_call: int):
        self.rng = rng
        self.per_call = per_call
        self.samples = []
        self._n_hw = self._n_cuts = None

    def arm(self, n_hw: int, n_cuts):
        """Next planes hold ``n_hw`` real points and ``n_cuts[g]`` real
        groupings for graph g."""
        self._n_hw, self._n_cuts = n_hw, list(n_cuts)

    def poison_plane(self, plane, h0):
        for gi, nc in enumerate(self._n_cuts):
            k = self.per_call
            h = self.rng.integers(0, self._n_hw, k)
            c = self.rng.integers(0, nc, k)
            self.samples.append((gi, h + h0, c, np.array(plane[gi, h, c])))
        return plane


class Driver:
    """What the loops share: the program's design space and constraints,
    the reference's, and the counters the readers see."""

    def __init__(self, *, seed, config, config_mod, traffic, spans, out):
        self.seed, self.config = int(seed), config
        self.config_mod, self.traffic = config_mod, traffic
        self.spans, self.out = spans, out
        self.counters = {"attempted": 0, "failed": 0}
        self.ds = config["design_space"]

    # -- the program's side ------------------------------------------------

    def program_space(self):
        from repro.core import arch

        ds = self.ds
        return arch.config_space_grid(
            styles=ds["styles"], f1s=ds["f1"], f2s=ds["f2"], f3s=ds["f3"],
            f4s=ds["f4"], bus_widths=ds["bus_widths"],
            sram_splits=[name for name, _ in ds["sram_splits"]],
            pe_energy=ds["pe_energy"])

    @staticmethod
    def program_constraints(c: dict):
        from repro.core.arch import Constraints

        return Constraints(c["max_bandwidth_words"], c["max_latency_cycles"],
                           c["max_energy_nj"], c["max_area_um2"])

    def compiles(self) -> int:
        from repro.core import flow

        return int(flow.sweep_cache_stats()["misses"])

    def release(self) -> None:
        """Free the program's compiled sweeps before the reference runs."""
        from repro.core import flow

        flow.clear_sweep_cache()
        gc.collect()

    # -- the reference's side ----------------------------------------------

    def ref_space(self):
        return R.design_space(self.ds), R.area_constants(self.ds)

    def check_space(self, checks: Checks, grid, hw_ref) -> None:
        from repro.core import metrics as M

        hw_prog = np.stack([c.as_row() for c in grid])
        if hw_prog.shape != hw_ref.shape:
            checks.count("inputs_diffs", 1 + abs(len(hw_prog) - len(hw_ref)))
            return
        checks.count("inputs_diffs", int(np.sum(hw_prog != hw_ref)))
        area = M.area_consts_of(grid[0])
        checks.count("inputs_diffs", int(np.sum(
            area != np.asarray(R.area_constants(self.ds)))))


def limits_row(c: dict) -> np.ndarray:
    return np.asarray([c["max_bandwidth_words"], c["max_latency_cycles"],
                       c["max_energy_nj"], c["max_area_um2"]], np.float64)


def metrics_row(m) -> tuple:
    return (m.bandwidth_words, m.latency_cycles, m.energy_nj, m.area_um2)


def merge(winners):
    """The job's pick from its slices' picks, each given as (key, record)
    with the key of :func:`reference.key` over the job position of its
    grouping: ``run_fleet``'s own order (energy, bw, lat, area, h, c)."""
    return min(winners, key=lambda kr: kr[0]) if winners else None


def index_of_row(batch: np.ndarray, row: np.ndarray) -> int:
    hit = np.flatnonzero(np.all(batch == row[None, :], axis=1))
    return int(hit[0]) if len(hit) else -1


# ---------------------------------------------------------------------------
# slices: exhaustive co-search jobs
# ---------------------------------------------------------------------------


class SliceLoop(Driver):
    """Closed loop of exhaustive co-search jobs.  A job is every valid
    grouping x every hardware point, in an order drawn from the seed, sent
    as slices of ``c_slice`` groupings; the slices' winners are merged in
    ``run_fleet``'s own tie order."""

    def setup(self):
        from repro.core import flow, fusion

        t = self.traffic
        with self.spans.span("bench.trace"):
            self.g = self.config_mod.program_graph(self.config["model"])
        self.grid = self.program_space()
        self.hw_index = {c: i for i, c in enumerate(self.grid)}
        self.cons = self.program_constraints(self.config["constraints"])
        self.cuts = np.array(fusion.enumerate_valid_edge_cuts(self.g))
        self.C = int(t["c_slice"])
        if len(self.cuts) % self.C:
            raise BenchError(f"{len(self.cuts)} groupings do not split into "
                             f"slices of {self.C}")
        self.n_slices = len(self.cuts) // self.C
        self.devices = t.get("devices")
        self.rng = np.random.default_rng(self.seed)
        self.tap = PlaneTap(np.random.default_rng([self.seed, 1]),
                            int(t["sample_cells_per_call"]))
        self.calls, self.jobs = [], []
        warm = np.random.default_rng([self.seed, 2]).permutation(len(self.cuts))
        for s in range(int(t["warmup_calls"])):
            self._call(self.cuts[warm[s * self.C:(s + 1) * self.C]],
                       tap=False)
        self.counters["compiles_setup"] = self.compiles()

    def _call(self, batch, tap=True):
        from repro.core import flow
        from repro.core.errors import InfeasibleConstraintsError

        if tap:
            self.tap.arm(len(self.grid), [len(batch)])
        try:
            fl = flow.run_fleet(
                [self.g], config_space=self.grid, constraints=self.cons,
                groupings=[batch], pareto=False,
                hooks=self.tap if tap else None, devices=self.devices)
        except InfeasibleConstraintsError:
            return None
        return fl

    def run(self, seconds):
        H = len(self.grid)
        c0 = self.compiles()
        t0 = time.perf_counter()
        end = t0 + seconds
        cand = 0
        job = 0
        while time.perf_counter() < end:
            perm = self.rng.permutation(len(self.cuts))
            self.jobs.append({"perm": perm, "winner": None})
            won = []
            for s in range(self.n_slices):
                if time.perf_counter() >= end:
                    break
                batch = self.cuts[perm[s * self.C:(s + 1) * self.C]]
                self.counters["attempted"] += 1
                with self.spans.span("bench.run_fleet"):
                    ta = time.perf_counter()
                    fl = self._call(batch)
                    tb = time.perf_counter()
                rec = {"job": job, "slice": s, "wall": tb - ta,
                       "sample": len(self.tap.samples) - 1, "pick": None}
                if fl is not None:
                    r = fl.results[0]
                    c = index_of_row(batch, r.best_cuts)
                    rec.update(compile_s=fl.compile_seconds,
                               sweep_s=fl.sweep_seconds,
                               pick=(self.hw_index[r.best_hw], c),
                               metrics=metrics_row(r.best_metrics))
                    won.append((R.key(rec["metrics"], rec["pick"][0],
                                      s * self.C + c), rec))
                else:
                    rec.update(compile_s=0.0, sweep_s=0.0)
                self.calls.append(rec)
                cand += H * len(batch)
            else:
                with self.spans.span("bench.merge"):
                    best = merge(won)
                self.jobs[-1]["winner"] = (
                    None if best is None else
                    {"pick": (best[0][4], best[0][5]),
                     "metrics": best[1]["metrics"]})
            job += 1
        t1 = time.perf_counter()
        self.counters.update(
            compiles_in_window=self.compiles() - c0, candidates=cand,
            calls=self.calls, window_s=t1 - t0,
            shape=(self.g.n_nodes, self.g.n_edges, H, self.C))
        return {"sweep_cand_per_s": cand / (t1 - t0)}

    def check(self, control: bool = False) -> dict:
        ch = Checks()
        ref_g = self.config_mod.reference_graph(self.config, R)
        ch.count("inputs_diffs", self.config_mod.trace_diffs(
            self.config, self.g, ref_g, R))
        hw_ref, area = self.ref_space()
        self.check_space(ch, self.grid, hw_ref)
        distinct = len(np.unique(self.cuts, axis=0))
        if ref_g.n_edges == ref_g.n_nodes - 1:  # a chain: every cut is valid
            ch.count("inputs_diffs", abs((1 << ref_g.n_edges) - distinct))
        ch.count("inputs_diffs", len(self.cuts) - distinct)
        ev = R.Evaluator(ref_g, hw_ref, area)
        lim = limits_row(self.config["constraints"])
        dt = np.float32 if control else np.float64
        # (1) the sampled raw cells of every call
        for rec in self.calls:
            gi, h, c, got = self.tap.samples[rec["sample"]]
            perm = self.jobs[rec["job"]]["perm"]
            rows = self.cuts[perm[rec["slice"] * self.C + c]]
            want = ev.raw_pairs(rows, h)
            if control:
                got = ev.raw_pairs(rows, h, dt)
            ch.gap("cells_rel_gap", got, want)
        # (2) every call's pick and every finished job's merged pick.  Eq. (1)
        # depends on the grouping alone, so a grouping over the bandwidth
        # bound is infeasible at every point: the block leaves those out.
        terms = ev.cut_terms(self.cuts)
        keep = np.flatnonzero(terms["bw"] <= lim[0])
        blocks = {np.float64: ev.metric_block(self.cuts[keep])}
        if control:
            blocks[dt] = ev.metric_block(self.cuts[keep], dtype=dt)
        for job_i, job in enumerate(self.jobs):
            pos = np.empty(len(self.cuts), np.int64)
            pos[job["perm"]] = np.arange(len(self.cuts))
            order = np.argsort(pos[keep])
            jpos = pos[keep][order]  # job positions, ascending
            ordered = {d: b[:, order] for d, b in blocks.items()}
            bounds = np.searchsorted(
                jpos, np.arange(self.n_slices + 1) * self.C)
            for rec in (r for r in self.calls if r["job"] == job_i):
                s = rec["slice"]
                a, b = bounds[s], bounds[s + 1]
                want = self._pick(ordered[np.float64][:, a:b], jpos[a:b], lim)
                if control:
                    got = self._pick(ordered[dt][:, a:b], jpos[a:b], lim)
                elif rec["pick"] is None:
                    got = None
                else:
                    got = (rec["pick"][0], s * self.C + rec["pick"][1],
                           rec["metrics"])
                self._compare(ch, got, want)
            if job["winner"] is not None:
                want = self._pick(ordered[np.float64], jpos, lim)
                got = (self._pick(ordered[dt], jpos, lim) if control else
                       job["winner"]["pick"] + (job["winner"]["metrics"],))
                self._compare(ch, got, want)
        return ch.v

    @staticmethod
    def _pick(block, cpos, lim):
        """(h, job position, metrics) of the pick in a (H, K, 4) block whose
        columns sit at job positions ``cpos`` (ascending)."""
        p = R.pick(block, lim)
        if p is None:
            return None
        h, k = p
        return (h, int(cpos[k]), tuple(block[h, k].tolist()))

    @staticmethod
    def _compare(ch: Checks, got, want) -> None:
        if got is None or want is None:
            ch.count("picks_wrong", int((got is None) != (want is None)))
            return
        ch.count("picks_wrong", int(got[:2] != want[:2]))
        ch.gap("winner_rel_gap", got[2], want[2])


# ---------------------------------------------------------------------------
# plans: closed loop of whole plans
# ---------------------------------------------------------------------------


class PlanLoop(Driver):
    """Closed loop of plans: trace the model at a prefill length, search
    its groupings, sweep them over the design space with the Pareto front.
    Every seed plans the same lengths, in an order of its own."""

    def setup(self):
        self.grid = self.program_space()
        self.hw_index = {c: i for i, c in enumerate(self.grid)}
        self.cons = self.program_constraints(self.traffic["constraints"])
        self.seq_lens = [int(s) for s in self.traffic["seq_lens"]]
        self.rng = np.random.default_rng(self.seed)
        self.tap = PlaneTap(np.random.default_rng([self.seed, 1]),
                            int(self.traffic["sample_cells_per_call"]))
        self.plans = []
        for s in self.seq_lens:  # every length's trace, search and sweep
            self._plan(s, tap=False)
        self.counters["compiles_setup"] = self.compiles()

    def _plan(self, seq_len, tap=True):
        from repro.core import flow

        sp = self.spans
        with sp.span("bench.trace"):
            g = self.config_mod.program_graph(self.config["model"],
                                              seq_len=seq_len)
        with sp.span("bench.search"):
            batch, engine = flow.groupings_batch(g, "search",
                                                 with_provenance=True)
        if tap:
            self.tap.arm(len(self.grid), [len(batch)])
        with sp.span("bench.run_fleet"):
            ta = time.perf_counter()
            fl = flow.run_fleet([g], config_space=self.grid,
                                constraints=self.cons, groupings=[batch],
                                pareto=True, hooks=self.tap if tap else None)
            tb = time.perf_counter()
        return g, batch, engine, fl, tb - ta

    def run(self, seconds):
        c0 = self.compiles()
        t0 = time.perf_counter()
        end = t0 + seconds
        order = []
        while time.perf_counter() < end:
            if not order:
                order = list(self.rng.permutation(self.seq_lens))
            s = int(order.pop())
            self.counters["attempted"] += 1
            g, batch, engine, fl, wall = self._plan(s)
            r = fl.results[0]
            self.plans.append({
                "seq_len": s, "g": g, "batch": batch, "engine": engine,
                "sample": len(self.tap.samples) - 1,
                "pick": (self.hw_index[r.best_hw],
                         index_of_row(batch, r.best_cuts)),
                "metrics": metrics_row(r.best_metrics),
                "front": {(int(h), int(c)) for h, c in zip(
                    r.pareto.hw_indices, r.pareto.cut_indices)},
                "front_metrics": np.array(r.pareto.metrics),
                "compile_s": fl.compile_seconds, "sweep_s": fl.sweep_seconds,
                "wall": wall})
        t1 = time.perf_counter()
        self.counters.update(compiles_in_window=self.compiles() - c0,
                             plans=len(self.plans), window_s=t1 - t0,
                             calls=self.plans)
        return {"plans_per_s": len(self.plans) / (t1 - t0)}

    def check(self, control: bool = False) -> dict:
        ch = Checks()
        hw_ref, area = self.ref_space()
        self.check_space(ch, self.grid, hw_ref)
        lim = limits_row(self.traffic["constraints"])
        dt = np.float32 if control else np.float64
        cache = {}
        for p in self.plans:
            k = (p["seq_len"], p["batch"].tobytes())
            if k not in cache:
                ref_g = self.config_mod.reference_graph(
                    self.config, R, seq_len=p["seq_len"])
                diffs = self.config_mod.trace_diffs(
                    self.config, p["g"], ref_g, R, seq_len=p["seq_len"])
                ev = R.Evaluator(ref_g, hw_ref, area)
                blk = ev.metric_block(p["batch"])
                low = ev.metric_block(p["batch"], dtype=dt) if control else blk
                # With no SRAM budget the exact search optimum is one fused
                # group: its Eq. (1) traffic is the least of any grouping.
                fused = ev.cut_terms(np.zeros((1, ref_g.n_edges), bool))
                best_bw = ev.cut_terms(p["batch"])["bw"].min()
                search_wrong = int(best_bw != fused["bw"][0])
                pk = R.pick(blk, lim)
                lpk = R.pick(low, lim)
                cache[k] = dict(
                    ev=ev, diffs=diffs, search_wrong=search_wrong,
                    pick=pk, metrics=blk[pk], low_pick=lpk,
                    low_metrics=low[lpk], front=R.pareto(blk, lim),
                    low_front=R.pareto(low, lim) if control else None, blk=blk)
            ref = cache[k]
            ch.count("inputs_diffs", ref["diffs"])
            ch.count("picks_wrong", ref["search_wrong"])
            gi, h, c, got = self.tap.samples[p["sample"]]
            want = ref["ev"].raw_pairs(p["batch"][c], h)
            if control:
                got = ref["ev"].raw_pairs(p["batch"][c], h, dt)
            ch.gap("cells_rel_gap", got, want)
            if control:
                pick, met, front = ref["low_pick"], ref["low_metrics"], \
                    ref["low_front"]
            else:
                pick, met, front = p["pick"], p["metrics"], p["front"]
            ch.count("picks_wrong", int(tuple(pick) != tuple(ref["pick"])))
            ch.gap("winner_rel_gap", met, ref["metrics"])
            ch.count("picks_wrong", int(front != ref["front"]))
            if not control:
                hs, cs = zip(*sorted(front, key=lambda hc: hc))
                ch.gap("winner_rel_gap",
                       np.sort(p["front_metrics"], axis=0),
                       np.sort(ref["blk"][list(hs), list(cs)], axis=0))
        return ch.v


# ---------------------------------------------------------------------------
# serve: open loop of plan requests
# ---------------------------------------------------------------------------


def zipf_counts(n_keys: int, n: int, s: float) -> np.ndarray:
    """Requests per popularity rank for ``n`` requests over ``n_keys`` keys,
    proportional to rank**-s and rounded to sum to ``n`` exactly (largest
    remainders first), so every seed sends the same multiset."""
    w = np.arange(1, n_keys + 1, dtype=np.float64) ** -float(s)
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    rest = n - int(counts.sum())
    counts[np.argsort(-(exact - counts), kind="stable")[:rest]] += 1
    return counts


def arrivals(rng, n: int, seconds: float) -> np.ndarray:
    """``n`` Poisson arrivals in [0, seconds): a Poisson process given its
    count places the arrivals uniformly, so every seed sends ``n``."""
    return np.sort(rng.uniform(0.0, seconds, n))


def request_plan(seed: int, traffic: dict, n_caps: int, seconds: float):
    """(due times, key ids) of one run: key id = seq-len index * n_caps +
    cap index; popularity ranks are dealt to keys by the seed."""
    rng = np.random.default_rng(seed)
    n = int(round(float(traffic["rate_per_s"]) * seconds))
    n_keys = len(traffic["seq_lens"]) * n_caps
    counts = zipf_counts(n_keys, n, traffic["zipf_s"])
    rank_to_key = rng.permutation(n_keys)
    keys = np.repeat(rank_to_key, counts)
    rng.shuffle(keys)
    return arrivals(rng, n, seconds), keys


def area_caps(ref_ev: R.Evaluator, traffic: dict, seed: int) -> np.ndarray:
    """The seeded set of area caps, between two percentiles of the areas
    the design space reaches with layer-by-layer groupings (the least area
    each hardware point has), so every request has a feasible point."""
    lbl = np.ones((1, ref_ev.g.n_edges), bool)
    areas = ref_ev.metric_block(lbl)[:, 0, 3]
    lo, hi = np.percentile(areas, traffic["cap_percentiles"])
    rng = np.random.default_rng([seed, 3])
    caps = np.sort(rng.uniform(lo, hi, int(traffic["n_caps"])))
    return np.maximum(caps, areas.min())


class ServeLoop(Driver):
    """Open loop into the program's ``AsyncPlanningService`` with its
    default options: a generator thread submits each request at its due
    time and nothing else; completion is stamped when the response is
    handed to the client's future."""

    def setup(self):
        from repro.core import flow

        t = self.traffic
        self.grid = self.program_space()
        self.hw_index = {c: i for i, c in enumerate(self.grid)}
        self.seq_lens = [int(s) for s in t["seq_lens"]]
        with self.spans.span("bench.trace"):
            self.graphs = [self.config_mod.program_graph(
                self.config["model"], seq_len=s) for s in self.seq_lens]
        hw_ref, area = self.ref_space()
        ev0 = R.Evaluator(
            self.config_mod.reference_graph(
                self.config, R, seq_len=self.seq_lens[0]), hw_ref, area)
        self.caps = area_caps(ev0, t, self.seed)
        self.deadline = float(t["deadline_s"])
        self.opts = dict(t.get("service", {}))
        # Every fleet shape a tick can form: 1 .. max_batch graphs of this
        # cell's bucket, each with the search's rows.
        max_batch = int(self.opts.get("max_batch", 16))
        loose = self.program_constraints(
            {"max_bandwidth_words": math.inf, "max_latency_cycles": math.inf,
             "max_energy_nj": math.inf, "max_area_um2": math.inf})
        rows = [flow.groupings_batch(g, "search") for g in self.graphs]
        for n in range(1, max_batch + 1):
            idx = [i % len(self.graphs) for i in range(n)]
            flow.run_fleet([self.graphs[i] for i in idx],
                           config_space=self.grid, constraints=loose,
                           groupings=[rows[i] for i in idx])
        self.counters["compiles_setup"] = self.compiles()

    def run(self, seconds):
        from repro.core.arch import Constraints
        from repro.core.service import AsyncPlanningService, PlanRequest

        due, keys = request_plan(self.seed, self.traffic, len(self.caps),
                                 seconds)
        n = len(due)
        nc = len(self.caps)
        reqs = [PlanRequest(
            graph=self.graphs[k // nc], sram_budget_words=math.inf,
            deadline_seconds=self.deadline,
            constraints=Constraints(math.inf, math.inf, math.inf,
                                    float(self.caps[k % nc])))
            for k in keys]
        done = [None] * n
        late = [0.0] * n
        futs = [None] * n
        svc = AsyncPlanningService(config_space=self.grid, **self.opts)
        c0 = self.compiles()
        t0 = time.perf_counter()

        def stamp(i):
            def cb(_f):
                done[i] = time.perf_counter()
            return cb

        def generate():
            for i in range(n):
                at = t0 + due[i]
                while True:
                    dt = at - time.perf_counter()
                    if dt <= 0:
                        break
                    time.sleep(min(dt, 0.05))
                late[i] = time.perf_counter() - at
                with self.spans.span("bench.submit"):
                    f = svc.submit(reqs[i])
                f.add_done_callback(stamp(i))
                futs[i] = f

        gen = threading.Thread(target=generate, name="bench-generator")
        gen.start()
        gen.join()
        t_close = t0 + seconds
        wait_until = max(t_close, time.perf_counter()) + float(
            self.traffic["grace_s"])
        resps = [None] * n
        for i, f in enumerate(futs):
            try:
                resps[i] = f.result(timeout=max(0.0, wait_until
                                                - time.perf_counter()))
            except Exception:  # never answered within the grace period
                resps[i] = None
        svc.shutdown(drain=False, timeout=60.0)
        stats = svc.stats()
        lat = []
        good = 0
        failed = 0
        for i in range(n):
            r = resps[i]
            if r is None or not r.ok or done[i] is None:
                lat.append(math.inf)
                failed += 1
                continue
            li = done[i] - (t0 + due[i])
            lat.append(li)
            if r.exact and not r.degraded and li <= self.deadline:
                good += 1
        self.reqs, self.keys, self.resps = reqs, keys, resps
        self.counters.update(
            attempted=n, failed=failed,
            compiles_in_window=self.compiles() - c0,
            responses=[r for r in resps if r is not None],
            late_s=late, latency_s=lat, service_stats=stats,
            window_s=seconds, unanswered=sum(r is None for r in resps))
        say(self.out, f"serve: {n} requests, {failed} failed, "
            f"{self.counters['unanswered']} unanswered, service counters "
            f"{dict(stats.get('counters', {}))}")
        return {"plan_p95_ms": 1e3 * percentile(lat, 0.95),
                "plan_goodput_per_s": good / seconds}

    def check(self, control: bool = False) -> dict:
        ch = Checks(cells=False)
        hw_ref, area = self.ref_space()
        self.check_space(ch, self.grid, hw_ref)
        dt = np.float32 if control else np.float64
        nc = len(self.caps)
        ch.count("picks_wrong", self.counters["unanswered"])
        per_len = {}
        for si, s in enumerate(self.seq_lens):
            ref_g = self.config_mod.reference_graph(self.config, R,
                                                    seq_len=s)
            ch.count("inputs_diffs", self.config_mod.trace_diffs(
                self.config, self.graphs[si], ref_g, R, seq_len=s))
            ev = R.Evaluator(ref_g, hw_ref, area)
            # The exact rung's rows with no SRAM budget: one fused group,
            # layer by layer (no pool boundaries in a decoder block).
            rows = np.stack([np.zeros(ref_g.n_edges, bool),
                             np.ones(ref_g.n_edges, bool)])
            per_len[si] = (ev, rows, ev.metric_block(rows),
                           ev.metric_block(rows, dtype=dt))
        for req, k, r in zip(self.reqs, self.keys, self.resps):
            if r is None or not r.ok:
                continue
            ev, rows, blk, low = per_len[k // nc]
            lim = np.asarray([math.inf] * 3 + [self.caps[k % nc]])
            plan = r.plan
            h = self.hw_index[plan.best_hw]
            cut = np.asarray(plan.best_cuts, bool)
            if r.degraded:
                # Another rung's grouping: its metrics, and the best point
                # for that grouping.
                m = ev.metric_block(cut[None, :])
                want_h = R.pick(m, lim)
                if want_h is None:  # the served grouping fits no point
                    ch.count("picks_wrong")
                    continue
                got = (ev.metric_block(cut[None, :], dtype=dt)[want_h]
                       if control else metrics_row(plan.best_metrics))
                ch.gap("winner_rel_gap", got, m[want_h])
                ch.count("picks_wrong", int(not control and h != want_h[0]))
                continue
            want = R.pick(blk, lim)
            if control:
                got_p = R.pick(low, lim)
                got_m = low[got_p]
            else:
                got_p = (h, index_of_row(rows, cut))
                got_m = metrics_row(plan.best_metrics)
            ch.count("picks_wrong", int(tuple(got_p) != tuple(want)))
            ch.gap("winner_rel_gap", got_m, blk[want])
        return ch.v


DRIVERS = {"slices": SliceLoop, "plans": PlanLoop, "serve": ServeLoop}
