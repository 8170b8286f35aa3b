"""The benchmark's general harness: discovery by name, spans, one run of
one cell, and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in files of its own, found by the name ``BENCHMARK.json``
gives it:

* ``configs/<config>.json``  sizes, design space and constraints, with
  ``configs/<config>.py`` beside it: ``program_graph`` (how the program
  under test traces the model) and ``reference_graph`` (the plain
  reference's own layer table);
* ``traffic/<traffic>.json`` parameters of one mix; its ``loop`` names the
  generator in :mod:`drivers` that reads it;
* ``metrics/<metric>.py``    a per-layer metric's ``read(ctx)``, which
  returns a number or None when it finds nothing to read.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class BenchError(Exception):
    """The run cannot produce a result (no chip, unknown name, bad file)."""


# ---------------------------------------------------------------------------
# Discovery
# ---------------------------------------------------------------------------


def _load_module(path: pathlib.Path, tag: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_{tag}_{path.stem}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(name: str, root: pathlib.Path = BENCH_DIR):
    """(sizes dict, module) of configuration ``name``."""
    js = root / "configs" / f"{name}.json"
    py = root / "configs" / f"{name}.py"
    if not js.is_file() or not py.is_file():
        raise BenchError(f"configuration {name!r}: need {js} and {py}")
    return json.loads(js.read_text()), _load_module(py, "config")


def load_traffic(name: str, root: pathlib.Path = BENCH_DIR) -> dict:
    """Parameters of traffic mix ``name``."""
    js = root / "traffic" / f"{name}.json"
    if not js.is_file():
        raise BenchError(f"traffic {name!r}: no {js}")
    return json.loads(js.read_text())


def load_metric(name: str, root: pathlib.Path = BENCH_DIR):
    """The reader module of per-layer metric ``name``."""
    py = root / "metrics" / f"{name}.py"
    if not py.is_file():
        raise BenchError(f"per-layer metric {name!r}: no {py}")
    return _load_module(py, "metric")


def load_limits(root: pathlib.Path = BENCH_DIR) -> dict:
    """The limit of every number the correctness check compares."""
    return json.loads((root / "limits.json").read_text())


@dataclasses.dataclass
class Spec:
    """One cell as ``BENCHMARK.json`` states it, with its metrics."""

    workload: dict
    end_to_end: list
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_spec(bench_json: pathlib.Path, cell: str) -> Spec:
    """The entry of ``cell`` and the metrics that apply to it."""
    b = json.loads(pathlib.Path(bench_json).read_text())
    for w in b["workloads"]:
        if w["name"] == cell:
            return Spec(
                workload=w,
                end_to_end=[m for m in b["end_to_end"] if _applies(m, cell)],
                per_layer=[m for m in b["per_layer"] if _applies(m, cell)],
            )
    raise BenchError(f"no workload {cell!r} in {bench_json}")


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------


class Spans:
    """Host-clock spans around the harness's calls into each layer.  Each
    is also a ``jax.profiler.TraceAnnotation``, so a traced run sees it on
    the device trace's clock."""

    def __init__(self):
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self.records.append((name, t0, time.perf_counter()))

    def durations(self, name: str, t_from: float = -math.inf,
                  t_to: float = math.inf) -> list[float]:
        """Durations (s) of the ``name`` spans that began in [t_from, t_to]."""
        return [b - a for n, a, b in self.records
                if n == name and t_from <= a <= t_to]


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""

    spans: Spans
    counters: dict
    trace: dict | None
    window: tuple  # (t0, t1) on the host clock
    peaks: dict | None


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-quantile (0 < q <= 1) of ``values``; an infinite
    value (a failed request) sorts last."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    k = max(1, math.ceil(q * len(xs)))
    return xs[k - 1]


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the backend
    keeps no statistics)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def require_chips(chips: int):
    """JAX's devices, or BenchError when they are not ``chips`` TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(
            f"no TPU: JAX's first device is {devs[0].platform!r} "
            f"({devs[0].device_kind}); the benchmark never falls back")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, bench_json=None, root=BENCH_DIR,
             require_tpu: bool = True, control: bool = False,
             overrides: dict | None = None, compile_cache: bool = True,
             out=sys.stderr) -> dict:
    """One run of one cell; returns the result object of the last line.

    ``control`` replaces what the program produced, before the comparison,
    by the plain reference computed in float32: that run must come out not
    correct.  ``overrides`` replaces traffic parameters (the rate sweep).
    """
    import jax

    from drivers import DRIVERS  # noqa: E402  (bench/ is on sys.path)

    spec = load_spec(bench_json or ROOT / "BENCHMARK.json", cell)
    w = spec.workload
    devs = require_chips(w["chips"]) if require_tpu else jax.devices()
    cache = None
    if compile_cache:
        from repro.compile_cache import enable_compile_cache

        cache = enable_compile_cache(ROOT)
    say(out, f"cell {cell} seed {seed} seconds {seconds} trace {int(trace)} "
        f"device {devs[0].device_kind} x{len(devs)} cache {cache}")
    config, config_mod = load_config(w["config"], root)
    traffic = dict(load_traffic(w["traffic"], root), **(overrides or {}))
    loop = traffic["loop"]
    if loop not in DRIVERS:
        raise BenchError(f"traffic {w['traffic']!r}: unknown loop {loop!r}")
    spans = Spans()
    driver = DRIVERS[loop](seed=seed, config=config,
                           config_mod=config_mod, traffic=traffic,
                           spans=spans, out=out)
    driver.setup()
    trace_dir = pathlib.Path(root).resolve().parent / ".bench_trace" / cell
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    setup_s = time.perf_counter() - t_start
    try:
        with spans.span("bench.window"):
            t0 = time.perf_counter()
            e2e = driver.run(seconds)
            t1 = time.perf_counter()
    finally:
        if trace:
            jax.profiler.stop_trace()
    device = dict(device_info(devs), memory_peak_bytes=memory_peak(
        devs[:w["chips"]]))
    say(out, f"window {t1 - t0!r} s; compiles in the window "
        f"{driver.counters.get('compiles_in_window')}")
    driver.release()
    checks = driver.check(control=control)
    limits = load_limits(root)
    result = {"correct": None, "attempted": driver.counters["attempted"],
              "failed": driver.counters["failed"]}
    e2e["setup_s"] = setup_s
    if trace:
        from trace_reduce import reduce_trace

        red = reduce_trace(trace_dir, n_devices=w["chips"])
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = red["breakdown"]
        ctx = Context(spans=spans, counters=driver.counters,
                      trace=red, window=(t0, t1),
                      peaks=_peaks_or_none(devs[0]))
        metrics = {}
        for m in spec.per_layer:
            v = load_metric(m["name"], root).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {}
        for m in spec.end_to_end:
            if m["name"] not in e2e:
                raise BenchError(f"{cell}: the {loop} loop reports no "
                                 f"{m['name']!r}")
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    compared = {}
    ok = True
    for name, value in checks.items():
        lim = limits[name]
        good = value <= lim
        ok &= good
        compared[name] = {"value": value, "limit": lim}
    result["correct"] = bool(ok)
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = compared
    for name, c in compared.items():
        say(out, f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result


def _peaks_or_none(dev):
    from peaks import peaks

    try:
        return peaks(dev.device_kind)
    except KeyError:
        if dev.platform == "tpu":
            raise
        return None


def say(out, msg: str) -> None:
    print(f"[bench] {msg}", file=out, flush=True)
