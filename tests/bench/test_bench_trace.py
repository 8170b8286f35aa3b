"""The trace reduction: exact arithmetic on planes built by hand, and the
reading of a small trace recorded on the CPU."""
import time
from types import SimpleNamespace as NS

import pytest

import benchkit  # noqa: F401
import trace_reduce as T


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines])


def test_reduction_of_hand_built_planes():
    host = plane("/host:CPU", [("python", [
        ev("bench.window", 100, 1000),
        ev("bench.run_fleet", 100, 400),
        ev("bench.merge", 600, 300),
        ev("other", 0, 5000)])])
    dev0 = plane("/device:TPU:0", [
        ("XLA Modules", [ev("jit__evaluate_fleet_graph(7)", 150, 200),
                         ev("jit__evaluate_fleet_graph(8)", 950, 100)]),
        ("XLA Ops", [ev("fusion.1", 150, 100), ev("fusion.2", 200, 150),
                     ev("copy", 950, 100)])])
    dev1 = plane("/device:TPU:1", [
        ("XLA Ops", [ev("fusion.1", 0, 2000)])])
    r = T.reduce_planes([host, dev0, dev1], n_devices=1)
    assert r["window_s"] == pytest.approx(1000e-9)
    # ops union on TPU:0 inside [100, 1100]: [150, 350] and [950, 1050]
    assert r["busy_s"] == pytest.approx(300e-9)
    assert r["programs"] == {"jit__evaluate_fleet_graph": pytest.approx(
        300e-9)}
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["bench.merge", pytest.approx(600e-9)]
    assert sorted(g[0] for g in gaps) == ["bench.merge", "bench.run_fleet",
                                          "outside"]
    assert r["breakdown"]["device_ops"][0] == ["fusion.2", pytest.approx(
        150e-9)]
    both = T.reduce_planes([host, dev0, dev1], n_devices=2)
    assert both["busy_s"] == pytest.approx((300e-9 + 1000e-9) / 2)


def test_no_window_or_no_device_reads_nothing():
    host = plane("/host:CPU", [("python", [ev("bench.run_fleet", 0, 5)])])
    dev = plane("/device:TPU:0", [("XLA Ops", [ev("x", 0, 5)])])
    assert T.reduce_planes([host, dev]) is None
    host2 = plane("/host:CPU", [("python", [ev("bench.window", 0, 5)])])
    assert T.reduce_planes([host2]) is None


def test_cpu_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.run_fleet"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.merge"):
                    time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()

    def cpu_ops(plane_name, line_name):  # XLA's CPU client threads
        return ("ops" if plane_name == "/host:CPU"
                and line_name.startswith("tf_XLA") else None)

    assert T.reduce_trace(tmp_path) is None  # no TPU plane on the CPU
    r = T.reduce_trace(tmp_path, lines=cpu_ops)
    assert r is not None
    assert 0.06 <= r["window_s"] < 5.0
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["breakdown"]["idle_gaps"][0][0] == "bench.merge"
    assert len(r["breakdown"]["idle_gaps"]) <= 10
