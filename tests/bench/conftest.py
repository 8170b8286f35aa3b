"""A test parametrised by a benchmark cell that needs more chips than this
process has devices runs in a child pytest process that has them, as host
devices (``--xla_force_host_platform_device_count``), the way the other
multi-device tests run.  The test process keeps its single CPU device."""
import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
_CHILD = "BENCH_TEST_HOST_DEVICES"


def _chips(cell) -> int:
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    return next((int(w["chips"]) for w in b["workloads"]
                 if w["name"] == cell), 1)


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    callspec = getattr(pyfuncitem, "callspec", None)
    cell = callspec.params.get("cell") if callspec else None
    if not isinstance(cell, str) or os.environ.get(_CHILD):
        return None
    chips = _chips(cell)
    if chips <= 1:
        return None
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu", **{_CHILD: str(chips)},
               XLA_FLAGS=f"{flags} --xla_force_host_platform_device_count="
                         f"{chips}".strip())
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", pyfuncitem.nodeid],
        cwd=pyfuncitem.config.rootpath, env=env, capture_output=True,
        text=True, timeout=900)
    lines = p.stdout.strip().splitlines()
    # A skipped child also exits 0: only its one test passing counts.
    assert p.returncode == 0 and lines and re.fullmatch(
        r"1 passed(, \d+ warnings?)? in .*", lines[-1]), (
        f"{pyfuncitem.nodeid} on {chips} host devices:\n"
        + p.stdout[-4000:] + p.stderr[-2000:])
    return True
