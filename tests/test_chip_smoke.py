"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and its
phases (every check it makes on the chip) pass on the CPU backend at the
same full-width shapes, so the script cannot rot between chip runs."""
import json
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.compile_cache import enable_compile_cache
from repro.kernels import interpret_default, tile_bytes

ROOT = pathlib.Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(ROOT))
    return chip_smoke


@pytest.fixture(scope="module")
def zoo(smoke):
    return smoke.zoo_workload()


def _run(cwd, env_extra):
    import os

    env = {**os.environ, **env_extra}
    return subprocess.run([sys.executable, str(cwd / "chip_smoke.py")],
                          capture_output=True, text=True, cwd=cwd, env=env,
                          timeout=300)


def _printed_result(proc) -> bool:
    lines = proc.stdout.strip().splitlines()
    return bool(lines) and lines[-1].startswith('{"ok"')


def test_smoke_refuses_the_cpu():
    proc = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert not _printed_result(proc)


def test_smoke_fails_outside_the_repo(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert not _printed_result(proc)


def test_paper_lock_phase(smoke):
    smoke.phase_paper_lock()


def test_zoo_phase_checks_every_cell(smoke, zoo):
    graphs, batches, grid, timings = zoo
    assert len(grid) == 2560 and [b.shape[0] for b in batches] == [3, 3]
    reading = smoke.phase_zoo(graphs, batches, grid, timings)
    assert reading["n_candidates"] == 2 * 2560 * 3
    json.dumps(reading)


def test_zoo_phase_catches_a_wrong_cell(smoke, zoo, monkeypatch):
    from repro.core import metrics as M

    graphs, batches, grid, timings = zoo
    real = M.compose_metrics

    def off_by_one(raw, hw_rows):
        out = np.array(real(raw, hw_rows))
        out[..., 1][out[..., 1] > 0] += 1.0  # one cycle on every latency
        return out

    monkeypatch.setattr(M, "compose_metrics", off_by_one)
    with pytest.raises(smoke.SmokeFailure):
        smoke.phase_zoo(graphs, batches, grid, timings)


def test_service_phase(smoke, zoo, tmp_path):
    graphs, _, grid, _ = zoo
    smoke.phase_service(graphs, grid, tmp_path / "journal")


def test_kernel_phase_interpreted(smoke):
    smoke.phase_kernels(interpret=True)


def test_interpret_default_follows_the_operands():
    x = jax.numpy.ones((8, 128))
    assert interpret_default(x) is (jax.default_backend() == "cpu")
    assert interpret_default(np.ones(3)) is (jax.default_backend() == "cpu")


@pytest.mark.parametrize("shape,itemsize,expect", [
    ((8, 128), 4, 8 * 128 * 4),
    ((1, 16), 4, 8 * 128 * 4),  # minor dim pads to 128 lanes, rows to 8
    ((16, 128), 2, 16 * 128 * 2),
    ((3, 5, 16), 2, 3 * 16 * 128 * 2),  # bf16 rows pad to 16
    ((512,), 4, 8 * 512 * 4),
])
def test_tile_bytes_pads_to_native_tiling(shape, itemsize, expect):
    assert tile_bytes(shape, itemsize) == expect


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir(tmp_path, monkeypatch, env_dir):
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    try:
        got = enable_compile_cache(tmp_path)
        if env_dir is None:
            assert got == tmp_path.resolve() / ".jax_cache"
            assert jax.config.jax_compilation_cache_dir == str(got)
        else:
            assert got == tmp_path / env_dir
            assert (jax.config.jax_compilation_cache_dir
                    == saved["jax_compilation_cache_dir"])
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
