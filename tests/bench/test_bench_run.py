"""The entry point and discovery by name: no TPU means no result, a
directory without the program gives none, and a configuration, traffic
mix or per-layer metric added as files is found without a harness edit."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import benchkit
from benchkit import ROOT


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "vgg16.exhaustive",
         "--seed", "3", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_cpu_device():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_without_the_program_gives_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for d in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    root = benchkit.small_root(tmp_path)
    cfg = json.loads((root / "configs" / "vgg16.json").read_text())
    cfg["name"] = "vgg16-narrow"
    cfg["design_space"]["styles"] = ["hsiao"]
    (root / "configs" / "vgg16-narrow.json").write_text(json.dumps(cfg))
    shutil.copy(root / "configs" / "vgg16.py",
                root / "configs" / "vgg16-narrow.py")
    t = json.loads((root / "traffic" / "exhaustive.json").read_text())
    t.update(c_slice=32768, warmup_calls=0)
    (root / "traffic" / "coarse.json").write_text(json.dumps(t))
    (root / "metrics" / "calls.made.py").write_text(
        "def read(ctx):\n    return float(len(ctx.counters['calls']))\n")
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "vgg16-narrow.coarse",
                           "config": "vgg16-narrow", "traffic": "coarse",
                           "chips": 1, "why": "test"})
    for m in b["end_to_end"]:
        if m["name"] == "sweep_cand_per_s":
            m["workloads"].append("vgg16-narrow.coarse")
    b["per_layer"].append({"name": "calls.made", "unit": "calls",
                           "better": "higher", "source": "host_clock",
                           "layer": "test", "moves": "sweep_cand_per_s",
                           "workloads": ["vgg16-narrow.coarse"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    import time

    from harness import run_cell

    res = run_cell("vgg16-narrow.coarse", 9, 1.0, True,
                   t_start=time.perf_counter(),
                   bench_json=tmp_path / "BENCHMARK.json", root=root,
                   require_tpu=False, compile_cache=False)
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["calls.made"]["value"] >= 1
    assert pathlib.Path(root / "configs" / "vgg16-narrow.py").is_file()
