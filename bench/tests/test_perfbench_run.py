"""Whole runs of the harness at the tests' size on the CPU (past its
look for a chip), and its refusal to run without one."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench_fixtures import ROOT, run_cell

E2E = {"tiny.turbo": {"setup_s", "latency_p50_s", "latency_p95_s"},
       "tiny.offline": {"setup_s", "images_per_s"}}


def no_chip_run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "sd15-q8_0.turbo-poisson", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_exits_without_a_tpu_and_prints_no_result():
    p = no_chip_run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU" in p.stderr


def test_exits_without_a_tpu_from_the_benchmark_files_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = no_chip_run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("cell", sorted(E2E))
def test_cell_runs_correct_with_its_end_to_end_metrics(tiny_root, capsys,
                                                       cell):
    out = run_cell(tiny_root, capsys, cell)
    assert out["correct"] is True
    assert set(out["metrics"]) == E2E[cell]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    chk = out["checks"]["image_rel_err"]
    assert 0 < chk["value"] <= chk["limit"]
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}


def test_traced_run_reports_host_layer_metrics(tiny_root, capsys):
    out = run_cell(tiny_root, capsys, "tiny.turbo", trace=1)
    assert out["correct"] is True
    # No device trace on the CPU: only the host-side readers read.
    assert set(out["metrics"]) == {"generator_lag_p95_s",
                                   "queue_wait_p95_s", "batch_fill.serve"}
    assert 0 < out["metrics"]["batch_fill.serve"]["value"] <= 100


def test_new_cell_config_mix_and_metric_are_found_by_name(tiny_root,
                                                          capsys):
    """Adding a configuration, a mix, a per-layer metric and a cell
    takes new files and entries only."""
    bdir = os.path.join(tiny_root, "bench")
    with open(os.path.join(bdir, "configs", "tiny-q8_0.json")) as f:
        spec = json.load(f)
    spec["name"] = "tiny-wide"
    spec["unet"]["num_heads"] = 4
    with open(os.path.join(bdir, "configs", "tiny-wide.json"), "w") as f:
        json.dump(spec, f)
    with open(os.path.join(bdir, "traffic", "tiny-turbo-poisson.json")) as f:
        mix = json.load(f)
    mix["rate_per_s"] = 5.0
    with open(os.path.join(bdir, "traffic", "slow-turbo.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bdir, "metrics", "requests_seen.new.py"),
              "w") as f:
        f.write("def read(run):\n    return len(run.win.rec)\n")
    with open(os.path.join(bdir, "checks", "tiny-wide.slow.json"), "w") as f:
        json.dump({"sample": 1, "numbers": {"image_rel_err":
                                            {"limit": 0.05}}}, f)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-wide", "source": "tests",
                             "file": "bench/configs/tiny-wide.json",
                             "reduced": [], "why": "tests"})
    bench["workloads"].append({"name": "tiny-wide.slow",
                               "config": "tiny-wide",
                               "traffic": "slow-turbo", "chips": 1,
                               "why": "tests"})
    bench["per_layer"].append({"name": "requests_seen.new", "unit": "1",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "engine", "moves": "setup_s",
                               "workloads": ["tiny-wide.slow"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    out = run_cell(tiny_root, capsys, "tiny-wide.slow", trace=1)
    assert out["correct"] is True
    assert out["metrics"]["requests_seen.new"]["value"] == 10
