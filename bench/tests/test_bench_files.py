"""The harness finds a cell's files by name, and the command refuses to run
without a TPU."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from bench import harness  # noqa: E402

NEW_METRIC = '''
def read(run):
    """Lanes in one call of the sweep."""
    return float(run["stats"]["lanes_per_call"])
'''


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    """A later change adds a deployment, a mix and a metric as new files
    and entries alone; the harness runs the new cell from them."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = json.loads((ROOT / "bench/configs/ccpp.json").read_text())
    config.update(rows=1500, T=40)
    (tmp_path / "bench/configs/ccpp_small.json").write_text(
        json.dumps(config))
    traffic = json.loads((ROOT / "bench/traffic/sweep_fedboost.json")
                         .read_text())
    traffic.update(seeds_per_call=3, budgets=[2.0, 4.0], check_lanes=2)
    (tmp_path / "bench/traffic/sweep_fedboost_small.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench/metrics/sweep.lanes.py").write_text(NEW_METRIC)
    spec["configs"].append({"name": "ccpp_small", "source": "test",
                            "file": "bench/configs/ccpp_small.json",
                            "reduced": ["rows", "T"], "why": "test"})
    spec["workloads"].append({"name": "ccpp_small.sweep_fedboost_small",
                              "config": "ccpp_small",
                              "traffic": "sweep_fedboost_small",
                              "chips": 1, "why": "test"})
    spec["end_to_end"][0]["workloads"].append(
        "ccpp_small.sweep_fedboost_small")
    spec["per_layer"].append({
        "name": "sweep.lanes", "unit": "lanes", "better": "higher",
        "source": "program_counter", "layer": "engine",
        "moves": "lane_rounds_per_s",
        "workloads": ["ccpp_small.sweep_fedboost_small"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    result, lines = harness.run_cell(
        "ccpp_small.sweep_fedboost_small", 7, 0.2, True, time.monotonic(),
        overrides={}, root=tmp_path)
    assert result["correct"], lines
    assert result["metrics"]["sweep.lanes"]["value"] == 6
    assert result["metrics"]["sweep.lanes"]["unit"] == "lanes"
    assert result["attempted"] % 6 == 0


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "energy.sweep_fedboost",
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_refuses_to_run_without_a_tpu():
    proc = _run_cli(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_cli_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = _run_cli(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
