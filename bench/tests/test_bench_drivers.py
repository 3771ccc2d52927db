"""Each driver end to end on the CPU at a tiny size, through the harness's
rehearsal argument: a sound run is correct and reports its metrics."""

import json
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import bench_faults  # noqa: E402


@pytest.mark.parametrize("workload", ["energy.sweep_fedboost",
                                      "energy.sweep_eflfg"])
def test_sweep_cell_is_correct(workload):
    result, lines = bench_faults.run(workload, bench_faults.TINY_SWEEP)
    assert result["correct"], lines
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"lane_rounds_per_s", "setup_s"}
    assert result["metrics"]["lane_rounds_per_s"]["unit"] == "lane-rounds/s"
    assert result["attempted"] % 8 == 0 and result["failed"] == 0
    assert result["device"]["count"] >= 1
    assert any(line.startswith("check eval_gap") for line in lines)
    assert 'compiles_in_window {"jaxpr_trace_duration": 0, ' \
        '"backend_compile_duration": 0}' in lines
    json.dumps(result)


@pytest.mark.parametrize("workload", ["ccpp.serve_fedboost",
                                      "ccpp.serve_open"])
def test_serve_cell_is_correct(workload):
    result, lines = bench_faults.run(workload, bench_faults.TINY_SERVE,
                                     seconds=1.0)
    assert result["correct"], lines
    assert set(result["metrics"]) == {"req_per_s", "p95_latency_s",
                                      "setup_s"}
    assert result["attempted"] == 20 and result["failed"] == 0
    assert any(line.startswith("generator_late_s") for line in lines)


def test_traced_sweep_reports_per_layer_metrics():
    result, lines = bench_faults.run("energy.sweep_fedboost",
                                     bench_faults.TINY_SWEEP, trace=True)
    assert result["correct"], lines
    # the CPU has no TPU plane: the device metrics have nothing to read,
    # and a metric with nothing to read is left out of the line
    assert result["metrics"] == {}
    assert any(line.startswith("trace ") for line in lines)
    assert set(result["device"]) >= {"busy_s", "window_s"}
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
