"""Faults planted under the timed path of ccpp.serve_fedboost, and its control:
each run must come out not correct."""

import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import bench_faults  # noqa: E402

CELL = "ccpp.serve_fedboost"


@pytest.mark.parametrize("fault", sorted(bench_faults.FAULTS))
def test_fault_is_not_correct(monkeypatch, fault):
    with bench_faults.planted(monkeypatch, fault):
        result, lines = bench_faults.run(CELL, bench_faults.TINY_SERVE,
                                         seconds=1.0)
    assert not result["correct"], lines
    assert bench_faults.failed_checks(result), lines


def test_control_is_not_correct():
    bench_faults.assert_control_fails(CELL, bench_faults.TINY_SERVE)
