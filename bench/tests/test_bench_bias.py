"""bias.sweep_eflfg, the paper's Bias Correction federation under EFL-FG, on
the CPU at a tiny size through the harness's rehearsal argument: a sound
run is correct and reports its metrics, a planted fault and the control
are not correct; and each configuration file against the sizes the
program builds."""

import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import bench_faults  # noqa: E402

CELL = "bias.sweep_eflfg"


@pytest.mark.parametrize("trace", [False, True])
def test_cell_is_correct(trace):
    result, lines = bench_faults.run(CELL, bench_faults.TINY_SWEEP,
                                     trace=trace)
    assert result["correct"], lines
    assert set(result["checks"]) == {"lanes_bad", "violations", "eval_gap",
                                     "agree_min"}
    if trace:
        # the CPU has no TPU plane: only the program-span metrics read
        assert set(result["metrics"]) == {"engine.host_share.sweep",
                                          "engine.prepare_share.sweep"}
    else:
        assert set(result["metrics"]) == {"lane_rounds_per_s", "setup_s"}
        assert result["attempted"] % 8 == 0 and result["failed"] == 0


@pytest.mark.parametrize("fault", ["state_unchanged", "lanes_mislabeled"])
def test_fault_is_not_correct(monkeypatch, fault):
    with bench_faults.planted(monkeypatch, fault):
        result, lines = bench_faults.run(CELL, bench_faults.TINY_SWEEP)
    assert not result["correct"], lines
    assert bench_faults.failed_checks(result), lines


@pytest.mark.parametrize("name", ["energy", "ccpp", "bias"])
def test_config_states_the_sizes_it_runs(name):
    """A configuration's file states the published sizes the program
    builds from it: the dataset's rows and features, the paper's horizon,
    clients and split, and the anchors and stream that split leaves."""
    import json
    from repro.configs.paper_efl import CONFIG
    from repro.data.tabular import DATASETS
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in spec["configs"]}[name]
    cfg = json.loads((ROOT / entry["file"]).read_text())
    rows, features = DATASETS[cfg["dataset"]]
    anchors = int(round(CONFIG.pretrain_frac * rows))
    assert (cfg["rows"], cfg["features"]) == (rows, features)
    assert (cfg["anchors"], cfg["stream"]) == (anchors, rows - anchors)
    assert cfg["T"] == CONFIG.rounds[cfg["dataset"]]
    assert (cfg["n_clients"], cfg["clients_per_round"], cfg["budget"],
            cfg["pretrain_frac"], cfg["loss_scale"]) == (
        CONFIG.n_clients, CONFIG.clients_per_round, CONFIG.budget,
        CONFIG.pretrain_frac, CONFIG.loss_scale)
    assert entry["reduced"] == cfg["reduced"] == []


def test_control_is_not_correct():
    """The reference in bfloat16, put in the program's place, fails the
    cell's limits on every seed."""
    bench_faults.assert_control_fails(CELL, bench_faults.TINY_SWEEP)
