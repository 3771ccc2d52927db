"""energy.sweep_fedboost_4chip on four virtual CPU devices: a sound run is
correct on the sharded path, and each fault planted under it, the
exchange between chips left out among them, comes out not correct.

The devices are forced in a child process (the flag must be set before
JAX starts); the child runs on the CPU only."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent

CHILD = r"""
import json, sys
sys.path[:0] = [{root!r}, {root!r} + "/src", {here!r}]
import pytest
import bench_faults
tiny = {{"config": {{"rows": 2000, "T": 60}},
        "traffic": {{"seeds_per_call": 2, "check_lanes": 4}}}}
cell = "energy.sweep_fedboost_4chip"
out = {{}}
res, lines = bench_faults.run(cell, tiny)
out["sound"] = [res["correct"], res["device"]["count"], lines]
mp = pytest.MonkeyPatch()
for name in sorted({{**bench_faults.FAULTS, **bench_faults.MESH_FAULTS}}):
    with bench_faults.planted(mp, name):
        res, lines = bench_faults.run(cell, tiny)
    out[name] = [res["correct"], bench_faults.failed_checks(res), lines]
print(json.dumps(out))
"""


def test_four_device_sweep_and_its_faults():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", CHILD.format(root=str(ROOT), here=str(HERE))],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    correct, count, lines = out.pop("sound")
    assert correct and count == 4, lines
    assert set(out) == {"exchange_left_out", "half_clients",
                        "kernel_answer", "lanes_mislabeled",
                        "state_unchanged"}
    for name, (correct, failed, lines) in out.items():
        assert not correct and failed, (name, lines)
