"""Faults planted under a benchmark run, for the tests that see ``correct``
come out false.  Each is a context manager that breaks the timed path where
the fault would live and clears the engine's program caches on the way in
and out, so the next program traced sees (or no longer sees) it."""

import atexit
import contextlib
import functools
import json
import shutil
import tempfile
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
HELD_BACK = Path(__file__).resolve().parent / "held_back"


@functools.lru_cache(maxsize=None)
def root() -> Path:
    """A checkout whose ``BENCHMARK.json`` also names the cells held back
    from the benchmark (``held_back/``: EFL-FG, whose runs on the TPU part
    from the reference, PERF.md), so that the tests still hold their
    comparison and faults on the CPU, where the program agrees."""
    tmp = Path(tempfile.mkdtemp(prefix="bench_cells_"))
    atexit.register(shutil.rmtree, tmp, True)
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    held = json.loads((HELD_BACK / "workloads.json").read_text())
    for w in held:
        shutil.copy(HELD_BACK / f"{w['traffic']}.json",
                    tmp / "bench" / "traffic")
        spec["workloads"].append(w)
        serve = w["traffic"].startswith("serve")
        for m in spec["end_to_end"]:
            if "workloads" in m and (m["name"] != "lane_rounds_per_s") \
                    == serve:
                m["workloads"].append(w["name"])
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp

# tiny sizes for the CPU; the limits of ``correct`` read differently at
# these sizes, so the rehearsal sets its own (the cells' own are set from
# chip runs, PERF.md)
TINY_LIMITS = {"eval_gap": 5e-3, "agree_min": 1}
TINY_SWEEP = {"config": {"rows": 2000, "T": 60},
              "traffic": {"seeds_per_call": 2, "check_lanes": 4}}
TINY_SERVE = {"config": {"rows": 2000, "T": 60},
              "traffic": {"rate_hz": 20.0, "max_batch": 4,
                          "check_requests": 4}}


def tiny(overrides, workload):
    """``overrides`` with the cell's limits, the size-dependent ones
    replaced by ``TINY_LIMITS``."""
    from bench import harness
    spec = harness.load_spec(root())
    cell = {w["name"]: w for w in spec["workloads"]}[workload]
    limits = json.loads((root() / "bench" / "traffic"
                         / f"{cell['traffic']}.json").read_text())["limits"]
    limits = {**limits, **{k: v for k, v in TINY_LIMITS.items()
                           if k in limits}}
    return {"config": overrides["config"],
            "traffic": {**overrides["traffic"], "limits": limits}}


def clear_programs():
    from repro.federated import engine, simulation
    engine._SCAN_CACHE.clear()
    simulation._STEP_CACHE.clear()


@contextlib.contextmanager
def planted(monkeypatch, name):
    clear_programs()
    try:
        with monkeypatch.context() as mp:
            {**FAULTS, **MESH_FAULTS}[name](mp)
            yield
    finally:
        clear_programs()


def state_unchanged(mp):
    """The round's update returns the server state it was given."""
    from repro.core import eflfg, fedboost
    mp.setattr(eflfg, "update_state",
               lambda state, plan, *a: state._replace(t=state.t + 1))
    mp.setattr(fedboost, "fedboost_update",
               lambda state, *a: state._replace(t=state.t + 1))


def _wrap_client_eval(mp, change):
    from repro.kernels.client_eval import ops
    real = ops.client_eval

    def client_eval(preds, y, cursor, n_t, *args, **kw):
        return change(real, preds, y, cursor, n_t, *args, **kw)
    mp.setattr(ops, "client_eval", client_eval)


def half_clients(mp):
    """The kernel evaluates the first half of the round's clients and
    takes its means over them."""
    _wrap_client_eval(mp, lambda real, p, y, c, n, *a, **k:
                      real(p, y, c, (n + 1) // 2, *a, **k))


def kernel_answer(mp):
    """The kernel's mean squared ensemble error comes out 1 % high."""
    def change(real, *a, **k):
        out = real(*a, **k)
        return out._replace(ens_sq_mean=out.ens_sq_mean * 1.01)
    _wrap_client_eval(mp, change)


def lanes_mislabeled(mp):
    """Each lane answers for the wrong seed: the sweep and the served
    batch run every lane on its seed plus one."""
    from repro import federated

    for name in ("run_sweep", "run_batch"):
        real = getattr(federated, name)

        def shifted(*a, real=real, **k):
            if "seeds" in k:
                k["seeds"] = [s + 1 for s in k["seeds"]]
            else:               # (algo, preds, y, costs, T, cfg, seeds, ...)
                a = a[:6] + ([s + 1 for s in a[6]],) + a[7:]
            return real(*a, **k)
        mp.setattr(federated, name, shifted)


def exchange_left_out(mp):
    """On a mesh, the chips' shards never reach the host: every shard of
    the sweep's output reads as the first chip's."""
    import jax
    from repro.federated import sweep_sharding
    real = sweep_sharding.sharded_sweep_fn

    def sharded_sweep_fn(*a, **k):
        fn = real(*a, **k)

        def first_shard(x):
            shard = np.asarray(x.addressable_shards[0].data)
            return np.concatenate([shard] * (x.shape[0] // shard.shape[0]))

        return lambda *args: jax.tree.map(first_shard, fn(*args))
    mp.setattr(sweep_sharding, "sharded_sweep_fn", sharded_sweep_fn)


MESH_FAULTS = {"exchange_left_out": exchange_left_out}

FAULTS = {"state_unchanged": state_unchanged, "half_clients": half_clients,
          "kernel_answer": kernel_answer,
          "lanes_mislabeled": lanes_mislabeled}


def run(workload, overrides, seed=2**31 + 17, trace=False, seconds=0.5):
    """One rehearsal run of a cell at the tiny size and limits; returns
    ``(result, stderr lines)``."""
    import time
    from bench import harness
    return harness.run_cell(workload, seed, seconds, trace,
                            time.monotonic(),
                            overrides=tiny(overrides, workload), root=root())


def failed_checks(result):
    """The names of the checks a result line holds above their limits."""
    from bench.harness import Check
    return [name for name, c in result["checks"].items()
            if not Check(name, c["value"], c["limit"],
                         at_least=name == "agree_min").ok]


def assert_control_fails(workload, overrides, seeds=(1, 2, 3)):
    from bench import check, control
    overrides = tiny(overrides, workload)
    limits = overrides["traffic"]["limits"]
    rows = list(control.readings(workload, seeds, len(seeds), 0.3,
                                 overrides=overrides, root=root()))
    sound = [r for r in rows if r["kind"] == "program"]
    ctl = [r for r in rows if r["kind"] == "control"]
    assert len(ctl) == len(seeds)
    for row in sound:
        assert all(c.ok for c in check.checks(row, limits)), row
    for row in ctl:
        numbers = {k: row[k] for k in ("eval_gap", "agree_min")}
        mine = {k: v for k, v in limits.items() if k in numbers}
        assert not all(c.ok for c in check.checks(numbers, mine)), row
