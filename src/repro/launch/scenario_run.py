"""Scenario driver: run named non-stationary scenarios, emit artifacts.

Runs every requested registered scenario (``repro.scenarios``) through
the scan engine for both algorithms and writes one JSON artifact per
scenario under ``--out`` (default ``experiments/scenarios/``) with the
regret/violation summary the scenario subsystem exists to measure:
final MSE, budget violations vs the *realized* per-round budget,
terminal regret, mean transmit-set size, and the compiled-schedule
summary.  The committed ``experiments/scenarios/`` set is the default
synthetic paper-shaped stream at ``--T 600`` and is validated by
``tests/test_scenarios.py``.

    PYTHONPATH=src python -m repro.launch.scenario_run --list
    PYTHONPATH=src python -m repro.launch.scenario_run --T 600
    PYTHONPATH=src python -m repro.launch.scenario_run \
        --scenarios bursty_outage concept_drift --algos eflfg --T 400

The stream is synthetic by default (seeded, process-independent — the
engine's cost and the schedules' effects are independent of where the
(K, n_stream) prediction matrix came from); ``--dataset ccpp`` runs the
paper's expert pool on a real stream instead.
"""

from __future__ import annotations

import argparse
import json
import os

import numpy as np

from repro import scenarios
from repro.federated import SimConfig, run_simulation_scan

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "scenarios")


def _synthetic_stream(K: int, n_stream: int, seed: int):
    rng = np.random.default_rng(seed)
    preds = rng.normal(0, 1, (K, n_stream)).astype(np.float32)
    y = rng.normal(0, 1, n_stream).astype(np.float32)
    costs = rng.uniform(0.05, 1.0, K).astype(np.float32)
    return preds, y, costs


def _dataset_stream(name: str, anchors: int):
    from repro.data import make_dataset, pretrain_split
    from repro.experts import build_paper_pool, pool_predict_all
    from repro.configs import PAPER_EFL
    ds = make_dataset(name)
    (xp, yp), (xs, ys) = pretrain_split(ds, frac=PAPER_EFL.pretrain_frac)
    pool = build_paper_pool(xp, yp, subsample_anchors=anchors)
    return pool_predict_all(pool, xs), np.asarray(ys), np.asarray(pool.costs)


def run_scenario(name: str, algos, preds, y, costs, T: int,
                 cfg: SimConfig) -> dict:
    """Run one named scenario for every algo; returns the artifact dict."""
    scen = scenarios.get(name)
    comp = scen.compile(T, cfg)
    rec = {
        "scenario": name,
        "description": scen.description,
        "T": T, "K": int(np.asarray(preds).shape[0]),
        "budget": cfg.budget, "seed": cfg.seed,
        "neutral": comp.neutral,
        "schedule": scen.summary(T),
        "algos": {},
    }
    realized = cfg.budget * comp.scale
    rec["schedule"]["realized_budget_min"] = float(realized.min())
    for algo in algos:
        res = run_simulation_scan(algo, preds, y, costs, T, cfg,
                                  scenario=name)
        rec["algos"][algo] = {
            "final_mse": round(res.final_mse, 6),
            "budget_violations": int(res.budget_violations),
            "violation_frac": round(res.violation_frac, 6),
            "regret_T": round(float(res.regret.regret_curve()[-1]), 4),
            "mean_sel": round(float(res.sel_sizes.mean()), 3),
            "mean_round_cost": round(float(res.round_costs.mean()), 4),
            "best_model": int(res.regret.best_model()),
        }
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter,
        epilog="Registered scenarios: " + ", ".join(scenarios.names()))
    ap.add_argument("--scenarios", nargs="*", default=None,
                    help="scenario names (default: every registered one)")
    ap.add_argument("--algos", nargs="*", default=["eflfg", "fedboost"],
                    choices=["eflfg", "fedboost"])
    ap.add_argument("--T", type=int, default=600)
    ap.add_argument("--K", type=int, default=22)
    ap.add_argument("--n-stream", type=int, default=6000)
    ap.add_argument("--clients", type=int, default=100)
    ap.add_argument("--budget", type=float, default=3.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dataset", default=None,
                    help="run the paper expert pool on a real dataset "
                         "instead of the synthetic stream")
    ap.add_argument("--anchors", type=int, default=800)
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="artifact directory (default experiments/scenarios)")
    ap.add_argument("--list", action="store_true",
                    help="list registered scenarios and exit")
    args = ap.parse_args(argv)

    if args.list:
        for name in scenarios.names():
            print(f"{name}: {scenarios.get(name).description}")
        return 0

    names = args.scenarios or list(scenarios.names())
    for name in names:
        scenarios.get(name)          # unknown names fail before any run

    if args.dataset:
        preds, y, costs = _dataset_stream(args.dataset, args.anchors)
    else:
        preds, y, costs = _synthetic_stream(args.K, args.n_stream, 1)
    cfg = SimConfig(n_clients=args.clients, budget=args.budget,
                    seed=args.seed)

    out_dir = os.path.abspath(args.out)
    os.makedirs(out_dir, exist_ok=True)
    for name in names:
        rec = run_scenario(name, args.algos, preds, y, costs, args.T, cfg)
        rec["stream"] = args.dataset or "synthetic"
        path = os.path.join(out_dir, f"{name}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
            f.write("\n")
        line = " ".join(
            f"{algo}: mse={rec['algos'][algo]['final_mse']:.4f} "
            f"viol={rec['algos'][algo]['budget_violations']} "
            f"regret={rec['algos'][algo]['regret_T']:.1f}"
            for algo in args.algos)
        print(f"{name:22s} {line}  -> {os.path.relpath(path)}")
    return 0


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    raise SystemExit(main())
