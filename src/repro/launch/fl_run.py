"""Paper-experiment driver: run EFL-FG / FedBoost on the three datasets.

    PYTHONPATH=src python -m repro.launch.fl_run --dataset ccpp --T 1500 \
        --algo eflfg --budget 3
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from repro.data import make_dataset, pretrain_split
from repro.experts import build_paper_pool, pool_predict_all
from repro.federated import SimConfig, run_simulation
from repro.configs import PAPER_EFL


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="ccpp",
                    choices=list(PAPER_EFL.datasets))
    ap.add_argument("--algo", default="eflfg",
                    choices=["eflfg", "fedboost", "both"])
    ap.add_argument("--T", type=int, default=None)
    ap.add_argument("--budget", type=float, default=PAPER_EFL.budget)
    ap.add_argument("--clients", type=int,
                    default=PAPER_EFL.clients_per_round)
    ap.add_argument("--anchors", type=int, default=800)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scenario", default=None,
                    help="run under a registered non-stationary scenario "
                         "(see python -m repro.launch.scenario_run --list)")
    args = ap.parse_args()

    T = args.T or PAPER_EFL.rounds[args.dataset]
    ds = make_dataset(args.dataset)
    (xp, yp), (xs, ys) = pretrain_split(ds, frac=PAPER_EFL.pretrain_frac)
    print(f"# {args.dataset}: {ds.x.shape}, pretrain {xp.shape[0]}, "
          f"stream {xs.shape[0]}")
    pool = build_paper_pool(xp, yp, subsample_anchors=args.anchors)
    preds = pool_predict_all(pool, xs)

    algos = ["eflfg", "fedboost"] if args.algo == "both" else [args.algo]
    for algo in algos:
        res = run_simulation(
            algo, preds, ys, pool.costs, T=T,
            cfg=SimConfig(budget=args.budget, clients_per_round=args.clients,
                          seed=args.seed),
            scenario=args.scenario)
        print(json.dumps({
            "algo": algo, "dataset": args.dataset, "T": T,
            "scenario": args.scenario,
            "MSE_T": res.final_mse,
            "budget_violence_pct": 100 * res.violation_frac,
            "mean_sel": float(res.sel_sizes.mean()),
            "mean_domset": float(res.dom_sizes.mean()),
            "regret_T": float(res.regret.regret_curve()[-1]),
            "best_expert": pool.names[res.regret.best_model()],
        }, indent=1))


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    main()
