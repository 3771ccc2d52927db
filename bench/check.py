"""The comparison that decides ``correct``: the program's lanes against the
reference's, round by round.

A lane is one simulated federation.  Each round's transmit set is a discrete
choice made from float weights, so two correct implementations in different
precisions choose alike until a near-tie falls the other way; from then on
their trajectories are different, equally valid runs.  FedBoost's mixture
has a second such point: when the selected experts' weights sum to almost
nothing, their rounding decides the mixture (the reference marks those
rounds, ``stable``).  So the comparison holds each lane to the reference up
to the first round whose transmit set differs or that the reference marks:

* ``eval_gap``  the widest gap, over those rounds, between the clients'
  mean squared ensemble error as the program reports it and as the
  reference computes it, as a share of the lane's MSE over all its rounds
  in the reference: the client evaluation (kernel), the eq.-(5) mixture
  and every weight update before it are in this number.  Rounds whose
  selected log-weights are too large for float32 to give the mixture to
  1e-5 (the reference marks them, ``resolved``) are left out of it;
* ``agree_min`` the fewest such rounds in any lane: a program that
  updates nothing, or draws from the wrong distribution, parts from the
  reference within a few rounds.
"""

from __future__ import annotations

import numpy as np

from bench import reference
from bench.harness import Check


def reference_lanes(lanes, data, config: dict, dtype=np.float64) -> dict:
    """The reference's ``sel`` and ``ens_sq_mean`` for ``lanes``, a list of
    ``(algo, seed, budget)``, in their order; ``data`` is ``(preds, y,
    costs)`` on the host."""
    preds, y, costs = data
    T, K = int(config["T"]), preds.shape[0]
    out = {"sel": np.zeros((len(lanes), T, K), bool),
           "ens_sq_mean": np.zeros((len(lanes), T)),
           "stable": np.ones((len(lanes), T), bool),
           "resolved": np.ones((len(lanes), T), bool)}
    for algo in sorted({lane[0] for lane in lanes}):
        idx = [i for i, lane in enumerate(lanes) if lane[0] == algo]
        uni = reference.draw_uniforms([lanes[i][1] for i in idx], T, K, algo)
        ref = reference.run_lanes(
            algo, preds, y, costs, T, [lanes[i][2] for i in idx], uni,
            window=config["clients_per_round"],
            loss_scale=config["loss_scale"], dtype=dtype)
        for k in out:
            out[k][idx] = ref[k]
    return out


def compare(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: ``sel`` (L, T, K) bool and ``ens_sq_mean`` (L, T);
    ``ref`` also ``stable`` (L, T) bool."""
    sel_p, sel_r = np.asarray(prog["sel"]), np.asarray(ref["sel"])
    if sel_p.shape != sel_r.shape:
        return {"eval_gap": float("nan"), "agree_min": 0}
    L, T, _ = sel_r.shape
    differ = (sel_p != sel_r).any(-1) | ~np.asarray(ref["stable"])
    first = np.where(differ.any(-1), np.argmax(differ, -1), T)
    ens_r = np.asarray(ref["ens_sq_mean"], np.float64)
    ens_p = np.asarray(prog["ens_sq_mean"], np.float64)
    gap = np.abs(ens_p - ens_r) / ens_r.mean(-1, keepdims=True)
    agreed = np.arange(T)[None, :] < first[:, None]
    if "resolved" in ref:
        agreed &= np.asarray(ref["resolved"])
    eval_gap = float(np.max(np.where(agreed, gap, 0.0)))
    if not np.isfinite(ens_p[agreed]).all():
        eval_gap = float("nan")
    return {"eval_gap": eval_gap, "agree_min": int(first.min())}


def checks(numbers: dict, limits: dict) -> list:
    """``Check`` rows for the numbers the traffic file gives limits for."""
    out = []
    for name, limit in limits.items():
        out.append(Check(name, float(numbers[name]), float(limit),
                         at_least=name == "agree_min"))
    return out
