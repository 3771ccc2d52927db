"""Core EFL-FG algorithm (the paper's contribution).

Public API:
  feedback_graph / feedback_graph_np   Algorithm 1
  dominating_set / dominating_set_np   greedy set cover (Chvatal)
  EFLFGState, init_state, plan_round, update_state, round_step   Algorithm 2
  FedBoostState, fedboost_init, fedboost_round                    baseline
  RegretTracker, theorem1_bound                                   eq. 10/11
"""

from .graph import feedback_graph, feedback_graph_np
from .domset import dominating_set, dominating_set_np, independence_number_np
from . import policy
from .eflfg import (EFLFGState, EFLFGRoundOut, init_state, plan_round,
                    update_state, round_step, make_eflfg_scan_body)
from .fedboost import (FedBoostState, fedboost_init, fedboost_plan,
                       fedboost_update, project_simplex,
                       make_fedboost_scan_body)
from .regret import (RegretCarry, regret_init, regret_update, regret_value,
                     RegretTracker, theorem1_bound)

__all__ = [
    "feedback_graph", "feedback_graph_np",
    "dominating_set", "dominating_set_np", "independence_number_np",
    "policy",
    "EFLFGState", "EFLFGRoundOut", "init_state", "plan_round",
    "update_state", "round_step", "make_eflfg_scan_body",
    "FedBoostState", "fedboost_init", "fedboost_plan", "fedboost_update",
    "project_simplex", "make_fedboost_scan_body",
    "RegretCarry", "regret_init", "regret_update", "regret_value",
    "RegretTracker", "theorem1_bound",
]
