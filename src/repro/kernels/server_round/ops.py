"""Public fused server-round ops: backend dispatch + engine wiring.

``server_plan`` / ``server_update`` are the two launches (interpret mode
on CPU, compiled Pallas on TPU, an error elsewhere), returning the same pytrees as
``ref.server_plan_ref`` / ``ref.server_update_ref``.

``fused_server_round()`` packages them with the
``eflfg.plan_round`` / ``eflfg.update_state`` call signatures so
``make_eflfg_scan_body`` can swap the server implementation behind
``SimConfig.use_fused_server`` without touching the round structure.
The PRNG split stays outside the kernel: the node draw consumes
``numerics.gumbel(key, (K,))``, the noise of ``policy.draw_node``'s
Gumbel-max draw, bit for bit (see ``ref``).
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

from repro.core import numerics

from ..platform import interpret_mode
from .kernel import server_plan_pallas, server_update_pallas
from .ref import ServerPlanOut, ServerUpdateOut

__all__ = ["server_plan", "server_update", "fused_server_round",
           "FusedServerRound"]


def server_plan(log_w, log_u, prev_adj, costs, budget, gumbel, xi,
                *, interpret: Optional[bool] = None) -> ServerPlanOut:
    """One fused planning launch (see ``ref.server_plan_ref`` for exact
    semantics).  Masks come back as bool."""
    if interpret is None:
        interpret = interpret_mode()
    adj, dom, p, drawn, sel, mix, cost, iters = server_plan_pallas(
        log_w, log_u, prev_adj, costs, budget, gumbel, xi,
        interpret=interpret)
    return ServerPlanOut(adj != 0, dom != 0, p, drawn, sel != 0, mix,
                         cost, iters)


def server_update(adj, p, sel, drawn, model_losses, ens_loss, log_w,
                  log_u, eta, *,
                  interpret: Optional[bool] = None) -> ServerUpdateOut:
    """One fused update launch (see ``ref.server_update_ref``)."""
    if interpret is None:
        interpret = interpret_mode()
    new_w, new_u = server_update_pallas(
        adj, p, sel, drawn, model_losses, ens_loss, log_w, log_u, eta,
        interpret=interpret)
    return ServerUpdateOut(new_w, new_u)


class FusedServerRound(NamedTuple):
    """Drop-in server implementation for ``make_eflfg_scan_body``:
    ``plan`` matches ``eflfg.plan_round``, ``update`` matches
    ``eflfg.update_state``."""
    plan: Callable
    update: Callable


def fused_server_round(interpret: Optional[bool] = None) -> FusedServerRound:
    from repro.core.eflfg import EFLFGRoundOut, EFLFGState

    def plan(state, key, costs, budget, xi):
        K = state.log_w.shape[0]
        gumbel = numerics.gumbel(key, (K,))
        out = server_plan(state.log_w, state.log_u, state.prev_adj,
                          costs, budget, gumbel, xi, interpret=interpret)
        return EFLFGRoundOut(out.adj, out.dom, out.p, out.drawn, out.sel,
                             out.mix, out.round_cost, out.graph_iters)

    def update(state, plan_out, model_losses, ens_loss, eta):
        out = server_update(plan_out.adj, plan_out.p, plan_out.sel,
                            plan_out.drawn, model_losses, ens_loss,
                            state.log_w, state.log_u, eta,
                            interpret=interpret)
        return EFLFGState(out.log_w, out.log_u, plan_out.adj, state.t + 1)

    return FusedServerRound(plan, update)
