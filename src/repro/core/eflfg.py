"""EFL-FG server (Algorithm 2), as a pure jitted round step.

The server state carries log-weights for both the per-model confidences
``w`` (eq. 9a) and the per-node ensemble confidences ``u`` (eq. 9b), plus
the previous round's feedback graph, whose out-neighborhoods bound the
next round's weights in eq. (2).

The round step is model-agnostic: it consumes the (K, n_clients) matrix of
per-model *per-client* losses and the (n_clients,) ensemble losses — who
computes those (kernel experts, LLM experts, simulated clients sharded over
a mesh) is the business of `repro.experts` / `repro.federated`.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .graph import feedback_graph
from .domset import dominating_set
from .numerics import ladder_sum
from . import policy

__all__ = ["EFLFGState", "EFLFGRoundOut", "init_state", "plan_round",
           "update_state", "round_step", "make_eflfg_scan_body"]


class EFLFGState(NamedTuple):
    log_w: jnp.ndarray          # (K,) model confidence, log space
    log_u: jnp.ndarray          # (K,) node confidence, log space
    prev_adj: jnp.ndarray       # (K, K) bool previous round's feedback graph
                                # (all True before the first round)
    t: jnp.ndarray              # round counter


class EFLFGRoundOut(NamedTuple):
    adj: jnp.ndarray            # (K, K) feedback graph
    dom: jnp.ndarray            # (K,) dominating set mask
    p: jnp.ndarray              # (K,) node PMF
    drawn: jnp.ndarray          # scalar int, I_t
    sel: jnp.ndarray            # (K,) bool, S_t = N_out(I_t)
    mix: jnp.ndarray            # (K,) eq. (5) ensemble mixture weights
    round_cost: jnp.ndarray     # scalar, sum of costs of S_t
    graph_iters: jnp.ndarray    # scalar int32: the graph builder's OWN
                                # productive append-step count this round
                                # (feeds SweepResult.lockstep_waste)


def init_state(K: int) -> EFLFGState:
    """w_{k,1} = u_{k,1} = 1; no previous neighborhood: every node stands
    in for it, so eq. (2)'s weight bound never binds in round 1."""
    return EFLFGState(
        log_w=jnp.zeros((K,)),
        log_u=jnp.zeros((K,)),
        prev_adj=jnp.ones((K, K), bool),
        t=jnp.zeros((), jnp.int32),
    )


def plan_round(state: EFLFGState, key: jax.Array, costs: jnp.ndarray,
               budget: jnp.ndarray, xi: jnp.ndarray) -> EFLFGRoundOut:
    """Server-side planning: build graph, draw node, emit the transmit set.

    This is the part that must run *before* any model is sent to clients.
    """
    adj, iters = feedback_graph(state.log_w, costs, budget,
                                state.prev_adj, with_iters=True)
    dom = dominating_set(adj)
    p = policy.pmf(state.log_u, dom, xi)
    drawn = policy.draw_node(key, p)
    sel = adj[drawn]
    mix = policy.ensemble_mix_weights(state.log_w, sel)
    round_cost = ladder_sum(jnp.where(sel, costs, 0.0))
    return EFLFGRoundOut(adj, dom, p, drawn, sel, mix, round_cost, iters)


def update_state(state: EFLFGState, plan: EFLFGRoundOut,
                 model_losses: jnp.ndarray, ens_loss: jnp.ndarray,
                 eta: jnp.ndarray) -> EFLFGState:
    """Server-side update after receiving client losses (eqs. 6-9)."""
    q = policy.observation_probs(plan.adj, plan.p)
    ell, ell_hat = policy.is_loss_estimates(
        model_losses, ens_loss, plan.sel, plan.drawn, plan.p, q)
    log_w = policy.exp_weight_update(state.log_w, eta, ell)
    log_u = policy.exp_weight_update(state.log_u, eta, ell_hat)
    # the next round's eq. (2) bounds each row by this round's
    # neighborhood under the weights that round sees (the updated ones)
    return EFLFGState(log_w, log_u, plan.adj, state.t + 1)


def make_eflfg_scan_body(loss_fn, costs: jnp.ndarray, budget: jnp.ndarray,
                         eta: jnp.ndarray, xi: jnp.ndarray,
                         server_round=None):
    """Build a ``lax.scan`` body running one full Algorithm-2 round.

    ``loss_fn(plan, loss_carry, sched) -> (model_losses, ens_loss,
    new_loss_carry, out)`` supplies the client-side evaluation: who the
    clients are, how many of them uplink, what their losses look like.
    Everything it returns must be fixed-shape so the composed body stays
    traceable; the per-round ``out`` pytree is stacked by ``lax.scan``
    into the engine's metric arrays.

    The scan ``xs`` slice ``x`` is either ``None`` — the stationary
    path: every round plans against ``budget`` and ``loss_fn`` receives
    ``sched=None``, tracing exactly the pre-scenario program — or a
    per-round schedule slice (``repro.scenarios.ScheduleArrays``): the
    round's budget becomes ``budget * x.budget_scale`` and ``loss_fn``
    receives ``sched = (x.active, x.label_shift)``.

    The scan carry is ``(EFLFGState, prng_key, loss_carry)`` — the same
    key-splitting discipline as the reference Python loop, so a scan over
    rounds reproduces the loop draw-for-draw.

    ``server_round`` swaps the server implementation: ``None`` composes
    ``plan_round`` / ``update_state`` above, anything else must expose
    ``.plan`` / ``.update`` with the same signatures — the Pallas-fused
    ``repro.kernels.server_round.ops.fused_server_round()`` is the one
    production alternative (``SimConfig.use_fused_server``), bit-equal
    trajectories pinned by ``tests/test_server_round.py``.
    """
    plan_fn = plan_round if server_round is None else server_round.plan
    update_fn = update_state if server_round is None else server_round.update

    def body(carry, x):
        state, key, loss_carry = carry
        key, kdraw = jax.random.split(key)
        if x is None:
            budget_t, sched = budget, None
        else:
            budget_t = budget * x.budget_scale
            sched = (x.active, x.label_shift)
        with jax.named_scope("round.draw"):
            plan = plan_fn(state, kdraw, costs, budget_t, xi)
        model_losses, ens_loss, loss_carry, out = loss_fn(plan, loss_carry,
                                                          sched)
        with jax.named_scope("round.update"):
            state = update_fn(state, plan, model_losses, ens_loss, eta)
        return (state, key, loss_carry), out

    return body


@jax.jit
def round_step(state: EFLFGState, key: jax.Array,
               model_client_losses: jnp.ndarray,
               costs: jnp.ndarray, budget: jnp.ndarray,
               eta: jnp.ndarray, xi: jnp.ndarray):
    """One full Algorithm-2 round when per-(model, client) losses are known.

    ``model_client_losses``: (K, n) matrix of L(f_k(x_i), y_i).  The
    ensemble loss is *not* derivable from it in general (loss of the mix !=
    mix of losses), so callers that can evaluate the true ensemble loss
    should use plan_round/update_state directly; this convenience wrapper
    upper-bounds it by the Jensen mixture (exact for linear losses, upper
    bound for convex ones — consistent with Lemma 2's direction).
    Returns (new_state, plan, ens_loss).
    """
    plan = plan_round(state, key, costs, budget, xi)
    model_losses = jnp.sum(model_client_losses, axis=1)
    ens_loss = jnp.sum(plan.mix @ model_client_losses)
    new_state = update_state(state, plan, model_losses, ens_loss, eta)
    return new_state, plan, ens_loss
