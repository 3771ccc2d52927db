"""Pure-jnp oracle for the fused EFL-FG server-round kernels.

One Algorithm-2 server round splits into two device-side halves around
the client exchange:

* **plan** (before models are sent): Algorithm-1 feedback graph, greedy
  dominating set, the eq.-(4) PMF, the I_t draw, the transmit set
  S_t = N_out(I_t), the eq.-(5) mixture, and the round cost;
* **update** (after client losses return): eq.-(7) observation
  probabilities, the eq.-(6)/(8) importance-sampled estimates and both
  eq.-(9) exponential-weight updates (the next round's eq.-(2) bound
  reads the plan's graph, so the update computes nothing for it).

The reference here composes the *actual* core implementations
(``repro.core.graph`` / ``domset`` / ``policy``), so it is bit-equal to
``eflfg.plan_round`` / ``eflfg.update_state`` by construction — with one
deliberate deviation: the node draw consumes a precomputed Gumbel vector
instead of a PRNG key.  ``jax.random.categorical(key, logits)`` is
exactly ``argmax(gumbel(key, logits.shape, logits.dtype) + logits)``, so
sampling the Gumbels outside and taking the argmax inside reproduces
``policy.draw_node`` bit-for-bit while keeping the kernel free of PRNG
state (pinned by ``tests/test_server_round.py``).

``server_round_np`` is the independent float64 NumPy transcription both
halves are additionally tested against.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

import jax.numpy as jnp

from repro.core import policy
from repro.core.graph import feedback_graph, feedback_graph_np
from repro.core.domset import dominating_set, dominating_set_np
from repro.core.numerics import ladder_sum, log

__all__ = ["ServerPlanOut", "ServerUpdateOut", "server_plan_ref",
           "server_update_ref", "server_round_np"]


class ServerPlanOut(NamedTuple):
    adj: jnp.ndarray          # (K, K) bool feedback graph
    dom: jnp.ndarray          # (K,) bool dominating set
    p: jnp.ndarray            # (K,) node PMF
    drawn: jnp.ndarray        # scalar int, I_t
    sel: jnp.ndarray          # (K,) bool transmit set S_t
    mix: jnp.ndarray          # (K,) eq.-(5) mixture weights
    round_cost: jnp.ndarray   # scalar transmit cost of S_t
    graph_iters: jnp.ndarray  # scalar int32 productive append steps


class ServerUpdateOut(NamedTuple):
    log_w: jnp.ndarray           # (K,) updated model confidences
    log_u: jnp.ndarray           # (K,) updated node confidences


def server_plan_ref(log_w, log_u, prev_adj, costs, budget,
                    gumbel, xi) -> ServerPlanOut:
    """Planning half, formula-identical to ``eflfg.plan_round`` with the
    draw refactored to ``argmax(gumbel + log p)`` (module docstring)."""
    adj, iters = feedback_graph(log_w, costs, budget, prev_adj,
                                with_iters=True)
    dom = dominating_set(adj)
    p = policy.pmf(log_u, dom, xi)
    drawn = jnp.argmax(gumbel + log(jnp.maximum(p, 1e-38)))
    sel = adj[drawn]
    mix = policy.ensemble_mix_weights(log_w, sel)
    round_cost = ladder_sum(jnp.where(sel, costs, 0.0))
    return ServerPlanOut(adj, dom, p, drawn, sel, mix, round_cost, iters)


def server_update_ref(adj, p, sel, drawn, model_losses, ens_loss,
                      log_w, log_u, eta) -> ServerUpdateOut:
    """Update half, formula-identical to ``eflfg.update_state``."""
    q = policy.observation_probs(adj, p)
    ell, ell_hat = policy.is_loss_estimates(model_losses, ens_loss, sel,
                                            drawn, p, q)
    new_w = policy.exp_weight_update(log_w, eta, ell)
    new_u = policy.exp_weight_update(log_u, eta, ell_hat)
    return ServerUpdateOut(new_w, new_u)


def server_round_np(log_w, log_u, prev_adj, costs, budget, gumbel,
                    xi, model_losses, ens_loss, eta):
    """Independent float64 NumPy transcription of the full server round
    (plan + update), for the oracle tests.  Same argument convention as
    the two refs; returns ``(ServerPlanOut, ServerUpdateOut)`` as plain
    NumPy arrays.
    """
    log_w = np.asarray(log_w, np.float64)
    log_u = np.asarray(log_u, np.float64)
    costs = np.asarray(costs, np.float64)
    gumbel = np.asarray(gumbel, np.float64)
    K = log_w.shape[0]
    w = np.exp(log_w)       # exp space is safe in float64 at test spreads
    adj = feedback_graph_np(w, costs, float(budget), np.asarray(prev_adj))
    dom = dominating_set_np(adj)
    u = np.exp(log_u - log_u.max())
    exploit = u / u.sum()
    explore = dom.astype(float) / max(dom.sum(), 1)
    p = (1.0 - xi) * exploit + xi * explore
    p = p / p.sum()
    drawn = int(np.argmax(gumbel + np.log(np.maximum(p, 1e-38))))
    sel = adj[drawn]
    masked = np.where(sel, w / w.max(), 0.0)
    mix = masked / masked.sum()
    round_cost = float(costs[sel].sum())
    plan = ServerPlanOut(adj, dom, p, drawn, sel, mix, round_cost,
                         np.int32(0))   # iters not modeled by the oracle

    q = p @ adj.astype(float)
    ell = np.where(sel, np.asarray(model_losses, np.float64)
                   / np.maximum(q, 1e-12), 0.0)
    ell_hat = np.where(np.arange(K) == drawn,
                       float(ens_loss) / np.maximum(p, 1e-12), 0.0)
    new_w = log_w - eta * ell
    new_u = log_u - eta * ell_hat
    return plan, ServerUpdateOut(new_w, new_u)
