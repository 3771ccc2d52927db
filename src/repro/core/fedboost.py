"""FedBoost baseline (Hamer, Mohri, Suresh; ICML 2020), streaming variant.

FedBoost learns the ensemble mixture weights alpha (a point on the
K-simplex) by projected stochastic gradient on the ensemble loss, while
*sampling* the transmitted subset so that only the **expected** cost meets
the budget — the instantaneous cost can exceed it, which is exactly the
"budget violence" column of the paper's Table I.  Subset sampling in
FedBoost is quality-blind (it exists to control communication, not to
exploit): each model is included independently with

    pi_k = min(1, B / sum_j c_j)        =>  E[cost] = sum_k pi_k c_k <= B.

Gradients for unsampled models are zero; sampled models get the
importance-weighted gradient g_k / pi_k, keeping the estimator unbiased.
Per the paper's §IV, clients are streaming: each contributes the gradient
of its single newly-observed sample.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .numerics import fma_fence, ladder_sum

__all__ = ["FedBoostState", "fedboost_init", "fedboost_plan",
           "fedboost_update", "project_simplex", "make_fedboost_scan_body"]


class FedBoostState(NamedTuple):
    alpha: jnp.ndarray   # (K,) mixture weights on the simplex
    t: jnp.ndarray


def fedboost_init(K: int) -> FedBoostState:
    return FedBoostState(alpha=jnp.full((K,), 1.0 / K),
                         t=jnp.zeros((), jnp.int32))


def project_simplex(v: jnp.ndarray) -> jnp.ndarray:
    """Euclidean projection onto the probability simplex (Duchi et al.)."""
    K = v.shape[0]
    u = jnp.sort(v)[::-1]
    css = jnp.cumsum(u)
    ks = jnp.arange(1, K + 1, dtype=v.dtype)
    cond = u + (1.0 - css) / ks > 0
    rho = jnp.max(jnp.where(cond, jnp.arange(K), -1))
    lam = (1.0 - css[rho]) / (rho + 1.0)
    return jnp.maximum(v + lam, 0.0)


def _inclusion_probs(costs: jnp.ndarray, budget: jnp.ndarray) -> jnp.ndarray:
    K = costs.shape[0]
    pi = jnp.minimum(1.0, budget / jnp.maximum(jnp.sum(costs), 1e-12))
    return jnp.full((K,), pi)


def fedboost_plan(state: FedBoostState, key: jax.Array, costs: jnp.ndarray,
                  budget: jnp.ndarray):
    """Sample the round's transmit subset.  Returns (sel, pi, mix, cost)."""
    K = state.alpha.shape[0]
    pi = _inclusion_probs(costs, budget)
    sel = jax.random.uniform(key, (K,)) < pi
    # guarantee at least one transmitted model (highest current weight)
    best = jnp.argmax(state.alpha)
    sel = sel | ((jnp.arange(K) == best) & ~jnp.any(sel))
    # ladder reductions (core.numerics) keep the mixing bit-identical in
    # every program variant
    masked = jnp.where(sel, state.alpha, 0.0)
    mix = masked / jnp.maximum(ladder_sum(masked), 1e-12)
    cost = ladder_sum(jnp.where(sel, costs, 0.0))
    return sel, pi, mix, cost


def fedboost_update(state: FedBoostState, sel: jnp.ndarray, pi: jnp.ndarray,
                    grad_alpha: jnp.ndarray, lr: jnp.ndarray) -> FedBoostState:
    """Projected SGD step with importance-weighted sparse gradients."""
    g = jnp.where(sel, grad_alpha / pi, 0.0)
    # the fence pins lr*g to round before the subtraction in every
    # program variant (vmap widths, shard_map partitions, fused kernels)
    # — otherwise the backend may FMA-contract it in some programs but
    # not others and alpha drifts an ulp between them (numerics.fma_fence)
    alpha = project_simplex(state.alpha - fma_fence(lr * g))
    return FedBoostState(alpha=alpha, t=state.t + 1)


def make_fedboost_scan_body(grad_fn, costs: jnp.ndarray, budget: jnp.ndarray,
                            lr: jnp.ndarray):
    """Build a ``lax.scan`` body for one streaming FedBoost round.

    ``grad_fn((sel, pi, mix, cost), loss_carry, sched) -> (grad_alpha,
    new_loss_carry, out)`` supplies the clients' SGD gradient of the
    ensemble loss w.r.t. the mixture weights (fixed-shape, traceable).
    The scan ``xs`` slice ``x`` is ``None`` (stationary — the
    pre-scenario program, round budget = ``budget``, ``sched=None``) or
    a ``repro.scenarios.ScheduleArrays`` slice (round budget scaled by
    ``x.budget_scale``, ``sched = (x.active, x.label_shift)``) — the
    same contract as ``make_eflfg_scan_body``.
    The scan carry is ``(FedBoostState, prng_key, loss_carry)`` with the
    same key-splitting discipline as the reference loop.
    """

    def body(carry, x):
        state, key, loss_carry = carry
        key, ksub = jax.random.split(key)
        if x is None:
            budget_t, sched = budget, None
        else:
            budget_t = budget * x.budget_scale
            sched = (x.active, x.label_shift)
        with jax.named_scope("round.draw"):
            sel, pi, mix, cost = fedboost_plan(state, ksub, costs, budget_t)
        grad, loss_carry, out = grad_fn((sel, pi, mix, cost), loss_carry,
                                        sched)
        with jax.named_scope("round.update"):
            state = fedboost_update(state, sel, pi, grad, lr)
        return (state, key, loss_carry), out

    return body
