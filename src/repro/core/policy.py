"""Sampling policy and weight updates for EFL-FG (eqs. 4, 6-9).

All weight vectors are kept in log space (see graph.py).  The functions
here are pure and jit-friendly; `eflfg.py` composes them into the round
step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .numerics import (exp, fma_fence, gumbel, ladder_logsumexp,
                       ladder_matvec, ladder_sum, log)

__all__ = [
    "pmf",
    "draw_node",
    "ensemble_mix_weights",
    "observation_probs",
    "is_loss_estimates",
    "exp_weight_update",
]


def pmf(log_u: jnp.ndarray, dom: jnp.ndarray, xi: jnp.ndarray) -> jnp.ndarray:
    """Eq. (4): p_k = (1-xi) u_k / U + xi / |D| * I(k in D)."""
    exploit = exp(log_u - ladder_logsumexp(log_u))
    dsize = jnp.sum(dom)
    explore = dom.astype(exploit.dtype) / jnp.maximum(dsize, 1)
    # the fences pin the two products to round before the mixture add:
    # without them XLA/LLVM may contract one into an FMA in some fusion
    # contexts (vmapped vs flat, fused kernel vs unfused) and the mixture
    # drifts an ulp between program variants (see numerics.fma_fence)
    p = fma_fence((1.0 - xi) * exploit) + fma_fence(xi * explore)
    # guard: renormalize away accumulated fp error so sampling is exact
    # (ladder reductions keep the bits identical inside the fused kernel)
    return p / ladder_sum(p)


def draw_node(key: jax.Array, p: jnp.ndarray) -> jnp.ndarray:
    """Draw I_t ~ p_t by the Gumbel-max trick, as
    ``jax.random.categorical(key, log p)`` does, with ``numerics.log``
    for the noise and the logits."""
    return jnp.argmax(gumbel(key, p.shape) + log(jnp.maximum(p, 1e-38)))


def ensemble_mix_weights(log_w: jnp.ndarray, sel: jnp.ndarray) -> jnp.ndarray:
    """Eq. (5) mixture weights: w_k / W_t restricted to the selected set."""
    masked = jnp.where(sel, log_w, -jnp.inf)
    return exp(masked - ladder_logsumexp(masked))


def observation_probs(adj: jnp.ndarray, p: jnp.ndarray) -> jnp.ndarray:
    """Eq. (7): q_k = sum_{j in N_in(k)} p_j.  adj[j, i] == i in N_out(j),
    so N_in(k) = {j : adj[j, k]} and q = p @ adj."""
    return ladder_matvec(p, adj.astype(p.dtype))


def is_loss_estimates(model_losses: jnp.ndarray, ens_loss: jnp.ndarray,
                      sel: jnp.ndarray, drawn: jnp.ndarray,
                      p: jnp.ndarray, q: jnp.ndarray):
    """Eqs. (6) and (8).

    Args:
      model_losses: (K,) per-model loss summed over the round's clients,
        i.e. ``sum_{i in C_t} L(f_k(x_i), y_i)``.
      ens_loss: scalar ensemble loss summed over clients.
      sel: (K,) bool — S_t, out-neighbors of the drawn node.
      drawn: scalar int — I_t.
      p, q: (K,) node-draw and observation probabilities.

    Returns (ell, ell_hat): the importance-sampled estimates (K,).
    """
    K = p.shape[0]
    ell = jnp.where(sel, model_losses / jnp.maximum(q, 1e-12), 0.0)
    onehot = jnp.arange(K) == drawn
    ell_hat = jnp.where(onehot, ens_loss / jnp.maximum(p, 1e-12), 0.0)
    return ell, ell_hat


def exp_weight_update(log_v: jnp.ndarray, eta: jnp.ndarray,
                      ell: jnp.ndarray) -> jnp.ndarray:
    """Eq. (9) in log space: log v_{t+1} = log v_t - eta * ell.

    The fence forces the product to round before the subtraction in
    every program variant — otherwise XLA/LLVM contracts ``mul`` +
    ``sub`` into an FMA in some fusion contexts but not others (the
    vmapped interpret-mode Pallas grid contracts even through an
    ``optimization_barrier``), and the weight state — which feeds back
    into every later round's selection — drifts an ulp between the
    fused kernel and the unfused scan."""
    return log_v - fma_fence(eta * ell)
