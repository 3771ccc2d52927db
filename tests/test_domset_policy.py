"""Dominating set (greedy set cover) + PMF/IS-estimate properties."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import (feedback_graph, dominating_set, dominating_set_np,
                        independence_number_np, policy)

settings.register_profile("ci", max_examples=12, deadline=None,
                          database=None, derandomize=True)
settings.load_profile("ci")


def _graph(seed, K, B=3.0):
    rng = np.random.default_rng(seed)
    w = rng.uniform(0.05, 1.0, K)
    c = rng.uniform(0.05, 1.0, K)
    return np.asarray(feedback_graph(jnp.log(w), jnp.asarray(c),
                                     jnp.float32(B), jnp.ones((K, K), bool)))


@given(st.integers(0, 10_000), st.sampled_from([3, 8, 22]))
def test_dominating_set_covers(seed, K):
    adj = _graph(seed, K)
    dom = np.asarray(dominating_set(jnp.asarray(adj)))
    assert adj[dom].any(axis=0).all(), "every vertex must be dominated"
    dom_np = dominating_set_np(adj)
    assert adj[dom_np].any(axis=0).all()


@given(st.integers(0, 10_000), st.sampled_from([4, 12]))
def test_pmf_is_distribution_with_floor(seed, K):
    adj = _graph(seed, K)
    dom = dominating_set(jnp.asarray(adj))
    rng = np.random.default_rng(seed)
    log_u = jnp.asarray(rng.normal(0, 2, K), jnp.float32)
    xi = 0.2
    p = np.asarray(policy.pmf(log_u, dom, jnp.float32(xi)))
    assert abs(p.sum() - 1.0) < 1e-5
    assert (p >= 0).all()
    dsize = int(np.asarray(dom).sum())
    # eq. (4): p_k > xi/|D| for k in D
    assert (p[np.asarray(dom)] >= xi / dsize - 1e-6).all()
    # every vertex observable: q_k = sum_{j in N_in(k)} p_j > 0
    q = np.asarray(policy.observation_probs(jnp.asarray(adj), jnp.asarray(p)))
    assert (q > xi / dsize - 1e-6).all()


def test_is_estimates_unbiased():
    """E[ell_k] over the node draw equals the true summed loss (eq. 19a)."""
    K = 6
    rng = np.random.default_rng(3)
    adj = _graph(7, K)
    adj_j = jnp.asarray(adj)
    dom = dominating_set(adj_j)
    log_u = jnp.asarray(rng.normal(0, 1, K), jnp.float32)
    p = policy.pmf(log_u, dom, jnp.float32(0.2))
    q = policy.observation_probs(adj_j, p)
    losses = jnp.asarray(rng.uniform(0, 1, K), jnp.float32)

    est = np.zeros(K)
    p_np = np.asarray(p)
    for i in range(K):                      # exact expectation over draws
        sel = adj_j[i]
        ell, _ = policy.is_loss_estimates(losses, jnp.float32(0.5), sel,
                                          jnp.int32(i), p, q)
        est += p_np[i] * np.asarray(ell)
    assert np.allclose(est, np.asarray(losses), atol=1e-4), (est, losses)


def test_exp_weight_update_matches_eq9():
    log_w = jnp.asarray([0.0, -1.0, 2.0])
    ell = jnp.asarray([1.0, 0.0, 3.0])
    out = np.asarray(policy.exp_weight_update(log_w, jnp.float32(0.5), ell))
    expected = np.array([0.0, -1.0, 2.0]) - 0.5 * np.array([1.0, 0.0, 3.0])
    assert np.allclose(out, expected)


def test_independence_number_budget_relation():
    """alpha(G) shrinks as the budget grows (paper's discussion of (11))."""
    rng = np.random.default_rng(5)
    K = 14
    w = rng.uniform(0.1, 1.0, K)
    c = rng.uniform(0.1, 1.0, K)
    alphas = []
    for B in (1.0, 3.0, 10.0):
        adj = np.asarray(feedback_graph(jnp.log(w), jnp.asarray(c),
                                        jnp.float32(B * c.max()),
                                        jnp.ones((K, K), bool)))
        alphas.append(independence_number_np(adj))
    assert alphas[0] >= alphas[-1]
    assert alphas[-1] >= 1
