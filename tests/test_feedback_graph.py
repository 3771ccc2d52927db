"""Algorithm 1 (feedback-graph generation): properties + oracle match."""

import numpy as np
import jax.numpy as jnp
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import feedback_graph, feedback_graph_np

settings.register_profile("ci", max_examples=12, deadline=None,
                          database=None, derandomize=True)
settings.load_profile("ci")


def _case(seed, K, budget_mult):
    rng = np.random.default_rng(seed)
    w = rng.uniform(0.05, 1.0, K)
    c = rng.uniform(0.05, 1.0, K)
    B = budget_mult * c.max()      # (a3): B >= max cost
    return w, c, B


@given(st.integers(0, 10_000), st.sampled_from([3, 8, 22]),
       st.floats(1.0, 6.0))
def test_budget_never_violated(seed, K, budget_mult):
    """The hard guarantee of the paper: every out-neighborhood costs <= B,
    so ANY drawn node yields a transmit set within budget."""
    w, c, B = _case(seed, K, budget_mult)
    adj = np.asarray(feedback_graph(jnp.log(w), jnp.asarray(c),
                                    jnp.float32(B), jnp.ones((K, K), bool)))
    for k in range(K):
        assert c[adj[k]].sum() <= B + 1e-5


@given(st.integers(0, 10_000), st.sampled_from([3, 8, 22]), st.floats(1.0, 4.0))
def test_self_loops_always_present(seed, K, budget_mult):
    w, c, B = _case(seed, K, budget_mult)
    adj = np.asarray(feedback_graph(jnp.log(w), jnp.asarray(c),
                                    jnp.float32(B), jnp.ones((K, K), bool)))
    assert np.diag(adj).all()


@given(st.integers(0, 5_000), st.sampled_from([4, 9]), st.floats(1.2, 4.0))
def test_matches_numpy_oracle(seed, K, budget_mult):
    """lax.while_loop implementation == literal pseudo-code transcription."""
    w, c, B = _case(seed, K, budget_mult)
    adj_j = np.asarray(feedback_graph(jnp.log(w), jnp.asarray(c),
                                      jnp.float32(B), jnp.ones((K, K), bool)))
    adj_n = feedback_graph_np(w, c, B)
    assert (adj_j == adj_n).all()


@given(st.integers(0, 5_000), st.sampled_from([5, 10]))
def test_weight_constraint_monotone(seed, K):
    """With a previous neighborhood, the new neighborhood's weight sum
    never exceeds the previous one's under the same weights (eq. 2's
    second constraint), and the builder matches the literal oracle."""
    rng = np.random.default_rng(seed)
    w = rng.uniform(0.05, 1.0, K)
    c = rng.uniform(0.05, 0.5, K)
    B = 3.0
    prev = (rng.random((K, K)) < 0.4) | np.eye(K, dtype=bool)  # binding
    adj = np.asarray(feedback_graph(jnp.log(w), jnp.asarray(c),
                                    jnp.float32(B), jnp.asarray(prev)))
    for k in range(K):
        # self loop always allowed; appended nodes respect the cap
        extra = adj[k] & (np.arange(K) != k)
        if extra.any():
            assert w[adj[k]].sum() <= w[prev[k]].sum() * (1 + 1e-4)
    assert (adj == feedback_graph_np(w, c, B, prev)).all()


@given(st.integers(0, 5_000))
def test_ineligible_leader_extreme_spread_matches_oracle(seed):
    """Regression (per-row score shift): a high-weight node that is
    *ineligible* (cost alone exceeds the budget) must not crush the
    eq.-(3) scores of the candidates that actually compete.  With the old
    global-max shift, eligible weights ~120 nats below the leader all
    underflowed to ratio 0 and the argmax degenerated to lowest-index;
    the per-row eligible shift keeps them exact.  Non-hypothesis batched
    coverage: tests/test_feedback_graph_batched.py."""
    K = 10
    r = np.random.default_rng(seed)
    lw = np.zeros(K)
    lw[1:] = -120.0 + r.uniform(0.0, 5.0, K - 1)
    c = np.empty(K)
    c[0] = 10.0                       # leader can never be appended
    c[1:] = r.uniform(0.1, 1.0, K - 1)
    adj = np.asarray(feedback_graph(jnp.asarray(lw, jnp.float32),
                                    jnp.asarray(c, jnp.float32),
                                    jnp.float32(3.0), jnp.ones((K, K), bool)))
    adj_np = feedback_graph_np(np.exp(lw), c, 3.0)
    assert (adj == adj_np).all()


def test_greedy_prefers_cheap_high_weight():
    """eq. (3): among eligible nodes the max w/(cost_sum + c) is appended
    first — a cheap good model beats an expensive equal one."""
    w = np.array([1.0, 0.9, 0.9])
    c = np.array([1.0, 1.0, 0.1])
    adj = feedback_graph_np(w, c, 1.2)
    # node 0: budget 1.2, self costs 1.0 -> only node 2 (c=0.1) fits
    assert adj[0, 2] and not adj[0, 1]


def test_larger_budget_denser_graph():
    rng = np.random.default_rng(1)
    K = 12
    w = rng.uniform(0.1, 1.0, K)
    c = rng.uniform(0.1, 1.0, K)
    edges = []
    for B in (1.0, 2.0, 4.0, 8.0):
        adj = feedback_graph_np(w, c, B * c.max())
        edges.append(adj.sum())
    assert edges == sorted(edges), f"density should grow with budget {edges}"
