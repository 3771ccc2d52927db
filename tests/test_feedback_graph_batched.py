"""Batched-native Algorithm 1 + dominating set: the custom_vmap rules.

Pins the two contracts the de-lockstepped builder introduces:

* **Bit-equality**: ``vmap(feedback_graph)`` / ``vmap(dominating_set)``
  (the batched-native loops) produce exactly the bits of per-lane solo
  calls — adjacency, dominating set, AND the per-lane ``n_iters``
  diagnostic — across heterogeneous budgets, including lanes that
  converge immediately riding next to lanes needing the full K-1 trips.
* **Numerics**: the per-row eligible score shift fixes the
  ineligible-leader degeneracy at extreme weight spreads (regression vs
  the float64 NumPy oracle); the hypothesis twin lives in
  tests/test_feedback_graph.py.

No hypothesis dependency — this file must run on minimal installs and in
the pallas-interpret CI job's environment.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import feedback_graph, feedback_graph_np
from repro.core.domset import dominating_set, dominating_set_np

K = 22


def _rand(seed, B=1):
    rng = np.random.default_rng(seed)
    log_w = jnp.asarray(rng.normal(-1.0, 1.5, (B, K)).astype(np.float32))
    costs = jnp.asarray(rng.uniform(0.05, 1.0, K).astype(np.float32))
    return log_w, costs


def _solo(log_w, costs, budget, prev):
    adj, it = feedback_graph(log_w, costs, budget, prev, with_iters=True)
    return np.asarray(adj), int(it)


@pytest.mark.parametrize("seed", [0, 3])
def test_vmap_bit_equal_to_solo_lanes_hetero_budgets(seed):
    """One flat batched dispatch == B independent solo calls, bit for bit,
    with budgets spanning converge-in-one-trip to needs-all-K-1-trips."""
    B = 8
    log_w, costs = _rand(seed, B)
    # lane 0: budget below any pairwise cost sum -> zero appends, 0 iters;
    # lane B-1: budget covers everything -> K-1 appends.
    budgets = jnp.asarray(
        np.concatenate([[0.05], np.linspace(1.0, 8.0, B - 2),
                        [float(np.sum(np.asarray(costs))) + 1.0]]),
        jnp.float32)
    prev = jnp.ones((B, K, K), bool)

    vfg = jax.jit(jax.vmap(
        lambda lw, b, lp: feedback_graph(lw, costs, b, lp, with_iters=True),
        in_axes=(0, 0, 0)))
    adj_b, it_b = jax.tree.map(np.asarray, vfg(log_w, budgets, prev))
    dom_b = np.asarray(jax.jit(jax.vmap(dominating_set))(jnp.asarray(adj_b)))

    iters = []
    for i in range(B):
        adj_s, it_s = _solo(log_w[i], costs, budgets[i], prev[i])
        assert (adj_b[i] == adj_s).all(), f"lane {i} adjacency diverged"
        assert int(it_b[i]) == it_s, f"lane {i} n_iters diverged"
        assert (dom_b[i] == np.asarray(dominating_set(adj_b[i]))).all()
        iters.append(it_s)
    # the spread this test is about: fast and slow lanes truly co-resident
    assert iters[0] == 0 and max(iters) >= 2


def test_nested_vmap_grid_bit_equal_to_solo():
    """budgets x seeds grid (vmap of vmap, the run_sweep shape) still hits
    the batched rule and matches solo lanes bit-for-bit."""
    n_b, n_s = 3, 4
    log_w, costs = _rand(7, n_s)
    budgets = jnp.asarray([1.0, 3.0, 9.0], jnp.float32)
    prev = jnp.ones((K, K), bool)

    grid = jax.jit(jax.vmap(jax.vmap(
        lambda lw, b: feedback_graph(lw, costs, b, prev, with_iters=True),
        in_axes=(0, None)), in_axes=(None, 0)))
    adj_g, it_g = jax.tree.map(np.asarray, grid(log_w, budgets))
    assert adj_g.shape == (n_b, n_s, K, K)
    for bi in range(n_b):
        for si in range(n_s):
            adj_s, it_s = _solo(log_w[si], costs, budgets[bi], prev)
            assert (adj_g[bi, si] == adj_s).all()
            assert int(it_g[bi, si]) == it_s


def test_graph_iters_invariant_to_batch_composition():
    """A lane's n_iters (and bits) must not depend on its co-residents or
    the batch width — the invariance lockstep-waste accounting relies on."""
    log_w, costs = _rand(11, 4)
    prev = jnp.ones((4, K, K), bool)
    budgets = jnp.asarray([0.2, 2.0, 5.0, 30.0], jnp.float32)
    vfg = jax.jit(jax.vmap(
        lambda lw, b, lp: feedback_graph(lw, costs, b, lp, with_iters=True),
        in_axes=(0, 0, 0)))
    adj4, it4 = jax.tree.map(np.asarray, vfg(log_w, budgets, prev))
    # same lane pair embedded in a width-2 batch
    adj2, it2 = jax.tree.map(np.asarray,
                             vfg(log_w[1:3], budgets[1:3], prev[1:3]))
    assert (adj4[1:3] == adj2).all() and (it4[1:3] == it2).all()
    for i in range(4):
        adj_s, it_s = _solo(log_w[i], costs, budgets[i], prev[i])
        assert (adj4[i] == adj_s).all() and int(it4[i]) == it_s


def test_ineligible_leader_extreme_spread_matches_oracle():
    """Regression for the per-row eligible score shift (see
    graph.feedback_graph's precision note): an unaffordable leader 120
    nats above every eligible candidate used to underflow their scores to
    a lowest-index tie; now the trajectory matches the float64 oracle."""
    for seed in range(20):
        r = np.random.default_rng(seed)
        Kk = 10
        lw = np.zeros(Kk)
        lw[1:] = -120.0 + r.uniform(0.0, 5.0, Kk - 1)
        c = np.empty(Kk)
        c[0] = 10.0
        c[1:] = r.uniform(0.1, 1.0, Kk - 1)
        adj = np.asarray(feedback_graph(jnp.asarray(lw, jnp.float32),
                                        jnp.asarray(c, jnp.float32),
                                        jnp.float32(3.0),
                                        jnp.ones((Kk, Kk), bool)))
        adj_np = feedback_graph_np(np.exp(lw), c, 3.0)
        assert (adj == adj_np).all(), f"seed {seed}"


def test_batched_oracle_agreement_random_cases():
    """vmapped builder vs the literal NumPy transcription across random
    sizes (moderate spreads: the regime every sweep actually runs in)."""
    for seed in range(25):
        r = np.random.default_rng(seed)
        Kk = int(r.integers(3, 12))
        w = r.uniform(0.05, 1.0, Kk)
        c = r.uniform(0.05, 1.0, Kk)
        bud = float(r.uniform(1.0, 4.0) * c.max())
        lw = jnp.asarray(np.log(w), jnp.float32)
        cj = jnp.asarray(c, jnp.float32)
        prev = jnp.ones((Kk, Kk), bool)
        adj_b = np.asarray(jax.vmap(
            lambda l: feedback_graph(l, cj, jnp.float32(bud), prev)
        )(jnp.stack([lw, lw])))
        adj_np = feedback_graph_np(w, c, bud)
        assert (adj_b[0] == adj_np).all() and (adj_b[1] == adj_np).all()


def test_domset_vmap_bit_equal_and_oracle():
    for seed in range(10):
        rng = np.random.default_rng(seed)
        adj = rng.random((6, K, K)) < 0.25
        adj |= np.eye(K, dtype=bool)[None]
        adj_j = jnp.asarray(adj)
        dom_b = np.asarray(jax.jit(jax.vmap(dominating_set))(adj_j))
        for i in range(6):
            dom_s = np.asarray(dominating_set(adj_j[i]))
            assert (dom_b[i] == dom_s).all()
            assert (dom_s == dominating_set_np(adj[i])).all()
            assert adj[i][dom_b[i]].any(axis=0).all()   # actually dominates


def test_round_trip_trajectory_vmap_equals_solo():
    """300-round graph+domset+weight-update trajectory: the full recurrent
    composition the engine runs, vmapped vs per-lane solo, bit-equal."""
    T, B = 120, 4
    log_w0, costs = _rand(5, B)
    budgets = jnp.asarray([1.0, 2.5, 4.0, 8.0], jnp.float32)

    def roll(log_w, bud):
        def body(carry, _):
            lw, prev = carry
            adj, it = feedback_graph(lw, costs, bud, prev, with_iters=True)
            dom = dominating_set(adj)
            lw = lw - 0.01 * (jnp.sum(adj, -1) + dom).astype(jnp.float32)
            return (lw, adj), (adj, dom, it)
        K = log_w.shape[-1]
        _, outs = jax.lax.scan(body, (log_w, jnp.ones((K, K), bool)), None,
                               length=T)
        return outs

    batched = jax.jit(jax.vmap(roll, in_axes=(0, 0)))
    o_b = jax.tree.map(np.asarray, batched(log_w0, budgets))
    for i in range(B):
        o_s = jax.tree.map(np.asarray,
                           jax.jit(roll)(log_w0[i], budgets[i]))
        for got, want, name in zip((a[i] for a in o_b), o_s,
                                   ("adj", "dom", "iters")):
            assert (got == want).all(), f"lane {i} {name}"
