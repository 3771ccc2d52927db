"""Parity pins for the fused EFL-FG server-round kernels.

The contract under test (repro/kernels/server_round/): the two Pallas
launches — plan and update — are *bit-equal* to the unfused
``eflfg.plan_round`` / ``eflfg.update_state`` composition, in every
execution context the engine uses them from: single launch, flat
``lax.scan``, and vmapped sweep/batch (where XLA's per-fusion FMA
contraction used to break parity until ``numerics.fma_fence``; the
long-scan tests here are the regression pins for that).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import eflfg, policy
from repro.core import numerics
from repro.core.numerics import (fma_fence, ladder_logsumexp, ladder_sum,
                                 ladder_matvec)
from repro.kernels.server_round import ops, ref


def _round1_state(K):
    return eflfg.init_state(K)


def _evolved_state(K, rounds, seed):
    """A realistic mid-trajectory state: run the unfused server for a few
    rounds on synthetic losses (full pipeline not needed — the server
    only sees aggregate losses)."""
    rng = np.random.default_rng(seed)
    costs = jnp.asarray(rng.uniform(0.1, 1.0, K).astype(np.float32))
    ml = jnp.asarray(rng.uniform(0, 5, (rounds, K)).astype(np.float32))
    el = jnp.asarray(rng.uniform(0, 5, rounds).astype(np.float32))

    def body(carry, x):
        state, key = carry
        key, kdraw = jax.random.split(key)
        plan = eflfg.plan_round(state, kdraw, costs, jnp.float32(3.0),
                                jnp.float32(0.05))
        new = eflfg.update_state(state, plan, x[0], x[1], jnp.float32(0.02))
        return (new, key), None

    (state, _), _ = jax.lax.scan(
        body, (eflfg.init_state(K), jax.random.PRNGKey(seed)), (ml, el))
    return state, costs


def _cases(K):
    yield _round1_state(K), jnp.asarray(
        np.random.default_rng(K).uniform(0.1, 1.0, K).astype(np.float32))
    for seed in (0, 7):
        yield _evolved_state(K, 60, seed)


@pytest.mark.parametrize("K", [22, 5])
def test_plan_kernel_matches_unfused(K):
    """One fused planning launch == jitted plan_round, bit for bit (the
    gumbel-vector draw reproduces the categorical draw exactly)."""
    plan_ref = jax.jit(eflfg.plan_round)
    for i, (state, costs) in enumerate(_cases(K)):
        key = jax.random.PRNGKey(100 + i)
        budget, xi = jnp.float32(3.0), jnp.float32(0.05)
        want = plan_ref(state, key, costs, budget, xi)
        got = ops.fused_server_round().plan(state, key, costs, budget, xi)
        for f in want._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, f)), np.asarray(getattr(want, f)),
                err_msg=f"plan field {f} (case {i})")


@pytest.mark.parametrize("K", [22, 5])
def test_update_kernel_matches_unfused(K):
    upd_ref = jax.jit(eflfg.update_state)
    plan_ref = jax.jit(eflfg.plan_round)
    for i, (state, costs) in enumerate(_cases(K)):
        rng = np.random.default_rng(200 + i)
        key = jax.random.PRNGKey(300 + i)
        plan = plan_ref(state, key, costs, jnp.float32(3.0),
                        jnp.float32(0.05))
        ml = jnp.asarray(rng.uniform(0, 5, K).astype(np.float32))
        el = jnp.float32(rng.uniform(0, 5))
        eta = jnp.float32(0.02)
        want = upd_ref(state, plan, ml, el, eta)
        got = ops.fused_server_round().update(state, plan, ml, el, eta)
        for f in want._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, f)), np.asarray(getattr(want, f)),
                err_msg=f"update field {f} (case {i})")


def test_gumbel_argmax_reproduces_categorical():
    """The kernel's PRNG-free draw — argmax(gumbel + log p) with the
    Gumbel vector sampled outside — equals policy.draw_node bit-for-bit
    (same key), for many keys and PMF shapes, and the draw is
    ``jax.random.categorical``'s."""
    K = 22
    rng = np.random.default_rng(3)
    for i in range(20):
        p = rng.dirichlet(np.full(K, 0.3)).astype(np.float32)
        p = jnp.asarray(p)
        key = jax.random.PRNGKey(i)
        want = policy.draw_node(key, p)
        gumbel = numerics.gumbel(key, (K,))
        got = jnp.argmax(gumbel + numerics.log(jnp.maximum(p, 1e-38)))
        assert int(got) == int(want)
        cat = jax.random.categorical(key, jnp.log(jnp.maximum(p, 1e-38)))
        assert int(cat) == int(want)


@pytest.mark.parametrize("K", [22, 6])
def test_matches_float64_oracle(K):
    """Both launches vs the independent float64 NumPy transcription:
    discrete outputs exact, continuous within float32 tolerance."""
    for i, (state, costs) in enumerate(_cases(K)):
        rng = np.random.default_rng(400 + i)
        key = jax.random.PRNGKey(500 + i)
        gumbel = jax.random.gumbel(key, (K,), jnp.float32)
        budget, xi, eta = 3.0, 0.05, 0.02
        ml = rng.uniform(0, 5, K).astype(np.float32)
        el = np.float32(rng.uniform(0, 5))
        plan_np, upd_np = ref.server_round_np(
            state.log_w, state.log_u, state.prev_adj, costs, budget,
            gumbel, xi, ml, el, eta)
        plan = ops.server_plan(state.log_w, state.log_u, state.prev_adj,
                               costs, jnp.float32(budget), gumbel,
                               jnp.float32(xi))
        np.testing.assert_array_equal(np.asarray(plan.adj), plan_np.adj)
        np.testing.assert_array_equal(np.asarray(plan.dom), plan_np.dom)
        assert int(plan.drawn) == plan_np.drawn
        np.testing.assert_array_equal(np.asarray(plan.sel), plan_np.sel)
        np.testing.assert_allclose(np.asarray(plan.p), plan_np.p,
                                   rtol=0, atol=1e-6)
        np.testing.assert_allclose(np.asarray(plan.mix), plan_np.mix,
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(float(plan.round_cost),
                                   plan_np.round_cost, rtol=1e-6)
        upd = ops.server_update(plan.adj, plan.p, plan.sel, plan.drawn, ml,
                                el, state.log_w, state.log_u,
                                jnp.float32(eta))
        np.testing.assert_allclose(np.asarray(upd.log_w), upd_np.log_w,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(upd.log_u), upd_np.log_u,
                                   rtol=1e-5, atol=1e-6)


def _server_scan(server_round, vmapped, costs, ml_all, el_all):
    """Server-only scan harness (no client eval): the sharpest detector
    of fused-vs-unfused drift, comparing full weight-state trajectories."""
    K = costs.shape[0]
    plan_fn = (eflfg.plan_round if server_round is None
               else server_round.plan)
    upd_fn = (eflfg.update_state if server_round is None
              else server_round.update)
    budget, xi, eta = jnp.float32(3.0), jnp.float32(0.05), jnp.float32(0.02)

    def body(carry, x):
        state, key = carry
        key, kdraw = jax.random.split(key)
        plan = plan_fn(state, kdraw, costs, budget, xi)
        new = upd_fn(state, plan, x[0], x[1], eta)
        out = dict(drawn=plan.drawn, sel=plan.sel, cost=plan.round_cost,
                   log_w=new.log_w, log_u=new.log_u,
                   prev_adj=new.prev_adj)
        return (new, key), out

    def solo(seed):
        init = (eflfg.init_state(K), jax.random.PRNGKey(seed))
        return jax.lax.scan(body, init, (ml_all, el_all))[1]

    return jax.jit(jax.vmap(solo) if vmapped else solo)


def test_long_scan_trajectories_bit_equal_flat_and_vmapped():
    """The tentpole pin: fused == unfused over a long scan, for the flat
    program AND the vmapped program, comparing every weight-state and
    selection trajectory bit-for-bit.  The vmapped half regresses
    immediately (round ~1 of log_w) if the eq.-(9)/(4) products lose
    their ``fma_fence`` — XLA contracts mul+sub into FMA per fusion
    cluster, straight through ``optimization_barrier``."""
    K, T, B = 22, 800, 2
    rng = np.random.default_rng(1)
    costs = jnp.asarray(rng.uniform(0.1, 1.0, K).astype(np.float32))
    ml_all = jnp.asarray(rng.uniform(0, 5, (T, K)).astype(np.float32))
    el_all = jnp.asarray(rng.uniform(0, 5, T).astype(np.float32))
    fr = ops.fused_server_round()
    seeds = jnp.arange(B)

    flat_u = _server_scan(None, False, costs, ml_all, el_all)(jnp.int32(0))
    flat_f = _server_scan(fr, False, costs, ml_all, el_all)(jnp.int32(0))
    vm_u = _server_scan(None, True, costs, ml_all, el_all)(seeds)
    vm_f = _server_scan(fr, True, costs, ml_all, el_all)(seeds)

    for k in flat_u:
        np.testing.assert_array_equal(
            np.asarray(flat_f[k]), np.asarray(flat_u[k]),
            err_msg=f"flat fused-vs-unfused {k}")
        np.testing.assert_array_equal(
            np.asarray(vm_f[k]), np.asarray(vm_u[k]),
            err_msg=f"vmapped fused-vs-unfused {k}")
        np.testing.assert_array_equal(
            np.asarray(vm_f[k])[0], np.asarray(flat_f[k]),
            err_msg=f"fused vmap-lane0-vs-flat {k}")


def test_full_pipeline_identical_and_sweep_parity():
    """Wiring pin: ``SimConfig.use_fused_server`` swaps the server inside
    the full engine (client eval + scan) without changing one bit —
    flat run and a heterogeneous-budget sweep (the vmapped + bucketed
    dispatch path)."""
    import dataclasses
    from repro.federated.engine import run_simulation_scan, run_sweep
    from repro.federated.simulation import SimConfig

    K, n_stream, T = 8, 400, 300
    rng = np.random.default_rng(0)
    preds = jnp.asarray(rng.normal(0, 1, (K, n_stream)).astype(np.float32))
    y = jnp.asarray(rng.normal(0, 1, n_stream).astype(np.float32))
    costs = jnp.asarray(rng.uniform(0.1, 1.0, K).astype(np.float32))
    cfg_u = SimConfig(n_clients=40, clients_per_round=40, budget=3.0,
                      eta=0.02, xi=0.05, seed=0)
    cfg_f = dataclasses.replace(cfg_u, use_fused=True,
                                use_fused_server=True)
    assert cfg_u.static_key(T) != cfg_f.static_key(T)

    a = run_simulation_scan("eflfg", preds, y, costs, T, cfg_u)
    b = run_simulation_scan("eflfg", preds, y, costs, T, cfg_f)
    bad = [f for f, ok in a.identical_fields(b).items() if not ok]
    assert not bad, f"flat fused-server run differs: {bad}"

    sa = run_sweep("eflfg", preds, y, costs, 200, cfg_u, seeds=[0, 1],
                   budgets=[2.0, 4.5])
    sb = run_sweep("eflfg", preds, y, costs, 200, cfg_f, seeds=[0, 1],
                   budgets=[2.0, 4.5])
    for f in ("mse_curves", "regret_curves", "sel_sizes", "round_costs",
              "violations", "graph_iters"):
        np.testing.assert_array_equal(getattr(sb, f), getattr(sa, f),
                                      err_msg=f"sweep field {f}")


class TestNumerics:
    """The reduction/fence helpers the parity contract stands on."""

    def test_fma_fence_is_bitwise_identity(self):
        # every finite *normal* float (and signed zero) comes back
        # bit-identical; subnormals flush to zero under XLA CPU's FTZ
        # environment (documented in the fence's docstring)
        tiny = float(np.finfo(np.float32).tiny)      # smallest normal
        x = np.asarray([0.0, -0.0, 1.0, -1.5, 3.4e37, -3.4e37, tiny,
                        -tiny, 7.25, np.float32(np.pi)], np.float32)
        out = np.asarray(jax.jit(fma_fence)(jnp.asarray(x)))
        assert np.array_equal(out.view(np.uint32), x.view(np.uint32))

    def test_ladder_sum_matches_numpy(self):
        rng = np.random.default_rng(0)
        for n in (1, 2, 3, 7, 16, 22, 100):
            x = rng.normal(0, 1, (4, n)).astype(np.float32)
            got = np.asarray(jax.jit(ladder_sum)(jnp.asarray(x)))
            np.testing.assert_allclose(got, x.astype(np.float64).sum(-1),
                                       rtol=1e-5, atol=1e-6)

    def test_ladder_logsumexp_matches_scipy_semantics(self):
        rng = np.random.default_rng(1)
        x = rng.normal(0, 10, (5, 22)).astype(np.float32)
        x[0, :3] = -1e30                      # masked-entry sentinels
        got = np.asarray(jax.jit(ladder_logsumexp)(jnp.asarray(x)))
        ref64 = np.log(np.exp(x.astype(np.float64)
                              - x.max(-1, keepdims=True)).sum(-1)) \
            + x.max(-1)
        np.testing.assert_allclose(got, ref64, rtol=1e-5, atol=1e-6)

    def test_float32_exp_log_within_a_few_ulp(self):
        """The transcendentals the round uses on a TPU, whose own float32
        exp and log are too coarse: within 2 ulp (exp, on the exponents
        the rounds use) and 4e-7 (log) of float64, special values as
        ``jnp``'s, subnormals flushed, scalars as vectors."""
        x = np.concatenate([np.linspace(-87.3, 80.0, 400_001),
                            -np.logspace(-30, 1.9, 20_000)]).astype(np.float32)
        got = np.asarray(jax.jit(numerics._exp_f32)(x), np.float64)
        want = np.exp(x.astype(np.float64))
        assert np.max(np.abs(got - want) / want) < 2 * 2.0**-24
        v = np.logspace(-37.9, 38.5, 400_001).astype(np.float32)
        got = np.asarray(jax.jit(numerics._log_f32)(v), np.float64)
        want = np.log(v.astype(np.float64))
        assert np.max(np.abs(got - want) / np.maximum(np.abs(want), 1.0)) \
            < 4e-7
        sp = np.asarray([0.0, np.inf, -np.inf, np.nan, 1e-40, 100.0, -100.0],
                        np.float32)
        np.testing.assert_array_equal(np.asarray(numerics._exp_f32(sp)),
                                      [1, np.inf, 0, np.nan, 1, np.inf, 0])
        sp = np.asarray([0.0, np.inf, np.nan, -1.0, 1.0, 1e-40], np.float32)
        np.testing.assert_array_equal(np.asarray(numerics._log_f32(sp)),
                                      [-np.inf, np.inf, np.nan, np.nan, 0,
                                       -np.inf])
        for f, a in ((numerics.exp, -1.5), (numerics.log, 3.0)):
            s = np.asarray(jax.jit(f)(jnp.float32(a)))
            assert s.shape == () and s == np.asarray(
                jax.jit(f)(jnp.full((1,), a, jnp.float32)))[0]

    def test_ladder_matvec_matches_numpy(self):
        rng = np.random.default_rng(2)
        v = rng.normal(0, 1, 22).astype(np.float32)
        m = rng.normal(0, 1, (22, 7)).astype(np.float32)
        got = np.asarray(jax.jit(ladder_matvec)(jnp.asarray(v),
                                                jnp.asarray(m)))
        np.testing.assert_allclose(
            got, v.astype(np.float64) @ m.astype(np.float64),
            rtol=1e-5, atol=1e-6)
