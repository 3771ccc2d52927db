"""Fused client-eval kernel: interpret-mode parity vs the jnp oracle, the
unfused round-body ops, and independent float64 NumPy implementations —
plus fused-vs-unfused engine trajectory equivalence.

Shape coverage deliberately includes the odd corners: windows that are
not sublane multiples (W=13, W=1), a single-expert pool (K=1), a
wrapping cursor, and the degenerate empty round (n_t=0, where both paths
produce NaN means/gradients and zero accumulators).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.client_eval import ops as ce_ops, ref as ce_ref
from repro.core.policy import ensemble_mix_weights
from repro.federated import SimConfig, run_simulation_scan, run_sweep
from repro.federated.simulation import (client_window_losses,
                                        fedboost_window_grad)


def _case(K, n_stream, W, seed=0):
    rng = np.random.default_rng(seed)
    preds = rng.normal(0, 1, (K, n_stream)).astype(np.float32)
    y = rng.normal(0, 1, n_stream).astype(np.float32)
    pe, ye = ce_ops.extend_stream(jnp.asarray(preds), jnp.asarray(y), W)
    return preds, y, pe, ye, rng


def _mixture(kind, rng, K):
    """A round's mixture as the round bodies hand it to the kernel:
    ``log``, EFL-FG's eq.-(5) softmax of log-weights over the transmit
    set; ``linear``, FedBoost's alpha renormalized over it; ``none``, a
    distribution as given."""
    sel = jnp.asarray(rng.integers(0, 2, K).astype(bool)).at[0].set(True)
    if kind == "log":
        log_w = jnp.asarray(rng.normal(0, 1, K).astype(np.float32))
        return ensemble_mix_weights(log_w, sel)
    alpha = jnp.asarray(rng.dirichlet(np.ones(K)).astype(np.float32))
    if kind == "linear":
        masked = jnp.where(sel, alpha, 0.0)
        return masked / jnp.sum(masked)
    return alpha


# --- kernel vs jnp oracle -----------------------------------------------------

@pytest.mark.parametrize("K,n_stream,W", [
    (22, 600, 100),   # paper shape
    (22, 600, 13),    # W not a sublane multiple
    (1, 40, 5),       # single expert
    (5, 30, 1),       # single-client window
    (7, 53, 53),      # window == stream length
])
@pytest.mark.parametrize("mixture", ["log", "linear", "none"])
def test_kernel_matches_ref(K, n_stream, W, mixture):
    preds, y, pe, ye, rng = _case(K, n_stream, W, seed=K * W)
    for trial in range(6):
        cursor = jnp.int32(rng.integers(0, n_stream))
        n_t = jnp.int32(rng.integers(1, W + 1))
        mix = _mixture(mixture, rng, K)
        out = ce_ops.client_eval(pe, ye, cursor, n_t, mix,
                                 loss_scale=4.0, window=W)
        ref = ce_ref.client_eval_ref(pe, ye, cursor, n_t, mix, 4.0, W)
        for got, want, name in zip(out, ref, out._fields):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-6, atol=1e-7, err_msg=name)


def test_kernel_empty_round_matches_ref():
    """n_t = 0: masked accumulators are exactly zero; the 0/0 mean and the
    inf*0 gradient are NaN in both the kernel and the oracle."""
    preds, y, pe, ye, rng = _case(6, 50, 8, seed=3)
    mix = _mixture("log", rng, 6)
    out = ce_ops.client_eval(pe, ye, jnp.int32(49), jnp.int32(0), mix,
                             loss_scale=4.0, window=8)
    ref = ce_ref.client_eval_ref(pe, ye, jnp.int32(49), jnp.int32(0), mix,
                                 4.0, 8)
    assert np.isnan(float(out.ens_sq_mean)) and np.isnan(
        float(ref.ens_sq_mean))
    assert float(out.ens_norm) == float(ref.ens_norm) == 0.0
    np.testing.assert_array_equal(np.asarray(out.model_losses),
                                  np.zeros(6, np.float32))
    assert np.isnan(np.asarray(out.grad)).all()


# --- kernel vs the unfused round-body ops ------------------------------------

@pytest.mark.parametrize("K,n_stream,W", [(22, 600, 100), (3, 29, 7)])
def test_kernel_matches_unfused_ops(K, n_stream, W):
    """Same numbers as `client_window_losses` + `fedboost_window_grad`,
    the two ops the kernel fuses, under EFL-FG's eq.-(5) mixture."""
    preds, y, pe, ye, rng = _case(K, n_stream, W, seed=11)
    pj, yj = jnp.asarray(preds), jnp.asarray(y)
    for trial in range(8):
        cursor = jnp.int32(rng.integers(0, n_stream))
        n_t = jnp.int32(rng.integers(1, W + 1))
        log_w = jnp.asarray(rng.normal(0, 1, K).astype(np.float32))
        sel = jnp.asarray(rng.integers(0, 2, K).astype(bool)).at[0].set(True)
        mix = ensemble_mix_weights(log_w, sel)
        out = ce_ops.client_eval(pe, ye, cursor, n_t, mix, loss_scale=4.0,
                                 window=W)
        es, en, ml = client_window_losses(pj, yj, cursor, n_t, mix, 4.0, W)
        g = fedboost_window_grad(pj, yj, cursor, n_t, mix, W)
        np.testing.assert_allclose(float(out.ens_sq_mean), float(es),
                                   rtol=1e-5)
        np.testing.assert_allclose(float(out.ens_norm), float(en), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(out.model_losses),
                                   np.asarray(ml), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(out.grad), np.asarray(g),
                                   rtol=1e-5, atol=1e-6)


def test_kernel_matches_float64_numpy_oracle():
    """Independent host-side float64 implementation (the pre-engine client
    evaluation), no jnp in the oracle path; the kernel takes the round
    body's eq.-(5) mixture."""
    K, n_stream, W, loss_scale = 9, 71, 12, 4.0
    preds, y, pe, ye, rng = _case(K, n_stream, W, seed=21)
    for trial in range(20):
        cursor = int(rng.integers(0, n_stream))
        n_t = int(rng.integers(1, W + 1))
        log_w = rng.normal(0, 1, K).astype(np.float32)
        sel = rng.integers(0, 2, K).astype(bool)
        sel[int(rng.integers(0, K))] = True
        got_mix = ensemble_mix_weights(jnp.asarray(log_w), jnp.asarray(sel))
        out = ce_ops.client_eval(pe, ye, jnp.int32(cursor), jnp.int32(n_t),
                                 got_mix, loss_scale=loss_scale, window=W)
        lw = np.where(sel, log_w.astype(np.float64), -np.inf)
        mix = np.exp(lw - (np.log(np.sum(np.exp(lw - lw.max()))) + lw.max()))
        idx = np.arange(cursor, cursor + n_t) % n_stream
        p_cl = preds[:, idx].astype(np.float64)
        y_cl = y[idx].astype(np.float64)
        sq = (p_cl - y_cl[None, :]) ** 2
        ml = np.minimum(sq / loss_scale, 1.0).sum(1)
        yhat = mix @ p_cl
        ens_sq = (yhat - y_cl) ** 2
        grad = (2.0 / n_t) * (p_cl @ (yhat - y_cl))
        np.testing.assert_allclose(np.asarray(got_mix), mix, rtol=1e-5,
                                   atol=1e-7)
        np.testing.assert_allclose(float(out.ens_sq_mean), ens_sq.mean(),
                                   rtol=1e-4)
        np.testing.assert_allclose(
            float(out.ens_norm), np.minimum(ens_sq / loss_scale, 1.0).sum(),
            rtol=1e-4)
        np.testing.assert_allclose(np.asarray(out.model_losses), ml,
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(out.grad), grad, rtol=1e-4,
                                   atol=1e-5)


# --- engine integration -------------------------------------------------------

@pytest.mark.parametrize("algo", ["eflfg", "fedboost"])
@pytest.mark.parametrize("bandwidth", [False, True])
def test_fused_round_body_matches_unfused(algo, bandwidth):
    """The tentpole contract: switching `use_fused` changes the execution
    strategy only.  Selection trajectories are bit-equal and every curve
    matches within float32 tolerance.

    Tolerance note (documented contract): on CPU the fused kernel's
    interpret mode traces to the same XLA ops as the unfused body, and
    curves are empirically bit-equal — except FedBoost in bandwidth mode,
    where the *unfused* path computes the mixture matvec twice (once in
    ``client_window_losses``, once in ``fedboost_window_grad``) and XLA's
    separate fusion clusters round the duplicate differently; the fused
    kernel computes it once.  The resulting 1-ulp gradient difference
    transiently amplifies through FedBoost's alpha feedback (~0.5%
    relative, reconverging as the running means accumulate), while the
    loss-blind subset sampling keeps selection masks bit-equal."""
    if bandwidth:
        cfg_kw = dict(budget=2.0, uplink_bandwidth=12.0, loss_bandwidth=1.0,
                      n_clients=20, seed=1)
    else:
        cfg_kw = dict(budget=2.0, seed=0)
    chaotic = bandwidth and algo == "fedboost"
    tol = dict(rtol=2e-2, atol=1e-3) if chaotic else dict(rtol=0, atol=1e-5)
    rng = np.random.default_rng(5)
    K, n_stream, T = 8, 400, 150
    preds = rng.normal(0, 1, (K, n_stream)).astype(np.float32)
    y = rng.normal(0, 1, n_stream).astype(np.float32)
    costs = rng.uniform(0.1, 1.0, K).astype(np.float32)
    fused = run_simulation_scan(algo, preds, y, costs, T=T,
                                cfg=SimConfig(use_fused=True, **cfg_kw))
    unfused = run_simulation_scan(algo, preds, y, costs, T=T,
                                  cfg=SimConfig(use_fused=False, **cfg_kw))
    np.testing.assert_array_equal(fused.sel_masks, unfused.sel_masks)
    np.testing.assert_array_equal(fused.dom_sizes, unfused.dom_sizes)
    np.testing.assert_allclose(fused.mse_curve, unfused.mse_curve, **tol)
    np.testing.assert_allclose(fused.regret.regret_curve(),
                               unfused.regret.regret_curve(),
                               rtol=tol["rtol"], atol=0.5 if chaotic
                               else 1e-5)
    np.testing.assert_allclose(fused.round_costs, unfused.round_costs,
                               atol=1e-5)
    assert fused.budget_violations == unfused.budget_violations


def test_fused_sweep_single_dispatch_parity():
    """run_sweep vmaps the fused kernel (one batched-grid launch per
    round); results must match the unfused sweep and stay deterministic."""
    rng = np.random.default_rng(6)
    preds = rng.normal(0, 1, (6, 300)).astype(np.float32)
    y = rng.normal(0, 1, 300).astype(np.float32)
    costs = rng.uniform(0.1, 1.0, 6).astype(np.float32)
    T, seeds = 80, [0, 1, 2]
    a = run_sweep("eflfg", preds, y, costs, T=T,
                  cfg=SimConfig(budget=2.0, use_fused=True), seeds=seeds)
    b = run_sweep("eflfg", preds, y, costs, T=T,
                  cfg=SimConfig(budget=2.0, use_fused=False), seeds=seeds)
    c = run_sweep("eflfg", preds, y, costs, T=T,
                  cfg=SimConfig(budget=2.0, use_fused=True), seeds=seeds)
    np.testing.assert_array_equal(a.sel_sizes, b.sel_sizes)
    np.testing.assert_allclose(a.mse_curves, b.mse_curves, atol=1e-5)
    np.testing.assert_allclose(a.regret_curves, b.regret_curves, atol=1e-5)
    np.testing.assert_array_equal(a.mse_curves, c.mse_curves)  # determinism


def test_short_stream_falls_back_to_unfused():
    """W > n_stream (multi-wrap window) can't use the extension trick; the
    round body silently falls back and still matches use_fused=False."""
    rng = np.random.default_rng(7)
    preds = rng.normal(0, 1, (4, 3)).astype(np.float32)   # stream of 3
    y = rng.normal(0, 1, 3).astype(np.float32)
    costs = rng.uniform(0.1, 1.0, 4).astype(np.float32)
    cfg_f = SimConfig(clients_per_round=5, budget=2.0, use_fused=True)
    cfg_u = SimConfig(clients_per_round=5, budget=2.0, use_fused=False)
    a = run_simulation_scan("eflfg", preds, y, costs, T=40, cfg=cfg_f)
    b = run_simulation_scan("eflfg", preds, y, costs, T=40, cfg=cfg_u)
    np.testing.assert_array_equal(a.sel_masks, b.sel_masks)
    np.testing.assert_allclose(a.mse_curve, b.mse_curve, atol=1e-6)


def test_extend_stream_rejects_long_window():
    with pytest.raises(ValueError):
        ce_ops.extend_stream(jnp.zeros((2, 4)), jnp.zeros(4), 5)


# --- property test (hypothesis, optional dependency) -------------------------

def test_client_eval_properties_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=15, deadline=None, database=None,
              derandomize=True)
    @given(st.integers(0, 10_000))
    def check(seed):
        rng = np.random.default_rng(seed)
        K = int(rng.integers(1, 12))
        n_stream = int(rng.integers(8, 80))
        W = int(rng.integers(1, n_stream + 1))
        preds = rng.normal(0, 1, (K, n_stream)).astype(np.float32)
        y = rng.normal(0, 1, n_stream).astype(np.float32)
        pe, ye = ce_ops.extend_stream(jnp.asarray(preds), jnp.asarray(y), W)
        cursor = jnp.int32(rng.integers(0, n_stream))
        n_t = int(rng.integers(1, W + 1))
        log_w = jnp.asarray(rng.normal(0, 1, K).astype(np.float32))
        sel = jnp.asarray(rng.integers(0, 2, K).astype(bool))
        sel = sel.at[int(rng.integers(0, K))].set(True)
        mix = ensemble_mix_weights(log_w, sel)
        out = ce_ops.client_eval(pe, ye, cursor, jnp.int32(n_t), mix,
                                 loss_scale=4.0, window=W)
        mix = np.asarray(mix)
        # eq.-(5) mixture: a distribution supported on the selected set
        assert np.all(mix >= -1e-7)
        np.testing.assert_allclose(mix.sum(), 1.0, atol=1e-5)
        assert np.all(mix[~np.asarray(sel)] == 0.0)
        # normalized accumulators are bounded by the client count
        ml = np.asarray(out.model_losses)
        assert np.all(ml >= 0.0) and np.all(ml <= n_t + 1e-5)
        assert 0.0 <= float(out.ens_norm) <= n_t + 1e-5
        assert float(out.ens_sq_mean) >= 0.0

    check()
