"""Scan engine vs reference loop: trajectories must match, sweeps must be
deterministic, and the shared round body must match independent oracles.

Two layers of defense:

* The engine runs the same round body as the reference loop (built by
  ``make_round_body``), so equivalence between the two execution paths is
  expected to be *bit-exact* for the selection masks and within float
  tolerance for every curve — across algos, seeds, and both client-count
  modes (fixed N_t and the paper's uplink bandwidth formula).
* Because that shared body makes the two paths equivalent by
  construction, the body's client-side *semantics* are additionally
  pinned against independent host-side float64 NumPy implementations
  (the pre-engine ``_client_losses`` / ``_clients_for_round`` logic,
  resurrected here as test oracles)."""

from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.federated import (SimConfig, engine, run_batch,
                             run_simulation_reference, run_simulation_scan,
                             run_sweep)
from repro.federated.simulation import (client_window_losses,
                                        fedboost_window_grad,
                                        n_clients_traceable)


def _stream(K=8, n_stream=400, seed=0):
    rng = np.random.default_rng(seed)
    preds = rng.normal(0, 1, (K, n_stream)).astype(np.float32)
    y = rng.normal(0, 1, n_stream).astype(np.float32)
    costs = rng.uniform(0.1, 1.0, K).astype(np.float32)
    return preds, y, costs


@pytest.mark.parametrize("algo", ["eflfg", "fedboost"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_scan_matches_reference(algo, seed):
    preds, y, costs = _stream()
    cfg = SimConfig(budget=2.0, seed=seed)
    T = 150
    ref = run_simulation_reference(algo, preds, y, costs, T=T, cfg=cfg)
    eng = run_simulation_scan(algo, preds, y, costs, T=T, cfg=cfg)
    np.testing.assert_array_equal(ref.sel_masks, eng.sel_masks)
    np.testing.assert_array_equal(ref.sel_sizes, eng.sel_sizes)
    np.testing.assert_array_equal(ref.dom_sizes, eng.dom_sizes)
    np.testing.assert_allclose(ref.mse_curve, eng.mse_curve, atol=1e-5)
    np.testing.assert_allclose(ref.regret.regret_curve(),
                               eng.regret.regret_curve(), atol=1e-5)
    np.testing.assert_allclose(ref.round_costs, eng.round_costs, atol=1e-5)
    assert ref.budget_violations == eng.budget_violations
    assert ref.regret.best_model() == eng.regret.best_model()


@pytest.mark.parametrize("algo", ["eflfg", "fedboost"])
def test_scan_matches_reference_bandwidth_mode(algo):
    """The uplink formula N_t = floor(b / (b_loss (|S_t|+1))) makes the
    client count data dependent — the fixed-window masking must still
    reproduce the reference exactly."""
    preds, y, costs = _stream(seed=3)
    cfg = SimConfig(budget=2.0, uplink_bandwidth=12.0, loss_bandwidth=1.0,
                    n_clients=20, seed=0)
    T = 120
    ref = run_simulation_reference(algo, preds, y, costs, T=T, cfg=cfg)
    eng = run_simulation_scan(algo, preds, y, costs, T=T, cfg=cfg)
    np.testing.assert_array_equal(ref.sel_masks, eng.sel_masks)
    np.testing.assert_allclose(ref.mse_curve, eng.mse_curve, atol=1e-5)
    np.testing.assert_allclose(ref.regret.regret_curve(),
                               eng.regret.regret_curve(), atol=1e-5)
    assert ref.budget_violations == eng.budget_violations


def test_scan_matches_reference_on_expert_pool(small_pool):
    """End to end on real (kernel + MLP) experts, not synthetic streams."""
    from repro.experts import pool_predict_all
    pool, xs, ys = small_pool
    preds = pool_predict_all(pool, xs)
    cfg = SimConfig(budget=2.0, seed=0)
    ref = run_simulation_reference("eflfg", preds, ys, pool.costs, T=100,
                                   cfg=cfg)
    eng = run_simulation_scan("eflfg", preds, ys, pool.costs, T=100, cfg=cfg)
    np.testing.assert_array_equal(ref.sel_masks, eng.sel_masks)
    np.testing.assert_allclose(ref.mse_curve, eng.mse_curve, atol=1e-5)


def _client_losses_np(preds, y, cursor, n_t, mix, loss_scale):
    """Independent float64 host oracle: the pre-engine client evaluation
    (dynamic-size slice, no fixed window/masking)."""
    n_stream = preds.shape[1]
    idx = np.arange(cursor, cursor + n_t) % n_stream
    p_cl = preds[:, idx].astype(np.float64)
    y_cl = y[idx].astype(np.float64)
    sq = (p_cl - y_cl[None, :]) ** 2
    model_losses = np.minimum(sq / loss_scale, 1.0).sum(1)
    yhat = mix.astype(np.float64) @ p_cl
    ens_sq = (yhat - y_cl) ** 2
    return (ens_sq.mean(), np.minimum(ens_sq / loss_scale, 1.0).sum(),
            model_losses)


def test_window_losses_match_host_oracle():
    """The fixed-window masked evaluation must agree with the dynamic
    float64 NumPy implementation for every n_t <= window."""
    rng = np.random.default_rng(7)
    K, n_stream, window, loss_scale = 7, 53, 12, 4.0
    preds = rng.normal(0, 1, (K, n_stream)).astype(np.float32)
    y = rng.normal(0, 1, n_stream).astype(np.float32)
    for trial in range(30):
        cursor = int(rng.integers(0, n_stream))
        n_t = int(rng.integers(1, window + 1))
        mix = rng.dirichlet(np.ones(K)).astype(np.float32)
        ens_sq, ens_norm, ml = client_window_losses(
            jnp.asarray(preds), jnp.asarray(y), jnp.int32(cursor),
            jnp.int32(n_t), jnp.asarray(mix), loss_scale, window)
        o_sq, o_norm, o_ml = _client_losses_np(preds, y, cursor, n_t, mix,
                                               loss_scale)
        np.testing.assert_allclose(float(ens_sq), o_sq, rtol=1e-5)
        np.testing.assert_allclose(float(ens_norm), o_norm, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(ml), o_ml, rtol=1e-5,
                                   atol=1e-6)


def test_fedboost_grad_matches_host_oracle():
    """g_k = 2/n sum_i (yhat - y) f_k(x_i) over the round's n_t samples."""
    rng = np.random.default_rng(8)
    K, n_stream, window = 5, 40, 9
    preds = rng.normal(0, 1, (K, n_stream)).astype(np.float32)
    y = rng.normal(0, 1, n_stream).astype(np.float32)
    for trial in range(20):
        cursor = int(rng.integers(0, n_stream))
        n_t = int(rng.integers(1, window + 1))
        mix = rng.dirichlet(np.ones(K)).astype(np.float32)
        g = fedboost_window_grad(jnp.asarray(preds), jnp.asarray(y),
                                 jnp.int32(cursor), jnp.int32(n_t),
                                 jnp.asarray(mix), window)
        idx = np.arange(cursor, cursor + n_t) % n_stream
        p_cl = preds[:, idx].astype(np.float64)
        y_cl = y[idx].astype(np.float64)
        resid = mix.astype(np.float64) @ p_cl - y_cl
        oracle = (2.0 / n_t) * (p_cl @ resid)
        np.testing.assert_allclose(np.asarray(g), oracle, rtol=1e-4,
                                   atol=1e-6)


def test_bandwidth_formula_matches_host_oracle():
    """N_t = clip(floor(b / (b_loss (|S|+1))), 1, n_clients), against the
    pre-engine integer host computation (allowing the one-ulp float32
    boundary where floor(x) legitimately differs from float64)."""
    rng = np.random.default_rng(9)
    hits = 0
    for trial in range(500):
        b = float(rng.uniform(0.5, 60.0))
        bl = float(rng.uniform(0.2, 3.0))
        sel = int(rng.integers(0, 15))
        cfg = SimConfig(uplink_bandwidth=b, loss_bandwidth=bl, n_clients=30)
        n = int(n_clients_traceable(cfg, jnp.int32(sel)))
        oracle = max(1, min(int(b // (bl * (sel + 1))), cfg.n_clients))
        assert abs(n - oracle) <= 1, (b, bl, sel, n, oracle)
        hits += n == oracle
    assert hits >= 490   # exact agreement away from float boundaries


def test_sweep_shapes_and_determinism():
    preds, y, costs = _stream()
    cfg = SimConfig(budget=2.0)
    T, seeds = 80, [0, 1, 2, 3]
    a = run_sweep("eflfg", preds, y, costs, T=T, cfg=cfg, seeds=seeds)
    b = run_sweep("eflfg", preds, y, costs, T=T, cfg=cfg, seeds=seeds)
    assert a.mse_curves.shape == (4, T)
    assert a.regret_curves.shape == (4, T)
    assert a.sel_sizes.shape == (4, T)
    assert a.violations.shape == (4,)
    assert np.isfinite(a.mse_curves).all()
    # one compiled program, fixed seeds => bitwise reproducible
    np.testing.assert_array_equal(a.mse_curves, b.mse_curves)
    np.testing.assert_array_equal(a.regret_curves, b.regret_curves)
    np.testing.assert_array_equal(a.sel_sizes, b.sel_sizes)
    # distinct seeds actually produce distinct trajectories
    assert not np.array_equal(a.sel_sizes[0], a.sel_sizes[1])


def test_sweep_budget_grid():
    preds, y, costs = _stream()
    cfg = SimConfig()
    sw = run_sweep("eflfg", preds, y, costs, T=60, cfg=cfg, seeds=[0, 1],
                   budgets=[1.0, 2.0, 4.0])
    assert sw.mse_curves.shape == (3, 2, 60)
    assert sw.violations.shape == (3, 2)
    # EFL-FG holds the hard per-round budget at every grid point
    assert (sw.round_costs <= np.array([1.0, 2.0, 4.0])[:, None, None]
            + 1e-5).all()
    # larger budgets admit (weakly) larger transmit sets on average
    mean_sel = sw.sel_sizes.mean(axis=(1, 2))
    assert mean_sel[0] <= mean_sel[-1] + 1e-9


# ---------------------------------------------------------------------------
# Transmit sets cross the device boundary as packed uint32 words
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("K", [1, 22, 32, 40])
def test_sel_pack_round_trip(K):
    rng = np.random.default_rng(K)
    masks = rng.random((3, 5, K)) < 0.5          # (lanes, rounds, K)
    masks[0, 0], masks[0, 1] = True, False
    words = np.asarray(jax.vmap(jax.vmap(engine._pack_sel))(
        jnp.asarray(masks)))
    n_words = -(-K // 32)
    assert words.dtype == np.uint32
    assert words.shape == (3, 5) + (() if n_words == 1 else (n_words,))
    back = engine._unpack_sel(words, K)
    assert back.dtype == np.bool_
    np.testing.assert_array_equal(back, masks)
    # bit k % 32 of word k // 32 holds sel[k]: K = 32 sets bit 31, K = 40
    # sets bit 7 of the second word
    last = np.zeros(K, bool)
    last[-1] = True
    want = np.zeros(n_words, np.uint32)
    want[(K - 1) // 32] = np.uint32(1) << np.uint32((K - 1) % 32)
    np.testing.assert_array_equal(
        np.asarray(engine._pack_sel(jnp.asarray(last))).reshape(-1), want)


@pytest.mark.parametrize("K", [8, 40])
def test_sweep_result_gets_bool_masks(monkeypatch, K):
    """The outputs ``run_sweep`` hands to ``SweepResult`` hold the bool
    transmit sets (budgets, seeds, T, K), each lane's equal to its batched
    lane and its solo run (FedBoost's draws do not depend on its
    weights, so batched and solo selections agree)."""
    seen = {}

    class Keep(engine.SweepResult):
        def __init__(self, outs, *args, **kw):
            super().__init__(outs, *args, **kw)
            seen["sel"] = outs["sel"]

    monkeypatch.setattr(engine, "SweepResult", Keep)
    preds, y, costs = _stream(K=K)
    cfg = SimConfig()
    T, seeds, budgets = 40, [0, 1], [1.0, 2.0]
    sw = run_sweep("fedboost", preds, y, costs, T=T, cfg=cfg, seeds=seeds,
                   budgets=budgets)
    sel = seen["sel"]
    assert sel.dtype == np.bool_ and sel.shape == (2, 2, T, K)
    np.testing.assert_array_equal(sel.sum(-1), sw.sel_sizes)
    lanes = run_batch("fedboost", preds, y, costs, T, cfg,
                      seeds=seeds * 2, budgets=np.repeat(budgets, 2))
    for i, b in enumerate(budgets):
        for j, s in enumerate(seeds):
            solo = run_simulation_scan("fedboost", preds, y, costs, T,
                                       replace(cfg, seed=s, budget=b))
            np.testing.assert_array_equal(sel[i, j],
                                          lanes[2 * i + j].sel_masks)
            np.testing.assert_array_equal(sel[i, j], solo.sel_masks)


def test_batch_and_solo_masks_are_bool():
    preds, y, costs = _stream()
    cfg = SimConfig(budget=2.0)
    T, budgets = 30, [1.0, 1.0, 2.0, 2.0]
    assert engine.batch_buckets("eflfg", budgets) is not None
    solo = run_simulation_scan("eflfg", preds, y, costs, T, cfg)
    batch = run_batch("eflfg", preds, y, costs, T, cfg, seeds=[0, 1, 2, 3],
                      budgets=budgets)
    for r in [solo] + batch:
        assert r.sel_masks.dtype == np.bool_ and r.sel_masks.shape == (T, 8)
        np.testing.assert_array_equal(r.sel_masks.sum(1), r.sel_sizes)


@pytest.mark.parametrize("algo", ["eflfg", "fedboost"])
def test_scan_matches_reference_two_word_masks(algo):
    """K = 40 packs each round's transmit set into two words."""
    preds, y, costs = _stream(K=40, seed=5)
    cfg = SimConfig(budget=4.0, seed=1)
    T = 60
    ref = run_simulation_reference(algo, preds, y, costs, T=T, cfg=cfg)
    eng = run_simulation_scan(algo, preds, y, costs, T=T, cfg=cfg)
    np.testing.assert_array_equal(ref.sel_masks, eng.sel_masks)
    assert ref.sel_masks.any(axis=0)[32:].any()   # the second word is used
