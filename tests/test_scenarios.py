"""The repro.scenarios subsystem: pinned schedules, the determinism
contract, and the schedule-threaded program family.

Three layers of defense, mirroring the engine's own test discipline:

* **Pinned schedules.**  Every registered preset's compiled arrays are a
  deterministic host-side computation — each one is pinned exactly
  (values, not just shapes), so a preset cannot silently change meaning.
* **Golden equivalences.**  The all-neutral ``constant`` scenario must
  be *bit-equal* to the scenario-free engine/sweep/served-exact paths on
  the paper configuration (it dispatches the identical program — by
  construction, not by hoping XLA fuses two programs the same way).
* **Oracle for the scheduled family.**  The scheduled scan engine is
  pinned bit-equal to the scheduled *reference loop* (same round body,
  per-round dispatch) across scenarios and algos, the masked/shifted
  window evaluation against independent float64 NumPy, and the fused
  (Pallas) scheduled path against the unfused one.

The whole file also runs under CI's pallas-interpret job (the fused
scheduled kernel) and the forced-8-host-device job (the mesh-sharded
scheduled sweep, gated on ``jax.device_count() > 1``).
"""

import glob
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import scenarios
from repro.federated import (SimConfig, run_batch, run_simulation_reference,
                             run_simulation_scan, run_sweep)
from repro.federated.simulation import (client_window_losses, eval_window,
                                        fedboost_window_grad)

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _stream(K=8, n_stream=400, seed=0):
    rng = np.random.default_rng(seed)
    preds = rng.normal(0, 1, (K, n_stream)).astype(np.float32)
    y = rng.normal(0, 1, n_stream).astype(np.float32)
    costs = rng.uniform(0.1, 1.0, K).astype(np.float32)
    return preds, y, costs


# ---------------------------------------------------------------------------
# Registry + pinned compiled schedules (one regression pin per preset)
# ---------------------------------------------------------------------------

def test_registry_presets():
    names = scenarios.names()
    assert len(names) >= 6
    for name in names:
        s = scenarios.get(name)
        assert s.name == name and s.description
        assert scenarios.resolve(name) is s
        assert scenarios.resolve(s) is s
    with pytest.raises(ValueError, match="unknown scenario"):
        scenarios.get("ghost")
    with pytest.raises(TypeError):
        scenarios.resolve(42)
    with pytest.raises(ValueError, match="already registered"):
        scenarios.register(scenarios.get("constant"))


def test_constant_pinned():
    comp = scenarios.get("constant").compile(40, SimConfig())
    assert comp.neutral and comp.T == 40 and comp.window == 5
    assert np.asarray(comp.arrays.budget_scale).shape == (40,)
    assert np.asarray(comp.arrays.active).shape == (40, 5)
    np.testing.assert_array_equal(np.asarray(comp.arrays.budget_scale), 1.0)
    np.testing.assert_array_equal(np.asarray(comp.arrays.active), True)
    np.testing.assert_array_equal(np.asarray(comp.arrays.label_shift), 0.0)


def test_step_decay_pinned():
    scale = scenarios.get("step_decay").budget.scale(12)
    np.testing.assert_array_equal(
        scale, np.float32([1, 1, 1, 1, .5, .5, .5, .5, .25, .25, .25, .25]))


def test_bursty_outage_pinned():
    scen = scenarios.get("bursty_outage")
    scale = scen.budget.scale(600)
    t = np.arange(600)
    in_outage = (t >= 200) & (t % 200 < 20)
    np.testing.assert_array_equal(scale[in_outage], np.float32(0.05))
    np.testing.assert_array_equal(scale[~in_outage], np.float32(1.0))
    assert int(in_outage.sum()) == 40
    comp = scen.compile(600, SimConfig())
    assert not comp.neutral


def test_partial_participation_pinned():
    part = scenarios.get("partial_participation").participation
    m = part.mask(300, 20)
    # deterministic: same spec -> identical mask, whatever process
    np.testing.assert_array_equal(m, part.mask(300, 20))
    assert m[:, 0].all()                       # slot 0 never drops
    assert 0.5 < m.mean() < 0.7                # ~ prob=0.6
    assert not m.all()


def test_cohort_dropout_pinned():
    part = scenarios.get("cohort_dropout").participation
    m = part.mask(30, 10)
    np.testing.assert_array_equal(m[:10], True)     # before the segment
    np.testing.assert_array_equal(m[20:], True)     # after it
    np.testing.assert_array_equal(m[10:20, :6], True)
    np.testing.assert_array_equal(m[10:20, 6:], False)  # 40% cohort dark


def test_drift_pinned():
    d = scenarios.get("concept_drift").drift
    s = d.shifts(8)
    np.testing.assert_allclose(
        s, np.float32([0, 0, 1 / 3, 1 / 3, 2 / 3, 2 / 3, 1, 1]), rtol=1e-6)
    cyc = scenarios.get("regime_cycle").drift.shifts(12)
    seg = np.minimum(np.arange(12) * 6 // 12, 5)
    np.testing.assert_allclose(
        cyc, 0.5 * np.sin(2 * np.pi * seg / 6).astype(np.float32),
        rtol=1e-6)


def test_spec_validation():
    from repro.scenarios import BudgetSchedule, Drift, Participation
    with pytest.raises(ValueError, match="kind"):
        BudgetSchedule(kind="linear")
    with pytest.raises(ValueError, match="decay_factor"):
        BudgetSchedule(kind="step_decay", decay_factor=0.0)
    with pytest.raises(ValueError, match="prob"):
        Participation(kind="bernoulli", prob=0.0)
    with pytest.raises(ValueError, match="n_segments"):
        Drift(kind="step", n_segments=1)


def test_compile_validation_and_cache():
    from repro.federated.engine import _compile_scenario
    cfg = SimConfig()
    comp = _compile_scenario("concept_drift", 50, cfg)
    # compile cache: same (scenario, T, W) -> the same device arrays
    assert _compile_scenario("concept_drift", 50, cfg) is comp
    # a compiled scenario used with the wrong shape raises
    with pytest.raises(ValueError, match="compiled for"):
        _compile_scenario(comp, 60, cfg)
    with pytest.raises(ValueError, match="compiled for"):
        _compile_scenario(comp, 50, SimConfig(clients_per_round=7))


# ---------------------------------------------------------------------------
# Masked/shifted window evaluation vs independent float64 NumPy
# ---------------------------------------------------------------------------

def _masked_oracle(preds, y, cursor, n_t, mix, loss_scale, window, active,
                   shift):
    n_stream = preds.shape[1]
    idx = np.arange(cursor, cursor + window) % n_stream
    cmask = (np.arange(window) < n_t) & active
    p_cl = preds[:, idx].astype(np.float64)
    y_cl = y[idx].astype(np.float64) + shift
    sq = (p_cl - y_cl[None, :]) ** 2
    ml = np.where(cmask[None, :], np.minimum(sq / loss_scale, 1.0), 0).sum(1)
    yhat = mix.astype(np.float64) @ p_cl
    ens_sq = np.where(cmask, (yhat - y_cl) ** 2, 0.0)
    n_eff = max(int(cmask.sum()), 1)
    resid = np.where(cmask, yhat - y_cl, 0.0)
    grad = (2.0 / n_eff) * (p_cl @ resid)
    return (ens_sq.sum() / n_eff,
            np.minimum(ens_sq / loss_scale, 1.0).sum(), ml, grad)


def test_masked_window_losses_match_host_oracle():
    rng = np.random.default_rng(11)
    K, n_stream, window, loss_scale = 7, 53, 12, 4.0
    preds = rng.normal(0, 1, (K, n_stream)).astype(np.float32)
    y = rng.normal(0, 1, n_stream).astype(np.float32)
    for trial in range(20):
        cursor = int(rng.integers(0, n_stream))
        n_t = int(rng.integers(1, window + 1))
        mix = rng.dirichlet(np.ones(K)).astype(np.float32)
        active = rng.random(window) < 0.7
        active[0] = True
        shift = float(rng.normal())
        ens_sq, ens_norm, ml = client_window_losses(
            jnp.asarray(preds), jnp.asarray(y), jnp.int32(cursor),
            jnp.int32(n_t), jnp.asarray(mix), loss_scale, window,
            jnp.asarray(active), jnp.float32(shift))
        grad = fedboost_window_grad(
            jnp.asarray(preds), jnp.asarray(y), jnp.int32(cursor),
            jnp.int32(n_t), jnp.asarray(mix), window,
            jnp.asarray(active), jnp.float32(shift))
        o_sq, o_norm, o_ml, o_grad = _masked_oracle(
            preds, y, cursor, n_t, mix, loss_scale, window, active, shift)
        np.testing.assert_allclose(float(ens_sq), o_sq, rtol=1e-4,
                                   atol=1e-6)
        np.testing.assert_allclose(float(ens_norm), o_norm, rtol=1e-4,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(ml), o_ml, rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(grad), o_grad, rtol=1e-3,
                                   atol=1e-5)


def test_fused_kernel_masked_matches_refs():
    """The Pallas kernel's schedule operands vs the jnp oracle and the
    independent float64 NumPy implementation."""
    from repro.kernels.client_eval import ops, ref
    rng = np.random.default_rng(13)
    K, n_stream, W, loss_scale = 6, 47, 9, 4.0
    preds = rng.normal(0, 1, (K, n_stream)).astype(np.float32)
    y = rng.normal(0, 1, n_stream).astype(np.float32)
    pe, ye = ref.extend_stream(jnp.asarray(preds), jnp.asarray(y), W)
    for trial in range(10):
        cursor = int(rng.integers(0, n_stream))
        n_t = int(rng.integers(1, W + 1))
        mix = rng.dirichlet(np.ones(K)).astype(np.float32)
        active = rng.random(W) < 0.7
        active[0] = True
        shift = float(rng.normal())
        ev = ops.client_eval(
            pe, ye, jnp.int32(cursor), jnp.int32(n_t), jnp.asarray(mix),
            loss_scale=loss_scale, window=W, with_grad=True,
            active=jnp.asarray(active), shift=jnp.float32(shift))
        oracle = ref.client_eval_ref(
            pe, ye, jnp.int32(cursor), jnp.int32(n_t), jnp.asarray(mix),
            loss_scale, W, active=jnp.asarray(active),
            shift=jnp.float32(shift))
        np.testing.assert_allclose(float(ev.ens_sq_mean),
                                   float(oracle.ens_sq_mean), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(ev.model_losses),
                                   np.asarray(oracle.model_losses),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(ev.grad),
                                   np.asarray(oracle.grad), rtol=1e-4,
                                   atol=1e-5)
        o_sq, o_norm, o_ml, o_grad = _masked_oracle(
            preds, y, cursor, n_t, mix, loss_scale, W, active, shift)
        np.testing.assert_allclose(float(ev.ens_sq_mean), o_sq, rtol=1e-4,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(ev.model_losses), o_ml,
                                   rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="both"):
        ops.client_eval(pe, ye, jnp.int32(0), jnp.int32(1),
                        jnp.asarray(mix), loss_scale=loss_scale, window=W,
                        active=jnp.asarray(active))


# ---------------------------------------------------------------------------
# The scheduled program family vs its per-round oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scenario", ["bursty_outage",
                                      "partial_participation",
                                      "concept_drift", "degraded_uplink"])
@pytest.mark.parametrize("algo", ["eflfg", "fedboost"])
def test_scheduled_scan_matches_scheduled_reference(scenario, algo):
    """The scheduled scan engine must reproduce the scheduled reference
    loop (same round body, per-round dispatch) bit-for-bit — the PR-1
    oracle discipline, extended to the schedule-threaded family."""
    preds, y, costs = _stream()
    cfg = SimConfig(budget=2.0, seed=1)
    T = 150
    eng = run_simulation_scan(algo, preds, y, costs, T, cfg,
                              scenario=scenario)
    ref = run_simulation_reference(algo, preds, y, costs, T, cfg,
                                   scenario=scenario)
    np.testing.assert_array_equal(ref.sel_masks, eng.sel_masks)
    np.testing.assert_array_equal(ref.sel_sizes, eng.sel_sizes)
    np.testing.assert_allclose(ref.mse_curve, eng.mse_curve, atol=1e-5)
    np.testing.assert_allclose(ref.round_costs, eng.round_costs, atol=1e-5)
    np.testing.assert_allclose(ref.regret.regret_curve(),
                               eng.regret.regret_curve(), atol=1e-5)
    assert ref.budget_violations == eng.budget_violations


def test_neutral_scheduled_program_close_to_plain():
    """Forcing the SCHEDULED program onto all-neutral arrays must stay
    float32-close to the scenario-free program (they are different XLA
    programs, so bit-equality is not expected — the same fusion-context
    effect as batched-vs-solo, docs/serving.md#determinism) and
    bit-equal to the scheduled reference loop (its own family oracle)."""
    preds, y, costs = _stream()
    cfg = SimConfig(budget=2.0)
    T = 150
    forced = scenarios.get("constant").compile(T, cfg)._replace(
        neutral=False)
    plain = run_simulation_scan("eflfg", preds, y, costs, T, cfg)
    sched = run_simulation_scan("eflfg", preds, y, costs, T, cfg,
                                scenario=forced)
    ref = run_simulation_reference("eflfg", preds, y, costs, T, cfg,
                                   scenario=forced)
    np.testing.assert_allclose(sched.mse_curve, plain.mse_curve, atol=1e-4)
    np.testing.assert_array_equal(sched.sel_masks, ref.sel_masks)
    assert sched.budget_violations == ref.budget_violations


@pytest.mark.parametrize("algo", ["eflfg", "fedboost"])
def test_fused_unfused_scheduled_parity(algo):
    """Fused (Pallas) vs unfused scheduled round bodies: bit-equal
    selection trajectories, float32-tolerance curves — the PR-2 parity
    contract, extended to the schedule operands."""
    preds, y, costs = _stream(seed=2)
    T = 150
    fused = run_simulation_scan(
        algo, preds, y, costs, T, SimConfig(budget=2.0, use_fused=True),
        scenario="degraded_uplink")
    unfused = run_simulation_scan(
        algo, preds, y, costs, T, SimConfig(budget=2.0, use_fused=False),
        scenario="degraded_uplink")
    np.testing.assert_array_equal(fused.sel_masks, unfused.sel_masks)
    np.testing.assert_allclose(fused.mse_curve, unfused.mse_curve,
                               atol=1e-5)


def test_outage_records_budget_violations():
    """The bursty-outage scenario's collapsed budget forces violations —
    and ONLY outage rounds can violate for EFL-FG (the graph respects
    every non-outage budget)."""
    preds, y, costs = _stream()
    cfg = SimConfig(budget=2.0)
    T = 600
    res = run_simulation_scan("eflfg", preds, y, costs, T, cfg,
                              scenario="bursty_outage")
    comp = scenarios.get("bursty_outage").compile(T, cfg)
    realized = cfg.budget * comp.scale
    viol_rounds = np.where(res.round_costs > realized + 1e-6)[0]
    assert res.budget_violations == len(viol_rounds) > 0
    t = viol_rounds
    assert np.all((t >= 200) & (t % 200 < 20)), "non-outage round violated"
    # stationary violations stay zero: the graph held the full budget
    plain = run_simulation_scan("eflfg", preds, y, costs, T, cfg)
    assert plain.budget_violations == 0


def test_drift_and_participation_change_trajectories():
    preds, y, costs = _stream()
    cfg = SimConfig(budget=2.0)
    T = 200
    plain = run_simulation_scan("eflfg", preds, y, costs, T, cfg)
    drift = run_simulation_scan("eflfg", preds, y, costs, T, cfg,
                                scenario="concept_drift")
    part = run_simulation_scan("eflfg", preds, y, costs, T, cfg,
                               scenario="partial_participation")
    assert drift.final_mse > plain.final_mse      # stale experts hurt
    assert not np.array_equal(part.mse_curve, plain.mse_curve)
    # rerun determinism: same scenario, same bits
    again = run_simulation_scan("eflfg", preds, y, costs, T, cfg,
                                scenario="concept_drift")
    assert again.identical_to(drift)


# ---------------------------------------------------------------------------
# Golden equivalences on the paper configuration
# ---------------------------------------------------------------------------

def test_constant_bit_equal_paper_config():
    """Acceptance pin: ``scenarios.get("constant")`` is bit-equal to the
    scenario-free ``run_simulation_scan`` / ``run_sweep`` / served-exact
    paths on the paper configuration (T=2000, K=22, 100 clients)."""
    from repro.serve import SimServer, SimClient
    preds, y, costs = _stream(K=22, n_stream=6000, seed=1)
    T = 2000
    cfg = SimConfig(n_clients=100, budget=3.0)
    plain = run_simulation_scan("eflfg", preds, y, costs, T, cfg)
    const = run_simulation_scan("eflfg", preds, y, costs, T, cfg,
                                scenario="constant")
    fields = const.identical_fields(plain)
    assert all(fields.values()), f"engine: non-identical {fields}"

    cfg_v = SimConfig(n_clients=100, budget=3.0, sweep_sharded=False)
    sw_plain = run_sweep("eflfg", preds, y, costs, T, cfg_v, seeds=[0, 1])
    sw_const = run_sweep("eflfg", preds, y, costs, T, cfg_v, seeds=[0, 1],
                         scenario="constant")
    assert sw_const.identical_to(sw_plain)
    assert sw_const.budget_scale is None      # neutral: stationary result

    server = SimServer(max_batch=4, max_wait_ms=1.0)
    server.register_stream("default", preds, y, costs)
    with server:
        fut = SimClient(server).submit("eflfg", 0, T=T, cfg=cfg,
                                       exact=True, scenario="constant")
        served = fut.result(600)
    assert fut.execution["mode"] == "exact"
    fields = served.identical_fields(plain)
    assert all(fields.values()), f"served-exact: non-identical {fields}"


def test_constant_bit_equal_batch_small():
    preds, y, costs = _stream()
    cfg = SimConfig(budget=2.0, sweep_sharded=False)
    T = 120
    plain = run_batch("eflfg", preds, y, costs, T, cfg, seeds=range(3))
    const = run_batch("eflfg", preds, y, costs, T, cfg, seeds=range(3),
                      scenario="constant")
    for a, b in zip(plain, const):
        assert a.identical_to(b)


# ---------------------------------------------------------------------------
# Scenario sweeps/batches + lockstep-waste diagnostic
# ---------------------------------------------------------------------------

def test_scenario_sweep_and_batch_lanes_agree():
    """Batched-family invariance holds for the scheduled program too:
    run_batch lanes match run_sweep lanes under the same scenario, and
    violations count against the realized per-round budgets."""
    preds, y, costs = _stream()
    cfg = SimConfig(budget=2.0, sweep_sharded=False)
    T = 250            # past the first outage at t=200 (T=200 would
                       # compile all-neutral and take the stationary path)
    sw = run_sweep("eflfg", preds, y, costs, T, cfg, seeds=range(4),
                   scenario="bursty_outage")
    rb = run_batch("eflfg", preds, y, costs, T, cfg, seeds=range(4),
                   scenario="bursty_outage")
    for i in range(4):
        assert rb[i].identical_to_sweep_lane(sw, i), f"lane {i}"
    assert sw.budget_scale is not None and sw.budget_scale.shape == (T,)
    # budget grid under a schedule: factors multiply each lane's base,
    # and violations are counted against exactly those realized budgets
    # (the mandatory self-loop transmit may exceed a collapsed budget —
    # that is the violation mechanism, so no hard cost bound holds)
    g = run_sweep("eflfg", preds, y, costs, T, cfg, seeds=[0, 1],
                  budgets=[1.0, 3.0], scenario="step_decay")
    assert g.mse_curves.shape == (2, 2, T)
    realized = (np.asarray([1.0, 3.0])[:, None, None]
                * scenarios.get("step_decay").budget.scale(T))
    np.testing.assert_array_equal(
        g.violations, (g.round_costs > realized + 1e-6).sum(-1))
    # the tighter starting budget violates at least as often
    assert (g.violations[0] >= g.violations[1]).all()


def test_lockstep_waste_diagnostic():
    preds, y, costs = _stream()
    T = 100
    cfg = SimConfig(budget=2.0, sweep_sharded=False)
    sw = run_sweep("eflfg", preds, y, costs, T, cfg, seeds=range(4))
    assert sw.graph_iters.shape == (4, T)
    assert (sw.graph_iters >= 0).all() and sw.graph_iters.max() > 0
    # definition: sum over rounds/lanes of (max-over-lanes - own)
    it = sw.graph_iters
    expect = int((it.max(0, keepdims=True) - it).sum())
    assert sw.lockstep_waste == expect
    # one lane idles through nothing; FedBoost builds no graph at all
    solo = run_sweep("eflfg", preds, y, costs, T, cfg, seeds=[0])
    assert solo.lockstep_waste == 0
    fb = run_sweep("fedboost", preds, y, costs, T, cfg, seeds=range(3))
    assert fb.lockstep_waste == 0 and not fb.graph_iters.any()
    # heterogeneous budgets make lanes converge at different speeds —
    # the documented worst case actually shows up in the diagnostic
    grid = run_sweep("eflfg", preds, y, costs, T, cfg, seeds=range(3),
                     budgets=[0.5, 2.0, 8.0])
    assert grid.lockstep_waste > 0
    # a lane's own iteration counts are invariant to its co-residents
    # (the custom_vmap batched rule counts per-lane productive trips),
    # so waste attribution composes across dispatch groupings
    solo_hi = run_sweep("eflfg", preds, y, costs, T, cfg, seeds=range(3),
                        budgets=[8.0])
    np.testing.assert_array_equal(grid.graph_iters[2],
                                  solo_hi.graph_iters[0])
    # budget compaction (engine.batch_buckets) removes exactly the
    # cross-budget component: per-budget waste sums strictly below the
    # mixed-dispatch figure on this pinned grid — the lockstep idle time
    # a bucketed run_batch of the same lanes no longer pays
    per_bucket = sum(int((blk.max(0, keepdims=True) - blk).sum())
                     for blk in grid.graph_iters)
    assert per_bucket < grid.lockstep_waste


# ---------------------------------------------------------------------------
# Serving under scenarios
# ---------------------------------------------------------------------------

def test_served_scenario_batched_equals_engine():
    from repro.serve import SimServer, SimClient, SimRequest, group_key
    preds, y, costs = _stream()
    T, cfg = 120, SimConfig(budget=2.0)
    scen = scenarios.get("concept_drift")
    base = dict(algo="eflfg", seed=0, T=T)
    k_plain = group_key(SimRequest(**base))
    k_scen = group_key(SimRequest(**base, scenario=scen))
    assert k_plain != k_scen          # never share a bucket
    with SimServer(max_batch=8, max_wait_ms=100.0) as server:
        server.register_stream("default", preds, y, costs)
        client = SimClient(server)
        futs = client.submit_many(
            [dict(algo="eflfg", seed=s, T=T, cfg=cfg,
                  scenario="concept_drift") for s in range(3)]
            + [dict(algo="eflfg", seed=s, T=T, cfg=cfg) for s in range(3)])
        served = [f.result(120) for f in futs]
    cfg_v = SimConfig(budget=2.0, sweep_sharded=False)
    direct = run_batch("eflfg", preds, y, costs, T, cfg_v, seeds=range(3),
                       scenario="concept_drift")
    plain = run_batch("eflfg", preds, y, costs, T, cfg_v, seeds=range(3))
    for i in range(3):
        assert served[i].identical_to(direct[i]), f"scenario lane {i}"
        assert served[3 + i].identical_to(plain[i]), f"plain lane {i}"
    # unknown scenario names fail the submitter synchronously
    srv = SimServer(max_batch=4)
    srv.register_stream("default", preds, y, costs)
    with pytest.raises(ValueError, match="unknown scenario"):
        srv.submit("eflfg", 0, T=T, scenario="ghost")


# ---------------------------------------------------------------------------
# Per-lane mixed scenarios: one program serves any scenario mix
# ---------------------------------------------------------------------------

# one preset per schedule channel (budget scale / participation mask /
# label shift), all non-neutral at T=120
MIX = ("step_decay", "partial_participation", "concept_drift")


def test_mixed_scenario_batch_bit_equal_split_dispatch():
    """A run_batch whose lanes carry different scenarios is bit-equal,
    lane for lane, to scenario-keyed homogeneous dispatches of the same
    requests — co-tenant schedules must not leak across lanes, and the
    stacked program stays in the batched family."""
    preds, y, costs = _stream()
    cfg = SimConfig(budget=2.0, sweep_sharded=False)
    T = 120
    lanes = MIX * 2                   # 6 lanes, interleaved mix
    mixed = run_batch("eflfg", preds, y, costs, T, cfg, seeds=range(6),
                      scenario=list(lanes))
    for name in MIX:
        idx = [i for i, s in enumerate(lanes) if s == name]
        split = run_batch("eflfg", preds, y, costs, T, cfg,
                          seeds=idx, scenario=name)
        for j, i in enumerate(idx):
            assert mixed[i].identical_to(split[j]), f"{name} lane {i}"


def test_mixed_participation_per_lane_divisor():
    """Regression: lanes in one dispatch running different participation
    masks must each normalize by their OWN surviving-client count.  A
    per-bucket divisor would corrupt the full-participation lanes the
    moment a masked co-tenant shared their batch — pinned by
    bit-equality against the homogeneous dispatch for both algorithms,
    plus the lockstep_waste identity on the mixed sweep."""
    preds, y, costs = _stream()
    cfg = SimConfig(budget=2.0, sweep_sharded=False)
    T = 120
    lanes = ["partial_participation", "concept_drift"] * 2  # masked/full
    for algo in ("eflfg", "fedboost"):
        mixed = run_batch(algo, preds, y, costs, T, cfg, seeds=range(4),
                          scenario=lanes)
        part = run_batch(algo, preds, y, costs, T, cfg, seeds=[0, 2],
                         scenario="partial_participation")
        full = run_batch(algo, preds, y, costs, T, cfg, seeds=[1, 3],
                         scenario="concept_drift")
        for i, r in zip([0, 2], part):
            assert mixed[i].identical_to(r), f"{algo} masked lane {i}"
        for i, r in zip([1, 3], full):
            assert mixed[i].identical_to(r), f"{algo} full lane {i}"
    sw = run_sweep("eflfg", preds, y, costs, T, cfg, seeds=range(4),
                   scenario=lanes)
    it = sw.graph_iters
    assert sw.lockstep_waste == int((it.max(0, keepdims=True) - it).sum())


def test_mixed_all_neutral_lanes_take_stationary_path():
    """A per-lane sequence that is neutral in EVERY lane ("constant" /
    None) must dispatch the scenario-free program — bit-equal by
    construction to scenario=None, not merely float-close — and a
    length mismatch fails fast."""
    preds, y, costs = _stream()
    cfg = SimConfig(budget=2.0, sweep_sharded=False)
    T = 120
    plain = run_batch("eflfg", preds, y, costs, T, cfg, seeds=range(3))
    neut = run_batch("eflfg", preds, y, costs, T, cfg, seeds=range(3),
                     scenario=["constant", None, "constant"])
    for a, b in zip(plain, neut):
        assert a.identical_to(b)
    with pytest.raises(ValueError, match="per-lane"):
        run_batch("eflfg", preds, y, costs, T, cfg, seeds=range(3),
                  scenario=["constant", None])


def test_mixed_scenario_sweep_per_lane_scale():
    """run_sweep accepts a per-lane scenario sequence: lanes match the
    mixed run_batch, budget_scale comes back (n_seeds, T), and
    violations count against each lane's OWN realized budgets."""
    preds, y, costs = _stream()
    cfg = SimConfig(budget=2.0, sweep_sharded=False)
    T = 120
    sw = run_sweep("eflfg", preds, y, costs, T, cfg, seeds=range(3),
                   scenario=list(MIX))
    rb = run_batch("eflfg", preds, y, costs, T, cfg, seeds=range(3),
                   scenario=list(MIX))
    for i in range(3):
        assert rb[i].identical_to_sweep_lane(sw, i), f"lane {i}"
    assert sw.budget_scale.shape == (3, T)
    # lane 0 decays, lanes 1-2 are budget-neutral
    np.testing.assert_array_equal(
        sw.budget_scale[0], scenarios.get("step_decay").budget.scale(T))
    np.testing.assert_array_equal(sw.budget_scale[1:], 1.0)
    realized = 2.0 * np.asarray(sw.budget_scale)
    np.testing.assert_array_equal(
        sw.violations, (sw.round_costs > realized + 1e-6).sum(-1))


def test_mixed_scenario_stack_cache_reuse():
    """The stacked per-lane schedule arrays are cached across waves: a
    second dispatch of the same scenario mix (different seeds) reuses
    the same device-resident stack instead of recompiling it."""
    from repro.federated import engine
    preds, y, costs = _stream()
    cfg = SimConfig(budget=2.0, sweep_sharded=False)
    T = 120
    lanes = list(MIX * 2)
    engine._STACK_CACHE.clear()
    run_batch("eflfg", preds, y, costs, T, cfg, seeds=range(6),
              scenario=lanes)
    entries = {k: id(v) for k, v in engine._STACK_CACHE.items()}
    assert len(entries) == 1
    run_batch("eflfg", preds, y, costs, T, cfg, seeds=range(6, 12),
              scenario=lanes)
    assert {k: id(v) for k, v in engine._STACK_CACHE.items()} == entries


def test_mixed_scenario_sharded_trivial_mesh_bit_equal():
    """The per-lane schedule stack through the shard_map/padding
    machinery (trivial one-device mesh) reproduces the mixed vmap path
    bit-for-bit, pad_lane_tree included."""
    from repro.launch.mesh import make_sweep_mesh
    preds, y, costs = _stream()
    T = 120
    cfg_v = SimConfig(budget=2.0, sweep_sharded=False)
    cfg = SimConfig(budget=2.0)
    trivial = make_sweep_mesh(devices=jax.devices()[:1])
    sv = run_sweep("eflfg", preds, y, costs, T, cfg_v, seeds=range(3),
                   scenario=list(MIX))
    ss = run_sweep("eflfg", preds, y, costs, T, cfg, seeds=range(3),
                   mesh=trivial, scenario=list(MIX))
    assert ss.sharded and not sv.sharded
    assert ss.identical_to(sv)
    np.testing.assert_array_equal(ss.budget_scale, sv.budget_scale)


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >1 device (forced-8 CI job)")
def test_mixed_scenario_sharded_multi_device_bit_equal():
    """Real partitioning of the lane axis: a mixed-scenario sweep
    sharded over every visible device (schedule stack padded alongside
    keys/budgets) matches the mixed vmap path."""
    preds, y, costs = _stream()
    T = 120
    n_seeds = jax.device_count() + 2          # force pad_lane_tree
    lanes = [MIX[i % len(MIX)] for i in range(n_seeds)]
    cfg_v = SimConfig(budget=2.0, sweep_sharded=False)
    cfg = SimConfig(budget=2.0, sweep_sharded=True)
    sv = run_sweep("eflfg", preds, y, costs, T, cfg_v,
                   seeds=range(n_seeds), scenario=lanes)
    ss = run_sweep("eflfg", preds, y, costs, T, cfg,
                   seeds=range(n_seeds), scenario=lanes)
    assert ss.sharded
    assert ss.identical_to(sv)
    np.testing.assert_array_equal(ss.budget_scale, sv.budget_scale)


def test_served_mixed_scenario_wave_single_bucket():
    """The acceptance wave: 8 requests spanning three scenario presets
    coalesce into ONE bucket (the group key carries only the schedule
    CLASS) and dispatch as one batched program — each lane bit-equal to
    the scenario-keyed dispatch of the same request."""
    from repro.serve import SimServer, SimClient, SimRequest, group_key
    preds, y, costs = _stream()
    T, cfg = 120, SimConfig(budget=2.0)
    ka = group_key(SimRequest(algo="eflfg", seed=0, T=T,
                              scenario=scenarios.get("step_decay")))
    kb = group_key(SimRequest(algo="eflfg", seed=0, T=T,
                              scenario=scenarios.get("concept_drift")))
    assert ka == kb               # different scenarios, one bucket class
    lanes = [MIX[i % len(MIX)] for i in range(8)]
    with SimServer(max_batch=8, max_wait_ms=100.0) as server:
        server.register_stream("default", preds, y, costs)
        futs = SimClient(server).submit_many(
            [dict(algo="eflfg", seed=s, T=T, cfg=cfg, scenario=name)
             for s, name in enumerate(lanes)])
        served = [f.result(300) for f in futs]
        st = server.stats()
    assert st["batches"] == 1 and st["served"] == 8
    execs = [f.execution for f in futs]
    assert all(e["seq"] == execs[0]["seq"] for e in execs)
    assert execs[0]["bucket"] == 8 and execs[0]["scheduled"]
    assert execs[0]["n_scenarios"] == 3
    cfg_v = SimConfig(budget=2.0, sweep_sharded=False)
    for name in MIX:
        idx = [i for i, s in enumerate(lanes) if s == name]
        direct = run_batch("eflfg", preds, y, costs, T, cfg_v,
                           seeds=idx, scenario=name)
        for j, i in enumerate(idx):
            assert served[i].identical_to(direct[j]), f"{name} lane {i}"


def test_served_neutral_scenario_joins_stationary_bucket():
    """submit normalizes all-neutral scenarios to None, so "constant"
    traffic batches WITH stationary traffic — one bucket, and both
    lanes bit-equal to the scenario-free program by construction."""
    from repro.serve import SimServer, SimClient
    preds, y, costs = _stream()
    T, cfg = 120, SimConfig(budget=2.0)
    with SimServer(max_batch=8, max_wait_ms=100.0) as server:
        server.register_stream("default", preds, y, costs)
        futs = SimClient(server).submit_many(
            [dict(algo="eflfg", seed=0, T=T, cfg=cfg, scenario="constant"),
             dict(algo="eflfg", seed=1, T=T, cfg=cfg)])
        served = [f.result(120) for f in futs]
        st = server.stats()
    assert st["batches"] == 1
    assert not futs[0].execution["scheduled"]
    plain = run_batch("eflfg", preds, y, costs, T,
                      SimConfig(budget=2.0, sweep_sharded=False),
                      seeds=[0, 1])
    for s, p in zip(served, plain):
        assert s.identical_to(p)


# ---------------------------------------------------------------------------
# Committed artifacts + CLI wiring
# ---------------------------------------------------------------------------

def test_committed_scenario_artifacts():
    """The committed experiments/scenarios set: one JSON per registered
    preset, schema-complete, violations consistent with neutrality."""
    art_dir = os.path.join(REPO, "experiments", "scenarios")
    paths = sorted(glob.glob(os.path.join(art_dir, "*.json")))
    found = {os.path.splitext(os.path.basename(p))[0] for p in paths}
    assert set(scenarios.names()) <= found, \
        f"missing artifacts for {set(scenarios.names()) - found}"
    for path in paths:
        with open(path) as f:
            rec = json.load(f)
        assert rec["scenario"] in scenarios.names()
        assert rec["T"] > 0 and rec["algos"]
        for algo, cell in rec["algos"].items():
            assert algo in ("eflfg", "fedboost")
            assert cell["budget_violations"] >= 0
            assert 0.0 <= cell["violation_frac"] <= 1.0
            assert np.isfinite(cell["final_mse"])
        if rec["scenario"] == "constant":
            assert rec["neutral"] is True
            assert rec["algos"]["eflfg"]["budget_violations"] == 0
        if rec["scenario"] == "bursty_outage":
            assert rec["algos"]["eflfg"]["budget_violations"] > 0


def test_scenario_run_cli(tmp_path):
    from repro.launch import scenario_run
    rc = scenario_run.main(["--scenarios", "bursty_outage", "--algos",
                            "eflfg", "--T", "250", "--K", "6",
                            "--n-stream", "300", "--clients", "10",
                            "--out", str(tmp_path)])
    assert rc == 0
    with open(tmp_path / "bursty_outage.json") as f:
        rec = json.load(f)
    assert rec["algos"]["eflfg"]["budget_violations"] > 0
    assert scenario_run.main(["--list"]) == 0


# ---------------------------------------------------------------------------
# Mesh-sharded scheduled sweeps (trivial mesh everywhere; real partitioning
# under the forced-8 CI job)
# ---------------------------------------------------------------------------

def test_scenario_sharded_trivial_mesh_bit_equal():
    """The scheduled program through the full shard_map/padding machinery
    on a trivial one-device mesh must reproduce the scheduled vmap path
    bit-for-bit (same per-config program — the PR-3 discipline)."""
    from repro.launch.mesh import make_sweep_mesh
    preds, y, costs = _stream()
    T = 100
    cfg_v = SimConfig(budget=2.0, sweep_sharded=False)
    cfg = SimConfig(budget=2.0)
    trivial = make_sweep_mesh(devices=jax.devices()[:1])
    sv = run_sweep("eflfg", preds, y, costs, T, cfg_v, seeds=range(3),
                   scenario="degraded_uplink")
    ss = run_sweep("eflfg", preds, y, costs, T, cfg, seeds=range(3),
                   mesh=trivial, scenario="degraded_uplink")
    assert ss.sharded and not sv.sharded
    assert ss.identical_to(sv)
    np.testing.assert_array_equal(ss.violations, sv.violations)


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >1 device (forced-8 CI job)")
def test_scenario_sharded_multi_device_bit_equal():
    """Real partitioning: a scheduled sweep sharded over every visible
    device (padding included) matches the scheduled vmap path."""
    preds, y, costs = _stream()
    T = 100
    n_seeds = jax.device_count() + 2          # force padding
    cfg_v = SimConfig(budget=2.0, sweep_sharded=False)
    cfg = SimConfig(budget=2.0, sweep_sharded=True)
    sv = run_sweep("eflfg", preds, y, costs, T, cfg_v,
                   seeds=range(n_seeds), scenario="bursty_outage")
    ss = run_sweep("eflfg", preds, y, costs, T, cfg,
                   seeds=range(n_seeds), scenario="bursty_outage")
    assert ss.sharded
    assert ss.identical_to(sv)
