"""Federated ensemble-learning simulation (paper §IV setup): reference loop.

100 clients, a server holding the 22-expert pool, an online stream: at each
round the server plans a transmit set (EFL-FG graph draw or FedBoost
Bernoulli draw), the selected clients each observe one new sample, compute
the per-model and ensemble losses, and uplink them; the server updates its
weights.  Per the paper's modification of FedBoost, clients never batch —
one sample per client per round.

Losses sent to the server are squared errors normalized into [0, 1]
(assumption (a2)): L = min(sq_err / loss_scale, 1).  The *reported* MSE_t
metric is the paper's unnormalized running mean of per-round client-mean
squared errors: MSE_t = (1/t) sum_tau (1/|C_tau|) sum_i (yhat - y)^2.

The number of clients per round follows the paper's uplink bandwidth
formula N_t = floor(b_t / (b_loss * (|S_t| + 1))) when ``uplink_bandwidth``
is set, else it is the fixed ``clients_per_round``.

This module holds the *reference* execution path: one Python iteration per
round, one jitted dispatch of the round body, host-side float64 metric
bookkeeping.  The device-resident engine (`repro.federated.engine`) runs
the *same* round body — built by ``make_round_body`` from the traceable
pieces below — as a single ``lax.scan``, so the two paths produce
bit-identical trajectories (selection masks, costs, losses) and differ
only in execution strategy.  Equivalence is pinned by
``tests/test_engine_equivalence.py``; use the engine for anything
performance-sensitive.

Within either execution path, the *client-side evaluation* itself has
two implementations selected by ``SimConfig.use_fused``: the unfused
ops below (``client_window_losses`` + ``fedboost_window_grad`` + the
planner's eq.-(5) mixing) or the Pallas-fused
``repro.kernels.client_eval`` kernel, which runs them as one launch per
round.  Fused-vs-unfused parity (bit-equal selection trajectories,
float32-tolerance curves) is pinned by ``tests/test_client_eval.py``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import (init_state, fedboost_init,
                        make_eflfg_scan_body, make_fedboost_scan_body,
                        regret_init, regret_update, regret_value,
                        RegretTracker)
from repro.core.numerics import ladder_matvec, ladder_sum
from repro.kernels.client_eval import ops as client_eval_ops

__all__ = ["SimConfig", "SimResult", "run_simulation_reference",
           "make_round_body", "client_window_losses", "fedboost_window_grad",
           "n_clients_traceable", "eval_window"]


@dataclass
class SimConfig:
    n_clients: int = 100
    clients_per_round: int = 5
    budget: float = 3.0
    eta: Optional[float] = None       # default 1/sqrt(T) (paper)
    xi: Optional[float] = None        # default 1/sqrt(T) (paper)
    loss_scale: float = 4.0           # sq-err -> [0,1] normalization
    uplink_bandwidth: Optional[float] = None  # b_t; None = fixed N_t
    loss_bandwidth: float = 1.0       # b_loss
    seed: int = 0
    use_fused: bool = True            # Pallas-fused client eval (one kernel
                                      # per round) vs the unfused ~6-op path;
                                      # trajectories agree (float32, pinned
                                      # by tests/test_client_eval.py)
    use_fused_server: bool = False    # Pallas-fused EFL-FG server round
                                      # (repro.kernels.server_round: two
                                      # launches per round) vs the unfused
                                      # plan_round/update_state ops; bit-equal
                                      # trajectories pinned by
                                      # tests/test_server_round.py.  No-op
                                      # for FedBoost.
    sweep_sharded: Optional[bool] = None  # run_sweep dispatch: None = auto
                                      # (shard over the device mesh when >1
                                      # device is visible), True = force the
                                      # sharded path, False = always the
                                      # single-device vmap (docs/sweeps.md)

    def rates(self, T: int):
        eta = self.eta if self.eta is not None else 1.0 / np.sqrt(T)
        xi = self.xi if self.xi is not None else 1.0 / np.sqrt(T)
        return float(eta), float(xi)

    def static_key(self, T: int) -> tuple:
        """Every field that shapes the compiled program for horizon ``T``
        — excluding ``seed``/``budget``, which are jit arguments, and
        ``sweep_sharded``, which is a dispatch knob.  The single source
        for the engine's scan-cache keys AND the serving batcher's group
        key: a new program-shaping field added here batches and caches
        correctly everywhere at once (a field added to only one of the
        mirrored tuples would silently batch incompatible requests)."""
        return (self.n_clients, self.clients_per_round, self.loss_scale,
                self.uplink_bandwidth, self.loss_bandwidth, self.use_fused,
                self.use_fused_server, self.rates(T))


@dataclass
class SimResult:
    mse_curve: np.ndarray            # paper's MSE_t (running mean)
    budget_violations: int           # rounds with cost > B
    violation_frac: float
    regret: RegretTracker
    sel_sizes: np.ndarray            # |S_t| per round
    dom_sizes: np.ndarray            # |D_t| per round (EFL-FG only)
    round_costs: np.ndarray
    name: str = ""
    sel_masks: Optional[np.ndarray] = None  # (T, K) bool transmit sets

    # the arrays that define trajectory equality between execution paths
    # (mirrors SweepResult.FIELDS; regret is compared via its curve)
    FIELDS = ("mse_curve", "sel_sizes", "dom_sizes", "round_costs",
              "sel_masks")

    @property
    def final_mse(self) -> float:
        return float(self.mse_curve[-1])

    def identical_fields(self, other: "SimResult") -> dict:
        """Per-field exact-equality map vs another run's result."""
        def eq(a, b):
            if (a is None) != (b is None):
                return False
            return a is None or bool(np.array_equal(a, b))
        out = {f: eq(getattr(self, f), getattr(other, f))
               for f in self.FIELDS}
        out["regret_curve"] = eq(self.regret.regret_curve(),
                                 other.regret.regret_curve())
        out["budget_violations"] = \
            self.budget_violations == other.budget_violations
        return out

    def identical_to(self, other: "SimResult") -> bool:
        """True iff every trajectory array matches ``other`` bit-for-bit."""
        return all(self.identical_fields(other).values())

    def identical_to_sweep_lane(self, sweep, lane) -> bool:
        """Bit-equality vs one lane of a ``SweepResult``, on the fields
        both carry (the served-equals-sweep contract of
        docs/serving.md#determinism; shared by tests/test_serve.py and
        the bench gate flags).  Regret is excluded: ``SweepResult``
        keeps the on-device float32 accumulation while ``SimResult``
        re-reduces in float64, so the two are not bitwise comparable by
        construction."""
        return (np.array_equal(self.mse_curve, sweep.mse_curves[lane])
                and np.array_equal(self.round_costs,
                                   sweep.round_costs[lane])
                and np.array_equal(self.sel_sizes, sweep.sel_sizes[lane])
                and self.budget_violations == int(sweep.violations[lane]))


# ---------------------------------------------------------------------------
# Traceable client-side evaluation (shared by reference loop + scan engine)
# ---------------------------------------------------------------------------

def eval_window(cfg: SimConfig) -> int:
    """Static per-round client-window size.

    With the bandwidth formula active, N_t is data dependent (up to
    ``n_clients``); a fixed window + mask keeps every shape static so the
    same code jits, scans, and vmaps.  Without it N_t is constant.
    """
    if cfg.uplink_bandwidth is None:
        return cfg.clients_per_round
    return cfg.n_clients


def n_clients_traceable(cfg: SimConfig, sel_size: jnp.ndarray) -> jnp.ndarray:
    """Paper's uplink formula N_t = floor(b_t / (b_loss (|S_t|+1))) as a
    traceable float32 computation (clamped to [1, n_clients])."""
    if cfg.uplink_bandwidth is None:
        return jnp.full_like(sel_size, cfg.clients_per_round)
    n = jnp.floor(jnp.float32(cfg.uplink_bandwidth)
                  / (jnp.float32(cfg.loss_bandwidth)
                     * (sel_size.astype(jnp.float32) + 1.0)))
    return jnp.clip(n.astype(sel_size.dtype), 1, cfg.n_clients)


@partial(jax.jit, static_argnames=("window",))
def client_window_losses(preds: jnp.ndarray, y: jnp.ndarray,
                         cursor: jnp.ndarray, n_t: jnp.ndarray,
                         mix: jnp.ndarray, loss_scale: float, window: int,
                         active=None, shift=None):
    """One round of client-side evaluation on a fixed-size stream window.

    The round's ``n_t`` active clients are the first ``n_t`` positions of
    the ``window``-wide slice starting at ``cursor`` (wrapping); the rest
    are masked out.

    ``active``/``shift`` are the optional per-round schedule operands
    (``repro.scenarios``): a (window,) bool availability mask ANDed into
    the client mask — per-client means then divide by the surviving
    count, clamped to >= 1 — and a scalar additive label shift (concept
    drift).  ``None`` (the default) traces exactly the stationary
    program, so pre-scenario callers and cached programs are untouched.

    Returns ``(ens_sq_mean, ens_loss_norm, model_losses_norm)``.
    """
    n_stream = preds.shape[1]
    offs = jnp.arange(window)
    idx = (cursor + offs) % n_stream
    cmask = offs < n_t
    if active is not None:
        cmask = cmask & active
    p_cl = preds[:, idx]                           # (K, window)
    y_cl = y[idx]
    if shift is not None:
        y_cl = y_cl + shift
    sq = (p_cl - y_cl[None, :]) ** 2               # per-model sq errors
    # ladder reductions (core.numerics): client losses feed back into the
    # server weight state, so their accumulation order must be identical
    # across every program variant (unfused / fused kernels / vmapped)
    model_losses = ladder_sum(
        jnp.where(cmask[None, :], jnp.minimum(sq / loss_scale, 1.0), 0.0),
        axis=1)
    yhat = ladder_matvec(mix, p_cl)                # true ensemble prediction
    ens_sq = jnp.where(cmask, (yhat - y_cl) ** 2, 0.0)
    n_eff = (n_t if active is None
             else jnp.maximum(jnp.sum(cmask), 1))
    ens_sq_mean = ladder_sum(ens_sq) / n_eff.astype(ens_sq.dtype)
    ens_loss = ladder_sum(jnp.minimum(ens_sq / loss_scale, 1.0))
    return ens_sq_mean, ens_loss, model_losses


@partial(jax.jit, static_argnames=("window",))
def fedboost_window_grad(preds: jnp.ndarray, y: jnp.ndarray,
                         cursor: jnp.ndarray, n_t: jnp.ndarray,
                         mix: jnp.ndarray, window: int,
                         active=None, shift=None) -> jnp.ndarray:
    """Streaming clients' SGD gradient of the ensemble loss wrt the mixture
    weights over the round's window: g_k = 2/n sum_i (yhat - y) f_k(x_i).
    ``active``/``shift`` as in ``client_window_losses`` (masked clients
    contribute no gradient; ``n`` becomes the surviving count)."""
    n_stream = preds.shape[1]
    offs = jnp.arange(window)
    idx = (cursor + offs) % n_stream
    cmask = offs < n_t
    if active is not None:
        cmask = cmask & active
    p_cl = preds[:, idx]
    y_cl = y[idx]
    if shift is not None:
        y_cl = y_cl + shift
    resid = jnp.where(cmask, ladder_matvec(mix, p_cl) - y_cl, 0.0)
    n_eff = (n_t if active is None
             else jnp.maximum(jnp.sum(cmask), 1))
    return (2.0 / n_eff.astype(resid.dtype)) * ladder_sum(
        p_cl * resid[None, :], axis=1)


def _eflfg_loss_fn(evaluate, cfg, n_stream):
    """Client-side evaluation closure for the EFL-FG round body.

    ``loss_carry = (stream cursor, RegretCarry)``; the per-round ``out``
    pytree carries everything the metric layers need.  ``evaluate(plan,
    cursor, n_t, sched) -> (ens_sq_mean, ens_norm, model_losses, grad)``
    is the fused-or-unfused evaluation (see ``make_round_body``);
    ``sched`` is ``None`` (stationary) or the round's ``(active,
    label_shift)`` schedule slice (``repro.scenarios``).  Everything
    around it — client counting, regret accounting, the out dict, the
    cursor advance — is shared, so the execution strategies cannot drift
    apart structurally.  The cursor always advances by ``n_t``: stream
    time passes whether or not a masked client reports.
    """
    @jax.named_scope("round.eval")
    def loss_fn(plan, loss_carry, sched=None):
        cursor, racc = loss_carry
        sel_size = jnp.sum(plan.sel).astype(jnp.int32)
        n_t = n_clients_traceable(cfg, sel_size)
        ens_sq, ens_norm, ml_norm, _ = evaluate(plan, cursor, n_t, sched)
        racc = regret_update(racc, ens_norm, ml_norm)
        out = dict(sel=plan.sel, dom_size=jnp.sum(plan.dom),
                   cost=plan.round_cost, ens_sq_mean=ens_sq,
                   ens_norm=ens_norm, ml_norm=ml_norm,
                   regret=regret_value(racc),
                   graph_iters=plan.graph_iters)
        cursor = (cursor + n_t) % n_stream
        return ml_norm, ens_norm, (cursor, racc), out
    return loss_fn


def _fedboost_grad_fn(evaluate, cfg, n_stream):
    """Client-side gradient closure for the FedBoost round body (same
    ``evaluate`` contract as ``_eflfg_loss_fn``, with the gradient slot
    populated; ``graph_iters`` is zero — FedBoost builds no graph)."""
    @jax.named_scope("round.eval")
    def grad_fn(plan, loss_carry, sched=None):
        sel, _pi, _mix, cost = plan
        cursor, racc = loss_carry
        sel_size = jnp.sum(sel).astype(jnp.int32)
        n_t = n_clients_traceable(cfg, sel_size)
        ens_sq, ens_norm, ml_norm, grad = evaluate(plan, cursor, n_t, sched)
        racc = regret_update(racc, ens_norm, ml_norm)
        out = dict(sel=sel, dom_size=jnp.zeros((), jnp.int32),
                   cost=cost, ens_sq_mean=ens_sq,
                   ens_norm=ens_norm, ml_norm=ml_norm,
                   regret=regret_value(racc),
                   graph_iters=jnp.zeros((), jnp.int32))
        cursor = (cursor + n_t) % n_stream
        return grad, (cursor, racc), out
    return grad_fn


def _make_evaluate(algo: str, fused: bool, preds, y, cfg: SimConfig,
                   W: int, ext=None):
    """Build the ``evaluate(plan, cursor, n_t)`` callback: the only part
    of the round body that differs between the fused Pallas kernel and
    the unfused ops.

    Both algorithms hand the kernel the plan's mixture: EFL-FG's eq.-(5)
    softmax (no gradient needed) and FedBoost's renormalized alpha (the
    kernel also emits its SGD gradient).

    ``ext`` optionally supplies a precomputed ``extend_stream`` result —
    the reference loop passes it so the loop-invariant extension is built
    once per *run* instead of once per per-round jit dispatch.
    """
    if fused:
        preds_ext, y_ext = (client_eval_ops.extend_stream(preds, y, W)
                            if ext is None else ext)
    if algo == "eflfg":
        if fused:
            def evaluate(plan, cursor, n_t, sched=None):
                active, shift = sched if sched is not None else (None, None)
                ev = client_eval_ops.client_eval(
                    preds_ext, y_ext, cursor, n_t, plan.mix,
                    loss_scale=cfg.loss_scale, window=W, with_grad=False, active=active, shift=shift)
                return ev.ens_sq_mean, ev.ens_norm, ev.model_losses, None
        else:
            def evaluate(plan, cursor, n_t, sched=None):
                active, shift = sched if sched is not None else (None, None)
                return client_window_losses(
                    preds, y, cursor, n_t, plan.mix, cfg.loss_scale, W,
                    active, shift) + (None,)
    elif algo == "fedboost":
        if fused:
            def evaluate(plan, cursor, n_t, sched=None):
                active, shift = sched if sched is not None else (None, None)
                _sel, _pi, mix, _cost = plan
                ev = client_eval_ops.client_eval(
                    preds_ext, y_ext, cursor, n_t, mix,
                    loss_scale=cfg.loss_scale, window=W, with_grad=True, active=active, shift=shift)
                return ev.ens_sq_mean, ev.ens_norm, ev.model_losses, ev.grad
        else:
            def evaluate(plan, cursor, n_t, sched=None):
                active, shift = sched if sched is not None else (None, None)
                _sel, _pi, mix, _cost = plan
                losses = client_window_losses(
                    preds, y, cursor, n_t, mix, cfg.loss_scale, W,
                    active, shift)
                grad = fedboost_window_grad(preds, y, cursor, n_t, mix, W,
                                            active, shift)
                return losses + (grad,)
    else:
        raise ValueError(f"unknown algo {algo!r}")
    return evaluate


def _make_evaluate_sharded(algo: str, preds, y, cfg: SimConfig, W: int,
                           data_axis):
    """Data-parallel ``evaluate`` for round bodies traced inside a
    shard_map that binds a client/data mesh axis: each device on
    ``data_axis = (name, size)`` evaluates its contiguous chunk of the
    round's window and the totals come back via the same psum reduction
    as ``sharded.sharded_round_losses``.  Same contract as
    ``_make_evaluate``; requires ``W % size == 0`` (the caller falls back
    to replicated evaluation otherwise).
    """
    from .sharded import sharded_window_eval
    axis, size = data_axis
    if algo == "eflfg":
        def evaluate(plan, cursor, n_t, sched=None):
            active, shift = sched if sched is not None else (None, None)
            return sharded_window_eval(
                preds, y, cursor, n_t, plan.mix, cfg.loss_scale, W,
                axis=axis, axis_size=size, with_grad=False,
                active=active, shift=shift)
    elif algo == "fedboost":
        def evaluate(plan, cursor, n_t, sched=None):
            active, shift = sched if sched is not None else (None, None)
            _sel, _pi, mix, _cost = plan
            return sharded_window_eval(
                preds, y, cursor, n_t, mix, cfg.loss_scale, W,
                axis=axis, axis_size=size, with_grad=True,
                active=active, shift=shift)
    else:
        raise ValueError(f"unknown algo {algo!r}")
    return evaluate


def make_round_body(algo: str, preds, y, costs, cfg: SimConfig, budget,
                    eta, xi, ext=None, data_axis=None):
    """Build the one-round scan body and its initial-carry constructor.

    Returns ``(body, init_carry)`` where ``body(carry, x) -> (carry, out)``
    is a pure traceable function (the ``lax.scan`` body) and
    ``init_carry(key)`` builds the round-0 carry.  The reference loop runs
    ``body`` once per Python iteration; the engine scans it — the round
    computation itself is the same traced function either way.

    ``x`` is the scan's per-round ``xs`` slice: ``None`` on the
    stationary path (which then traces exactly the pre-scenario
    program), or a ``repro.scenarios.ScheduleArrays`` slice — the round
    budget is scaled by ``x.budget_scale`` and the client evaluation
    folds in ``x.active`` (participation mask) and ``x.label_shift``
    (concept drift).  The schedule arrays are jit *arguments*: one
    scheduled program serves every scenario of the same shape.

    With ``cfg.use_fused`` the client-side evaluation goes through the
    Pallas-fused ``repro.kernels.client_eval`` op (one launch per round)
    on a wrap-free W-extended copy of the stream — loop-invariant, so
    the scan engine builds it once per jitted call, and the reference
    loop precomputes it once per run and passes it in via ``ext``.
    Streams shorter than the window fall back to the unfused
    modulo-gather path (the extension trick needs ``W <= n_stream``).

    ``data_axis = (mesh_axis_name, size)`` marks the body as being traced
    inside a shard_map with a client/data axis (the engine's 2-D
    ``(sweep, data)`` sharded sweep): the client evaluation then splits
    the round's window across that axis and psums the totals
    (``_make_evaluate_sharded``).  When the window does not divide the
    axis size, every device evaluates the full window redundantly instead
    (replicated inputs make that correct, just not parallel).
    """
    K, n_stream = preds.shape
    W = eval_window(cfg)
    if (data_axis is not None and data_axis[1] > 1
            and W % data_axis[1] == 0):
        evaluate = _make_evaluate_sharded(algo, preds, y, cfg, W, data_axis)
    else:
        fused = cfg.use_fused and W <= n_stream
        evaluate = _make_evaluate(algo, fused, preds, y, cfg, W, ext)
    if algo == "eflfg":
        server_round = None
        if cfg.use_fused_server:
            from repro.kernels.server_round import ops as server_round_ops
            server_round = server_round_ops.fused_server_round()
        body = make_eflfg_scan_body(_eflfg_loss_fn(evaluate, cfg, n_stream),
                                    costs, budget, eta, xi,
                                    server_round=server_round)
        algo_init = lambda: init_state(K)
    else:
        body = make_fedboost_scan_body(
            _fedboost_grad_fn(evaluate, cfg, n_stream), costs, budget, eta)
        algo_init = lambda: fedboost_init(K)

    def init_carry(key):
        return (algo_init(), key, (jnp.int32(0), regret_init(K)))

    return body, init_carry


# ---------------------------------------------------------------------------
# Reference loop: per-round dispatch, host-side float64 metrics
# ---------------------------------------------------------------------------

class _Metrics:
    def __init__(self, K: int, T: int, budget):
        # ``budget`` may be a scalar or a (T,) realized-budget schedule
        # (base * scenario scale) — violations compare per round.
        self.regret = RegretTracker(K, capacity=T)
        self.T = T
        self._thresh = np.broadcast_to(np.asarray(budget, float), (T,))
        self.mse_curve = np.empty(T)
        self.sel_sizes = np.zeros(T, dtype=int)
        self.dom_sizes = np.zeros(T, dtype=int)
        self.round_costs = np.empty(T)
        self.sel_masks = np.zeros((T, K), dtype=bool)
        self.violations = 0
        self._sq = 0.0

    def record(self, t, out):
        sel = np.asarray(out["sel"])
        cost = float(out["cost"])
        self.sel_masks[t] = sel
        self.sel_sizes[t] = int(sel.sum())
        self.dom_sizes[t] = int(out["dom_size"])
        self.round_costs[t] = cost
        if cost > self._thresh[t] + 1e-6:
            self.violations += 1
        self._sq += float(out["ens_sq_mean"])
        self.mse_curve[t] = self._sq / (t + 1)
        self.regret.update(float(out["ens_norm"]), np.asarray(out["ml_norm"]))

    def result(self, name) -> SimResult:
        return SimResult(self.mse_curve, self.violations,
                         self.violations / self.T, self.regret,
                         self.sel_sizes, self.dom_sizes, self.round_costs,
                         name, self.sel_masks)


# Jitted per-round steps are cached per configuration, mirroring the
# engine's scan cache (stream data, budget and rates are jit arguments):
# repeated reference runs retrace nothing, so reference-vs-engine
# benchmarks compare execution strategies, not compile counts.
_STEP_CACHE: dict = {}


def _get_step(algo: str, cfg: SimConfig, eta: float, xi: float):
    # eta/xi ride in the closure as compile-time constants — the same
    # structure as the engine's scan (engine._make_scan), so XLA folds
    # constants identically in both programs and trajectories stay
    # bit-identical between the two execution paths.
    key = (algo, cfg.n_clients, cfg.clients_per_round, cfg.loss_scale,
           cfg.uplink_bandwidth, cfg.loss_bandwidth, cfg.use_fused,
           cfg.use_fused_server, eta, xi)
    fn = _STEP_CACHE.get(key)
    if fn is None:
        eta_j, xi_j = jnp.float32(eta), jnp.float32(xi)

        def step(preds, y, costs, budget, carry, ext, x):
            body, _ = make_round_body(algo, preds, y, costs, cfg, budget,
                                      eta_j, xi_j, ext=ext)
            return body(carry, x)
        fn = _STEP_CACHE[key] = jax.jit(step)
    return fn


def run_simulation_reference(algo: str, preds, y, costs, T: int,
                             cfg: SimConfig, scenario=None) -> SimResult:
    """Run ``T`` rounds of ``algo`` in {"eflfg", "fedboost"}, one Python
    iteration and one device dispatch per round (the execution oracle the
    scan engine is tested against; see module docstring).

    ``preds``: (K, n_stream) precomputed expert predictions on the online
    stream (identical numbers to per-round client evaluation — clients are
    deterministic functions of the transmitted models, so precomputation is
    a pure speed optimization, not a semantic change).

    ``scenario`` (a registered name, ``repro.scenarios.Scenario``, or an
    already-``CompiledScenario``) threads the same per-round schedule
    slices through the per-round dispatch that the engine scans over —
    the oracle for the scheduled program family.  All-neutral schedules
    dispatch the stationary step, mirroring the engine's neutral
    fast-path (docs/scenarios.md#determinism).
    """
    preds = jnp.asarray(preds, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    costs = jnp.asarray(costs, jnp.float32)
    eta, xi = cfg.rates(T)
    budget_j = jnp.float32(cfg.budget)
    comp = None
    if scenario is not None:
        from repro import scenarios as _scenarios
        comp = (scenario if isinstance(scenario, _scenarios.CompiledScenario)
                else _scenarios.resolve(scenario).compile(T, cfg))
    use_sched = comp is not None and not comp.neutral
    step = _get_step(algo, cfg, eta, xi)
    # The fused path's W-extended stream is loop-invariant: build it once
    # per run here and feed it through the per-round jitted step, instead
    # of re-concatenating (K, n_stream) inside every round's dispatch.
    W = eval_window(cfg)
    ext = (client_eval_ops.extend_stream(preds, y, W)
           if cfg.use_fused and W <= preds.shape[1] else None)
    _, init_carry = make_round_body(algo, preds, y, costs, cfg, budget_j,
                                    jnp.float32(eta), jnp.float32(xi),
                                    ext=ext)
    thresh = (cfg.budget if comp is None else cfg.budget * comp.scale)
    metrics = _Metrics(preds.shape[0], T, thresh)
    carry = init_carry(jax.random.PRNGKey(cfg.seed))
    for t in range(T):
        x = (jax.tree.map(lambda a: a[t], comp.arrays) if use_sched
             else None)
        carry, out = step(preds, y, costs, budget_j, carry, ext, x)
        metrics.record(t, out)
    return metrics.result(algo)
