"""Device-resident simulation engine: whole experiments as one dispatch.

The reference loop (`repro.federated.simulation`) pays a Python
iteration, a jit dispatch, and host<->device transfers per round — at the
paper's scale (thousands of rounds, sweeps over seeds and budgets) that is
orders of magnitude slower than the hardware allows.  Here the entire
experiment is a single jit-compiled ``jax.lax.scan`` over rounds:

* the online stream cursor, client-loss evaluation and uplink-bandwidth
  client counting are fixed-shape traceable ops (the round body is built
  by ``make_round_body``, shared verbatim with the reference loop, so
  trajectories match bit-for-bit); with ``SimConfig.use_fused`` (the
  default) the client evaluation inside the scan is one Pallas-fused
  launch per round (``repro.kernels.client_eval``) instead of ~6 small
  ops,
* metric/regret accounting rides in the carry as fixed-shape arrays
  (``repro.core.regret.RegretCarry``),
* ``run_sweep`` vmaps the scan over a seed axis — and optionally a budget
  grid — so an entire table of the paper's comparisons runs as one
  device program,
* with more than one visible device, ``run_sweep`` shards that flat
  configuration axis over a ``("sweep", "data")`` mesh instead
  (``run_sweep_sharded``; helpers in ``repro.federated.sweep_sharding``)
  — grids of hundreds of configurations use the whole pod, and callers
  are unchanged (same ``SweepResult``, auto-dispatch overridable via
  ``SimConfig.sweep_sharded``).  See docs/sweeps.md.
* every entry point accepts a ``scenario`` (``repro.scenarios``): a
  declarative non-stationary schedule — per-round budget factors,
  client-participation masks, label drift — compiled into device arrays
  and threaded through the scan as ``xs``, so shapes stay static and
  one scheduled program serves every scenario of a shape.  The batch
  and sweep entry points additionally take a *per-lane sequence* of
  scenarios: compiled rows stack along the batch axis as ordinary jit
  arguments, so the same program serves any MIX of scenarios — the
  serving layer batches tenants on different schedules together on the
  strength of this.  All-neutral schedules (the ``constant`` preset)
  dispatch the scenario-free program, bit-equal by construction.  See
  docs/scenarios.md and docs/determinism.md.

``run_simulation_scan`` runs one (algo, seed, budget) configuration and
returns the same ``SimResult`` as the reference.  It is exported from
``repro.federated`` as ``run_simulation`` — the default for all callers.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import RegretTracker
from . import sweep_sharding
from .simulation import SimConfig, SimResult, eval_window, make_round_body

__all__ = ["run_simulation_scan", "run_batch", "batch_dispatch_plan",
           "batch_buckets", "run_sweep", "run_sweep_sharded", "SweepResult"]


# A call's root span is counted under ``<root>.compiled`` when JAX
# compiled inside it, and kept out of the steady-state span histograms.
obs.watch_compiles(jax.monitoring)

# Compiled scans are cached per configuration: the stream data, PRNG key
# and budget are jit *arguments*, so re-running (other seeds, other
# datasets of the same shape, budget grids) never recompiles.  The
# ``scheduled`` key bit selects the schedule-threaded program
# (repro.scenarios): the schedule ARRAYS are jit arguments too, so one
# scheduled program serves every scenario of the same (T, W) shape.
_SCAN_CACHE: dict = {}
_SCAN_UNROLL = 1   # >1 lets XLA fuse across rounds: faster, but rounding
                   # then differs from the per-round reference dispatch,
                   # breaking bit-exact trajectory equivalence

# Compiled scenario schedules, keyed (Scenario, T, window): the device
# arrays persist across requests/sweeps so serving traffic re-uploads
# nothing (Scenario is frozen/hashable by design).
_SCENARIO_CACHE: dict = {}

# Per-lane schedule stacks, keyed (lane scenarios, T, window[, n]): a
# mixed serve wave re-using the same scenario mix hits the stacked
# device arrays instead of re-stacking/re-uploading them every wave.
_STACK_CACHE: dict = {}


def _compile_scenario(scenario, T: int, cfg: SimConfig):
    """Normalize a ``scenario=`` argument into a ``CompiledScenario``.

    ``None`` passes through (stationary path); an already-compiled
    scenario is shape-validated (tests use this to force the scheduled
    program under neutral schedules); names/``Scenario`` specs compile
    through the module-level cache.
    """
    if scenario is None:
        return None
    from repro import scenarios as _scenarios
    if isinstance(scenario, _scenarios.CompiledScenario):
        comp = scenario
    else:
        scen = _scenarios.resolve(scenario)
        key = (scen, T, eval_window(cfg))
        comp = _SCENARIO_CACHE.get(key)
        if comp is None:
            comp = _SCENARIO_CACHE[key] = scen.compile(T, cfg)
    if comp.T != T or comp.window != eval_window(cfg):
        raise ValueError(
            f"scenario compiled for (T={comp.T}, window={comp.window}) "
            f"used with (T={T}, window={eval_window(cfg)}) — compile "
            "against the same horizon and config")
    return comp


def _lane_schedules(scenario, T: int, cfg: SimConfig, n: int):
    """Normalize a batch/sweep ``scenario=`` argument — ``None``, ONE
    scenario(-like), or a per-lane sequence of them — into the per-lane
    stacked schedule arrays the batched scheduled programs consume.

    Returns ``(stacked, scale)``:

    * ``(None, None)`` — the stationary program (no scenario given, or
      every lane compiled all-neutral: identity schedules dispatch the
      scenario-free program, bit-equal by construction);
    * otherwise ``stacked`` is a ``repro.scenarios.ScheduleArrays``
      whose every leaf carries a leading ``(n,)`` lane axis (lane ``i``
      runs its own schedule rows — any mix of scenarios in one
      program), and ``scale`` holds the realized budget factors:
      ``(T,)`` float64 when one shared scenario was given (every lane
      identical — the pre-existing ``SweepResult.budget_scale`` shape),
      ``(n, T)`` for a per-lane sequence.

    Stacks are cached per resolved lane tuple (``_STACK_CACHE``) so
    repeated serve waves over the same scenario mix re-upload nothing;
    lanes passed as already-``CompiledScenario`` bypass the cache (the
    arrays are not hashable).
    """
    if scenario is None:
        return None, None
    from repro import scenarios as _scenarios
    W = eval_window(cfg)
    if isinstance(scenario, (list, tuple)) and not isinstance(
            scenario, (_scenarios.CompiledScenario,
                       _scenarios.ScheduleArrays)):
        lanes = list(scenario)
        if len(lanes) != n:
            raise ValueError(
                f"per-lane scenario sequence has {len(lanes)} entries for "
                f"{n} lanes — pass one scenario, or exactly one per lane")
        comps = [_compile_scenario(s, T, cfg) for s in lanes]
        if all(c is None or c.neutral for c in comps):
            return None, None
        try:
            key = (tuple(None if s is None else _scenarios.resolve(s)
                         for s in lanes), T, W)
        except TypeError:
            key = None                      # CompiledScenario lanes
        if key is not None and key in _STACK_CACHE:
            return _STACK_CACHE[key]
        comps = [None if c is not None and c.neutral else c for c in comps]
        out = _scenarios.stack_schedules(comps, T, W)
        if key is not None:
            _STACK_CACHE[key] = out
        return out
    comp = _compile_scenario(scenario, T, cfg)
    if comp.neutral:
        return None, None
    try:
        key = (_scenarios.resolve(scenario), T, W, n)
    except TypeError:
        key = None
    if key is not None and key in _STACK_CACHE:
        return _STACK_CACHE[key]
    stacked = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), comp.arrays)
    out = (stacked, comp.scale)
    if key is not None:
        _STACK_CACHE[key] = out
    return out


def _cfg_key(cfg: SimConfig, T: int):
    return (T,) + cfg.static_key(T)


def _make_scan(algo: str, T: int, cfg: SimConfig, data_axis=None,
               scheduled: bool = False):
    """Build ``scan(preds, y, costs, key, budget[, sched]) -> per-round
    outputs``.

    ``data_axis = (mesh_axis_name, size)`` marks the scan as traced inside
    a shard_map with a client/data axis (the 2-D sharded sweep) — see
    ``make_round_body``.  ``scheduled`` threads a
    ``repro.scenarios.ScheduleArrays`` pytree through the scan as its
    ``xs`` (per-round budget scale, participation mask, label shift);
    without it the scan body receives ``x=None`` and traces exactly the
    pre-scenario program.
    """
    eta, xi = cfg.rates(T)
    eta, xi = jnp.float32(eta), jnp.float32(xi)

    def build_body(preds, y, costs, budget):
        body, init_carry = make_round_body(
            algo, preds, y, costs, cfg, jnp.asarray(budget, jnp.float32),
            eta, xi, data_axis=data_axis)

        # The transmit set is stacked as packed uint32 words, not as a
        # (K,) bool row: under the sweeps' nested vmaps the TPU compiler
        # lays a pred stack out with the round axis minor-most, so each
        # round would write one strided column across every tile.  A word
        # stack is laid out like the float stacks, round axis major.  The
        # host unpacks the words (``_host_outs``) before any result reads
        # them.
        def packed_body(carry, x):
            carry, out = body(carry, x)
            return carry, dict(out, sel=_pack_sel(out["sel"]))

        return packed_body, init_carry

    if scheduled:
        def scan(preds, y, costs, key, budget, sched):
            body, init_carry = build_body(preds, y, costs, budget)
            _, outs = jax.lax.scan(body, init_carry(key), sched, length=T,
                                   unroll=_SCAN_UNROLL)
            return outs
    else:
        def scan(preds, y, costs, key, budget):
            body, init_carry = build_body(preds, y, costs, budget)
            _, outs = jax.lax.scan(body, init_carry(key), None, length=T,
                                   unroll=_SCAN_UNROLL)
            return outs

    return scan


def _pack_sel(sel):
    """A round's ``(K,)`` bool transmit set as uint32 words: bit ``k % 32``
    of word ``k // 32`` holds ``sel[k]``.  One scalar word for ``K <= 32``,
    else a trailing axis of ``ceil(K / 32)`` words."""
    K = sel.shape[-1]
    n_words = -(-K // 32)
    bits = jnp.pad(sel.astype(jnp.uint32), (0, 32 * n_words - K))
    words = jnp.sum(bits.reshape(n_words, 32)
                    << jnp.arange(32, dtype=jnp.uint32),
                    axis=-1, dtype=jnp.uint32)
    return words[0] if n_words == 1 else words


def _unpack_sel(words, K: int) -> np.ndarray:
    """Host inverse of ``_pack_sel`` over stacked rounds: words
    ``(..., T)`` (``K <= 32``) or ``(..., T, ceil(K / 32))`` to the exact
    bool masks ``(..., T, K)``."""
    words = np.ascontiguousarray(words, dtype="<u4")
    if K <= 32:
        words = words[..., None]
    return np.unpackbits(words.view(np.uint8), axis=-1, count=K,
                         bitorder="little").view(bool)


def _host_outs(outs, K: int) -> dict:
    """The fetched per-round outputs with ``sel`` unpacked to bool masks:
    what every result built from them reads."""
    return dict(outs, sel=_unpack_sel(outs["sel"], K))


def _get_scan(algo: str, T: int, cfg: SimConfig, sweep: str = "",
              scheduled: bool = False):
    key = (algo, sweep, scheduled) + _cfg_key(cfg, T)
    fn = _SCAN_CACHE.get(key)
    if fn is not None:
        return fn
    scan = _make_scan(algo, T, cfg, scheduled=scheduled)
    if not scheduled:
        if sweep == "seeds":
            def fn(preds, y, costs, keys, budget):
                return jax.vmap(
                    lambda k: _sweep_outs(scan(preds, y, costs, k, budget))
                )(keys)
        elif sweep == "grid":
            def fn(preds, y, costs, keys, budgets):
                per_seed = jax.vmap(
                    lambda k, b: _sweep_outs(scan(preds, y, costs, k, b)),
                    in_axes=(0, None))
                return jax.vmap(per_seed, in_axes=(None, 0))(keys, budgets)
        elif sweep == "flat":
            # one independent (seed, budget) pair per lane, FULL per-round
            # outputs (ml_norm/dom_size kept) so every lane reconstructs a
            # complete SimResult — the serving layer's batch entry point
            def fn(preds, y, costs, keys, budgets):
                return jax.vmap(
                    lambda k, b: scan(preds, y, costs, k, b))(keys, budgets)
        else:
            fn = scan
    else:
        # scheduled variants vmap over a PER-LANE schedule stack (leading
        # lane axis on every ScheduleArrays leaf): lane i runs its own
        # scenario's rows, so one compiled program serves any mix of
        # scenarios of the shape — the serving batcher coalesces tenants
        # on different schedules into one bucket on the strength of this
        if sweep == "seeds":
            def fn(preds, y, costs, keys, budget, sched):
                return jax.vmap(
                    lambda k, s: _sweep_outs(
                        scan(preds, y, costs, k, budget, s)))(keys, sched)
        elif sweep == "grid":
            # sched is per-SEED (the inner axis): every budget row of the
            # grid re-uses lane i's schedule for seed i
            def fn(preds, y, costs, keys, budgets, sched):
                per_seed = jax.vmap(
                    lambda k, b, s: _sweep_outs(
                        scan(preds, y, costs, k, b, s)),
                    in_axes=(0, None, 0))
                return jax.vmap(per_seed,
                                in_axes=(None, 0, None))(keys, budgets,
                                                         sched)
        elif sweep == "flat":
            def fn(preds, y, costs, keys, budgets, sched):
                return jax.vmap(
                    lambda k, b, s: scan(preds, y, costs, k, b, s)
                )(keys, budgets, sched)
        else:
            fn = scan
    fn = _SCAN_CACHE[key] = jax.jit(fn)
    return fn


def _run_program(fn, *args):
    """Run a jitted program and bring its outputs to the host: the
    enqueue (``engine.launch``), the wait for the device
    (``engine.device_wait``) and the copy (``engine.fetch``)."""
    with obs.span("engine.launch"):
        outs = fn(*args)
    with obs.span("engine.device_wait"):
        jax.block_until_ready(outs)
    with obs.span("engine.fetch"):
        return jax.tree.map(np.asarray, outs)


def _sweep_outs(outs):
    outs = dict(outs)
    outs.pop("ml_norm")              # (T, K) per config: sweep keeps it lean
    outs.pop("dom_size")
    return outs


def _to_result(outs, T: int, budget, name: str) -> SimResult:
    """Host-side float64 metric reduction (identical to the reference's
    ``_Metrics``) over the scan's per-round outputs.  ``budget`` is a
    scalar or a (T,) *realized* budget schedule (base x scenario scale) —
    violations compare each round's cost against its round's budget."""
    ens_sq = np.asarray(outs["ens_sq_mean"], dtype=float)
    mse_curve = np.cumsum(ens_sq) / np.arange(1, T + 1)
    round_costs = np.asarray(outs["cost"], dtype=float)
    violations = int((round_costs > np.asarray(budget, dtype=float)
                      + 1e-6).sum())
    sel_masks = np.asarray(outs["sel"])
    tracker = RegretTracker.from_rounds(np.asarray(outs["ens_norm"]),
                                        np.asarray(outs["ml_norm"]))
    return SimResult(mse_curve, violations, violations / T, tracker,
                     sel_masks.sum(1), np.asarray(outs["dom_size"]),
                     round_costs, name, sel_masks)


def run_simulation_scan(algo: str, preds, y, costs, T: int,
                        cfg: SimConfig, scenario=None) -> SimResult:
    """Run ``T`` rounds of ``algo`` as one jitted ``lax.scan`` dispatch.

    Same arguments and result as ``run_simulation_reference`` — the
    trajectories (selection masks, costs, loss curves) are identical; only
    the wall-clock differs.

    ``scenario`` (a registered name, a ``repro.scenarios.Scenario``, or
    an already-``CompiledScenario``) runs the configuration under a
    non-stationary schedule: per-round budget factors, participation
    masks and label drift threaded through the scan as ``xs``.
    All-neutral schedules (the ``constant`` preset) dispatch the
    scenario-free program with identical arguments — bit-equal by
    construction; non-neutral schedules run the scheduled program family
    (see docs/scenarios.md#determinism).  ``budget_violations`` count
    against the *realized* per-round budget ``cfg.budget * scale[t]``.
    """
    preds = jnp.asarray(preds, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    costs = jnp.asarray(costs, jnp.float32)
    comp = _compile_scenario(scenario, T, cfg)
    if comp is None or comp.neutral:
        scan = _get_scan(algo, T, cfg)
        outs = scan(preds, y, costs, jax.random.PRNGKey(cfg.seed),
                    jnp.float32(cfg.budget))
        thresh = cfg.budget
    else:
        scan = _get_scan(algo, T, cfg, scheduled=True)
        outs = scan(preds, y, costs, jax.random.PRNGKey(cfg.seed),
                    jnp.float32(cfg.budget), comp.arrays)
        thresh = cfg.budget * comp.scale
    outs = _host_outs(jax.tree.map(np.asarray, outs), preds.shape[0])
    return _to_result(outs, T, thresh, algo)


def _get_sharded_flat(algo: str, T: int, cfg: SimConfig, mesh,
                      scheduled: bool = False):
    """Cached shard_map'd FLAT batch (full per-lane outs) for serving."""
    key = (algo, "flat", mesh, scheduled) + _cfg_key(cfg, T)
    fn = _SCAN_CACHE.get(key)
    if fn is None:
        scan = _make_scan(algo, T, cfg, scheduled=scheduled)
        fn = _SCAN_CACHE[key] = sweep_sharding.sharded_sweep_fn(
            scan, mesh, scheduled=scheduled)
    return fn


def run_batch(algo: str, preds, y, costs, T: int, cfg: SimConfig,
              seeds: Sequence[int],
              budgets: Optional[Sequence[float]] = None,
              mesh=None, scenario=None) -> list:
    """Run a flat batch of independent (seed, budget) configurations as
    ONE dispatch, returning one complete ``SimResult`` per configuration.

    This is the serving layer's entry point (``repro.serve``): unlike
    ``run_sweep``'s (budgets x seeds) grid, the batch axis is *flat* —
    lane ``i`` runs ``(seeds[i], budgets[i])`` — so heterogeneous
    requests coalesce into one program.  Unlike the sweep paths, every
    lane keeps its full per-round outputs (``ml_norm``, ``dom_size``),
    so each returned ``SimResult`` is as complete as a direct
    ``run_simulation_scan`` result.

    ``budgets`` is per-lane (same length as ``seeds``) or ``None`` for
    ``cfg.budget`` everywhere.  ``scenario`` is per-lane too: ONE
    scenario(-like) applies the same schedule to every lane, while a
    sequence (length ``n``, entries ``None`` / name / ``Scenario`` /
    ``CompiledScenario``) gives lane ``i`` its own schedule — compiled
    rows stack along the batch axis as ordinary jit arguments, so one
    scheduled program serves ANY mix of scenarios (the serving batcher
    coalesces tenants on different schedules into one bucket).  An
    all-neutral lane set dispatches the scenario-free program,
    bit-equal by construction; per-lane violations count against
    ``budgets[i] * scale[i, t]``.

    Execution: a single vmap over the batch axis, or — when
    ``cfg.sweep_sharded``/auto-dispatch says so AND every mesh shard
    gets at least two lanes — the same flat axis shard_map-partitioned
    over a pure-``sweep`` mesh (padded with copies of the last lane,
    sliced after).  The per-shard-width >= 2 guard keeps every lane in
    the *batched* program family so results are independent of the
    dispatch choice (see the ``SweepResult`` determinism note); an
    explicit ``mesh`` (or ``cfg.sweep_sharded=True``) forces sharding
    but raises rather than produce width-1 shards.  Meshes with a
    non-trivial ``data`` axis are rejected: a data axis changes the
    client-evaluation program and batch lanes would no longer match
    their vmapped bits.

    On the vmap path, EFL-FG batches with heterogeneous budgets are
    additionally *budget-compacted* (``batch_buckets``): lanes are
    regrouped into one dispatch per distinct budget (each of width
    >= 2), so a bucket's graph-builder loop runs only its own trip
    count instead of the whole batch's worst case.  Lane bits are
    unchanged — batched-family invariance again — and results are
    reassembled in lane order.

    Determinism: lane results are bit-equal to the same configuration
    embedded in any other batch of width >= 2 (and to the ``run_sweep``
    vmap path), and float32-close — NOT bit-equal — to a solo
    ``run_simulation_scan``.  A single-lane batch (n=1) is the one
    exception: a width-1 vmap compiles to the solo program, so it
    matches direct runs instead (the serving layer therefore never
    dispatches batched width 1 — it pads to 2).  Pinned by
    tests/test_serve.py; the full equality map is
    docs/serving.md#determinism.
    """
    seeds = list(seeds)
    n = len(seeds)
    if budgets is None:
        budgets = [cfg.budget] * n
    budgets = [float(b) for b in budgets]
    if len(budgets) != n:
        raise ValueError(f"run_batch: {n} seeds but {len(budgets)} budgets "
                         "— the batch axis is flat (one pair per lane)")
    with obs.span("engine.run_batch", items=n) as call:
        with obs.span("engine.prepare"):
            preds = jnp.asarray(preds, jnp.float32)
            y = jnp.asarray(y, jnp.float32)
            costs = jnp.asarray(costs, jnp.float32)
            keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
            budgets_j = jnp.asarray(budgets, jnp.float32)
            sched, scale = _lane_schedules(scenario, T, cfg, n)
            scheduled = sched is not None
            sharded, mesh = batch_dispatch_plan(cfg, n, mesh)
            if sharded:
                n_sweep, _ = sweep_sharding.mesh_axes(mesh)
                pk, pb = sweep_sharding.pad_configs(keys, budgets_j, n_sweep)
                fn = _get_sharded_flat(algo, T, cfg, mesh,
                                       scheduled=scheduled)
                args = (preds, y, costs, pk, pb)
                if scheduled:
                    args += (sweep_sharding.pad_lane_tree(sched, n_sweep),)
            else:
                fn = _get_scan(algo, T, cfg, sweep="flat",
                               scheduled=scheduled)
                buckets = batch_buckets(algo, budgets)
        if sharded:
            outs = jax.tree.map(lambda a: a[:n], _run_program(fn, *args))
        elif buckets is None:
            outs = _run_program(fn, preds, y, costs, keys, budgets_j,
                                *(() if sched is None else (sched,)))
        else:
            # budget-compacted dispatch: one flat program per budget
            # bucket, so each bucket's graph loop runs only ITS max trip
            # count instead of the whole batch's.  Every bucket has
            # width >= 2, so lane bits are unchanged (batched-family
            # invariance) — reassembly below restores lane order.  The
            # schedule stack is lane-sliced along with keys/budgets, so
            # each bucket carries exactly its lanes' rows.
            outs = None
            for idx in buckets:
                with obs.span("engine.prepare"):
                    sel = jnp.asarray(idx)
                    args = (preds, y, costs, keys[sel], budgets_j[sel])
                    if sched is not None:
                        args += (jax.tree.map(lambda a: a[sel], sched),)
                o = _run_program(fn, *args)
                with obs.span("engine.unpack"):
                    if outs is None:
                        outs = {k: np.empty((n,) + v.shape[1:], v.dtype)
                                for k, v in o.items()}
                    for k, v in o.items():
                        outs[k][idx] = v
        with obs.span("engine.unpack"):
            outs = _host_outs(outs, preds.shape[0])
            if algo == "eflfg":
                iters = outs["graph_iters"]
                _count_graph_steps(call, [iters] if sharded or buckets is None
                                   else [iters[idx] for idx in buckets])
            if scale is None:
                thresh = [budgets[i] for i in range(n)]
            elif scale.ndim == 1:       # one shared scenario: (T,) factors
                thresh = [budgets[i] * scale for i in range(n)]
            else:                       # per-lane scenarios: (n, T) factors
                thresh = [budgets[i] * scale[i] for i in range(n)]
            return [_to_result(jax.tree.map(lambda a: a[i], outs), T,
                               thresh[i], algo)
                    for i in range(n)]


def batch_dispatch_plan(cfg: SimConfig, n: int, mesh=None):
    """Resolve how a flat ``run_batch`` of ``n`` lanes will execute.

    Returns ``(sharded, mesh)`` — ``(False, None)`` for the single-device
    vmap, else ``(True, mesh)``.  Shared between ``run_batch`` and the
    serving layer's execution metadata so the reported dispatch can
    never drift from the actual one.  Rules (in order): an explicit
    ``mesh`` forces sharding (conflicting with
    ``cfg.sweep_sharded=False`` raises); ``cfg.sweep_sharded`` forces or
    disables it; otherwise auto-shard only when more than one device is
    visible AND every shard of the default sweep mesh gets at least two
    lanes.  Width-1 shards would execute the solo program family and
    make lane bits depend on the dispatch choice (see the
    ``SweepResult`` determinism note), so *forced* sharding that would
    produce them raises instead of complying.  Meshes with a non-trivial
    ``data`` axis are rejected: a data axis changes the
    client-evaluation program, so batch lanes would no longer match
    their vmapped bits.
    """
    sharded = cfg.sweep_sharded
    if mesh is not None:
        if sharded is False:
            raise ValueError("run_batch: mesh= requests the sharded path "
                             "but cfg.sweep_sharded=False disables it — "
                             "drop one")
        sharded = True
    if sharded is None:
        if jax.device_count() > 1:
            mesh = sweep_sharding.default_sweep_mesh()
            sharded = n >= 2 * sweep_sharding.mesh_axes(mesh)[0]
        else:
            sharded = False
    if not sharded:
        return False, None
    if mesh is None:
        mesh = sweep_sharding.default_sweep_mesh()
    n_sweep, n_data = sweep_sharding.mesh_axes(mesh)
    if n_data > 1:
        raise ValueError("run_batch: serving batches require a pure sweep "
                         "mesh (a data axis changes the client-evaluation "
                         f"program); got data axis size {n_data}")
    if -(-n // n_sweep) < 2 and n_sweep > 1:
        # forced sharding cannot be allowed to slip into width-1 shards:
        # a width-1 vmap compiles the SOLO program family, so the lanes'
        # bits would depend on the dispatch choice — the exact
        # load-dependence the batched-family guarantee rules out.
        raise ValueError(
            f"run_batch: {n} lanes over a {n_sweep}-shard sweep mesh "
            "gives width-1 shards, which execute the solo program family "
            "and break batch determinism (docs/serving.md#determinism) — "
            f"batch at least {2 * n_sweep} lanes, shrink the mesh, or "
            "drop the forced sharding")
    return True, mesh


def batch_buckets(algo: str, budgets: Sequence[float]):
    """Budget-compaction plan for a flat (vmapped) ``run_batch``.

    Returns a list of lane-index lists — one bucket per distinct budget,
    in ascending budget order — or ``None`` when the batch should stay a
    single dispatch.  Bucketing only pays when the round body contains a
    data-dependent loop whose trip count grows with the budget (EFL-FG's
    Algorithm-1 builder: bigger budgets append more nodes), so a batch
    mixing tight- and loose-budget lanes pays every round for the loosest
    lane's trips.  Splitting by budget lets each bucket's ``while_loop``
    stop at its OWN max.  ``None`` is returned when:

    * ``algo`` has no such loop (FedBoost), or
    * budgets are uniform (nothing to compact — and on uniform traffic
      the extra dispatches are pure overhead), or
    * any bucket would have width 1: a width-1 vmap compiles the SOLO
      program family, so the lane's bits would depend on its co-tenants'
      budgets — the exact load-dependence the batched-family guarantee
      (docs/serving.md#determinism) rules out.  Lone-budget lanes ride
      the single mixed dispatch instead, which is bit-identical.

    Exposed (rather than inlined in ``run_batch``) so the serving layer
    can report the compaction in its dispatch metadata.
    """
    if algo != "eflfg":
        return None
    groups: dict = {}
    for i, b in enumerate(budgets):
        groups.setdefault(float(b), []).append(i)
    if len(groups) < 2 or any(len(v) < 2 for v in groups.values()):
        return None
    return [groups[b] for b in sorted(groups)]


class SweepResult:
    """Stacked curves from a (possibly mesh-sharded) sweep.

    Leading axes of every per-round field are the sweep axes —
    ``(n_seeds, T)``, or ``(n_budgets, n_seeds, T)`` when a budget grid
    was given — regardless of which execution path produced it (the
    sharded path unpads and re-assembles into this exact layout, so
    callers never see the mesh).

    Fields (all host-side ``np.ndarray``):
      mse_curves:    (..., T) float64 — the paper's running-mean MSE_t,
                     reduced on host from the engine's per-round float32
                     ``ens_sq_mean`` outputs.
      regret_curves: (..., T) float64 view of the on-device float32
                     ``RegretCarry`` accumulation.
      sel_sizes:     (..., T) int — |S_t| per round.
      round_costs:   (..., T) float64 transmit cost per round.
      violations:    (...,) int — rounds with cost > the round's realized
                     budget + 1e-6 (``budget * budget_scale[t]`` when a
                     scenario schedule was applied, see ``budget_scale``).
      graph_iters:   (..., T) int32 — the graph builder's OWN productive
                     append-iteration count per round (zeros for
                     FedBoost); feeds ``lockstep_waste``.
      seeds:         (n_seeds,) as given; budgets: scalar or (n_budgets,).
      budget_scale:  scenario budget factors, float64: (T,) when one
                     shared scenario was swept, (n_seeds, T) when a
                     per-lane scenario sequence was given (lane i's
                     realized factors), None for a stationary sweep.
      sharded:       True when produced by ``run_sweep_sharded``.

    Determinism: a given (seed, budget) configuration's trajectory is a
    deterministic function of the inputs only — identical whichever
    *batched* sweep it is embedded in (any batch width >= 2, any
    co-resident configurations, vmapped or mesh-sharded; pinned by
    tests/test_sweep_sharding.py and tests/test_serve.py).  The 1-D
    sweep mesh is bit-equal to the vmap path; a 2-D data-axis mesh
    implies the *unfused* client evaluation and is bit-equal to the
    unfused vmap path (see docs/sweeps.md).

    Batched vs solo: the batched program is NOT bit-equal to a solo
    ``run_simulation_scan`` of the same configuration — XLA compiles the
    vmapped round body with different fusion boundaries than the
    unbatched one, and the resulting float32 rounding differences feed
    back through the exponential-weight updates.  Curves agree to
    float32 tolerance; discrete trajectories (selections) can differ at
    long horizons.  See docs/serving.md#determinism for the full
    equality map (the serving layer's exact mode exists precisely to
    recover solo bits under batched traffic).
    """

    # the per-config result arrays that define trajectory equality between
    # execution paths — the contract identical_fields (and through it the
    # sweep-sharding tests and bench bit-equality gates) compares
    FIELDS = ("mse_curves", "regret_curves", "sel_sizes", "round_costs",
              "violations", "graph_iters")

    def __init__(self, outs, seeds, budgets, T: int, sharded: bool = False,
                 budget_scale=None):
        ens_sq = np.asarray(outs["ens_sq_mean"], dtype=float)
        self.mse_curves = np.cumsum(ens_sq, -1) / np.arange(1, T + 1)
        self.regret_curves = np.asarray(outs["regret"], dtype=float)
        self.sel_sizes = np.asarray(outs["sel"]).sum(-1)
        self.round_costs = np.asarray(outs["cost"], dtype=float)
        self.graph_iters = np.asarray(outs["graph_iters"])
        b = np.asarray(budgets, dtype=float)
        bcast = b[:, None, None] if b.ndim else b
        thresh = bcast if budget_scale is None \
            else bcast * np.asarray(budget_scale, dtype=float)
        self.violations = (self.round_costs > thresh + 1e-6).sum(-1)
        self.seeds = np.asarray(seeds)
        self.budgets = b
        self.budget_scale = (None if budget_scale is None
                             else np.asarray(budget_scale, dtype=float))
        self.sharded = sharded

    @property
    def final_mse(self) -> np.ndarray:
        return self.mse_curves[..., -1]

    @property
    def lockstep_waste(self) -> int:
        """Graph-builder append-iterations co-resident lanes idled through
        after their own convergence: ``sum over rounds and lanes of
        (max-over-lanes iters - own iters)``.

        Under ``vmap`` the builder's ``while_loop`` trip count is the
        maximum over the batched lanes each round, so every lane pays for
        the slowest one — the documented lockstep-batching limitation
        (docs/architecture.md#known-limitations), now measurable.  Exact
        for the vmapped sweep (one lockstep program over all lanes); for
        a mesh-sharded sweep it reports the would-be waste of the
        equivalent vmap dispatch (lockstep is per shard there).  Zero for
        FedBoost sweeps (no graph) and single-lane sweeps.
        """
        appends, trips = _graph_steps(self.graph_iters)
        return trips - appends

    def identical_fields(self, other: "SweepResult") -> dict:
        """Per-field exact-equality map vs another sweep's results."""
        return {f: bool(np.array_equal(getattr(self, f), getattr(other, f)))
                for f in self.FIELDS}

    def identical_to(self, other: "SweepResult") -> bool:
        """True iff every ``FIELDS`` array matches ``other`` bit-for-bit."""
        return all(self.identical_fields(other).values())


def _graph_steps(iters) -> tuple:
    """``(appends, trips)`` of one lockstep group from its stacked
    ``graph_iters`` (lanes..., T): the lanes' own productive append
    steps, and the steps the group ran — each round as many as its
    slowest lane, in every lane."""
    it = np.asarray(iters).reshape(-1, iters.shape[-1]).astype(np.int64)
    return int(it.sum()), int(it.max(axis=0).sum()) * it.shape[0]


def _count_graph_steps(call, groups) -> None:
    """Record the graph builder's lockstep on the call's span:
    ``<call>.graph_appends`` and ``<call>.graph_trips`` over the lockstep
    groups ``groups`` (stacked ``graph_iters``, one array per group)."""
    steps = [_graph_steps(g) for g in groups]
    call.count("graph_appends", sum(a for a, _ in steps))
    call.count("graph_trips", sum(t for _, t in steps))


def _flatten_configs(keys, budgets, default_budget):
    """Flatten a (seeds x budgets) grid into the flat config axis the
    sharded path partitions: budgets outermost (row-major), matching the
    vmap path's ``(n_budgets, n_seeds, ...)`` output layout.  Returns
    ``(flat_keys, flat_budgets, grid_shape|None, budgets_arr)``."""
    n_seeds = keys.shape[0]
    if budgets is None:
        flat_budgets = jnp.full((n_seeds,), jnp.float32(default_budget))
        return keys, flat_budgets, None, np.float64(default_budget)
    budgets_j = jnp.asarray(list(budgets), jnp.float32)
    n_b = budgets_j.shape[0]
    flat_keys = jnp.tile(keys, (n_b, 1))
    flat_budgets = jnp.repeat(budgets_j, n_seeds)
    return flat_keys, flat_budgets, (n_b, n_seeds), np.asarray(budgets_j)


def _get_sharded_sweep(algo: str, T: int, cfg: SimConfig, mesh,
                       scheduled: bool = False):
    """Cached shard_map'd flat sweep for (algo, cfg, T, mesh)."""
    key = (algo, mesh, scheduled) + _cfg_key(cfg, T)
    fn = _SCAN_CACHE.get(key)
    if fn is None:
        _, n_data = sweep_sharding.mesh_axes(mesh)
        data_axis = ((sweep_sharding.DATA_AXIS, n_data)
                     if n_data > 1 else None)
        scan = _make_scan(algo, T, cfg, data_axis=data_axis,
                          scheduled=scheduled)
        if scheduled:
            per_config = lambda p, y, c, k, b, s: _sweep_outs(
                scan(p, y, c, k, b, s))
        else:
            per_config = lambda p, y, c, k, b: _sweep_outs(
                scan(p, y, c, k, b))
        fn = _SCAN_CACHE[key] = sweep_sharding.sharded_sweep_fn(
            per_config, mesh, scheduled=scheduled)
    return fn


def run_sweep_sharded(algo: str, preds, y, costs, T: int, cfg: SimConfig,
                      seeds: Sequence[int],
                      budgets: Optional[Sequence[float]] = None,
                      mesh=None, scenario=None) -> SweepResult:
    """Run a sweep with the flat (seeds x budgets) axis sharded over a
    device mesh.

    Same arguments and ``SweepResult`` as ``run_sweep`` (including the
    optional ``scenario`` — one shared schedule or a per-seed-lane
    sequence, stacked and partitioned over the mesh alongside
    keys/budgets) plus an
    optional ``mesh`` (default: every visible device as a pure
    ``("sweep",)`` partition via ``launch.mesh.make_sweep_mesh``).  Each device vmaps
    the identical per-config scan over its shard of the flat axis; sweeps
    that don't divide the mesh are padded with copies of the last config
    and unpadded after the gather (``sweep_sharding.pad_configs``), so
    any sweep size works on any mesh.  A mesh with a non-trivial
    ``"data"`` axis additionally distributes each round's client window
    inside every scan (``sharded.sharded_window_eval``'s psum).

    Determinism: on a 1-D sweep mesh, trajectories are bit-equal to the
    single-device ``run_sweep`` vmap; a non-trivial data axis (divisible
    window) uses the unfused all-gather evaluation and is bit-equal to
    the *unfused* vmap path — the only residual difference vs the
    default path is the fused-vs-unfused kernel choice, not reduction
    order.  Both pinned by tests/test_sweep_sharding.py.
    """
    seeds = list(seeds)
    n_cfg = len(seeds) * (1 if budgets is None else len(budgets))
    with obs.span("engine.run_sweep", items=n_cfg) as call:
        with obs.span("engine.prepare"):
            preds = jnp.asarray(preds, jnp.float32)
            y = jnp.asarray(y, jnp.float32)
            costs = jnp.asarray(costs, jnp.float32)
            keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
            sched, scale = _lane_schedules(scenario, T, cfg, len(seeds))
            scheduled = sched is not None
            if mesh is None:
                mesh = sweep_sharding.default_sweep_mesh()
            n_sweep, _ = sweep_sharding.mesh_axes(mesh)
            flat_keys, flat_budgets, grid_shape, budgets_arr = \
                _flatten_configs(keys, budgets, cfg.budget)
            n_cfg = flat_keys.shape[0]
            flat_keys, flat_budgets = sweep_sharding.pad_configs(
                flat_keys, flat_budgets, n_sweep)
            fn = _get_sharded_sweep(algo, T, cfg, mesh, scheduled=scheduled)
            args = (preds, y, costs, flat_keys, flat_budgets)
            if scheduled:
                if grid_shape is not None:
                    # the flat config axis is budgets-outermost: tile
                    # each seed lane's schedule rows once per budget row,
                    # matching _flatten_configs' layout
                    sched = jax.tree.map(
                        lambda a: jnp.tile(a, (grid_shape[0],)
                                           + (1,) * (a.ndim - 1)), sched)
                args += (sweep_sharding.pad_lane_tree(sched, n_sweep),)
        outs = _run_program(fn, *args)
        with obs.span("engine.unpack"):
            outs = _host_outs(jax.tree.map(lambda a: a[:n_cfg], outs),
                              preds.shape[0])
            if grid_shape is not None:
                outs = jax.tree.map(
                    lambda a: a.reshape(grid_shape + a.shape[1:]), outs)
            res = SweepResult(outs, seeds, budgets_arr, T, sharded=True,
                              budget_scale=scale)
            if algo == "eflfg":
                _count_graph_steps(call, [res.graph_iters])
            return res


def _dispatch_sharded(cfg: SimConfig, n_cfg: int) -> bool:
    """``run_sweep`` auto-dispatch: shard when the config asks for it, or
    (by default) when >1 device is visible and there is >1 config."""
    if cfg.sweep_sharded is not None:
        return cfg.sweep_sharded
    return jax.device_count() > 1 and n_cfg > 1


def run_sweep(algo: str, preds, y, costs, T: int, cfg: SimConfig,
              seeds: Sequence[int],
              budgets: Optional[Sequence[float]] = None,
              mesh=None, scenario=None) -> SweepResult:
    """Run every (budget, seed) configuration as one compiled program.

    ``preds`` (K, n_stream) / ``y`` (n_stream,) / ``costs`` (K,) are the
    precomputed expert stream; ``seeds`` (and optionally ``budgets``)
    define the grid.  Returns a ``SweepResult`` whose leading axes are
    ``(n_seeds,)`` or ``(n_budgets, n_seeds)`` — see its docstring for
    field shapes.  Per-round (T, K) loss matrices are never materialized
    per configuration; regret accumulates on device via ``RegretCarry``.

    ``scenario`` (``repro.scenarios``) applies ONE non-stationary
    schedule to every grid point, or — as a sequence of length
    ``len(seeds)`` (entries ``None`` / name / ``Scenario``) — a
    *per-lane* schedule: seed lane ``i`` runs its own compiled rows,
    stacked along the batch axis as jit arguments, shared across the
    budget axis of a grid.  The per-round budget factor multiplies each
    lane's base budget, so a budget grid under ``step_decay`` sweeps
    the *starting* provision.  All-neutral lane sets dispatch the
    scenario-free program (bit-equal by construction); ``violations``
    always count against the realized per-round budgets.

    Execution: on a single device the scan is vmapped over the grid; with
    more than one visible device the flat configuration axis is sharded
    over the mesh instead (``run_sweep_sharded`` — same results, padding
    handled internally).  ``cfg.sweep_sharded`` forces (True) or disables
    (False) the sharded path; passing ``mesh`` explicitly also forces it
    (a requested partition is never silently ignored — conflicting with
    ``sweep_sharded=False`` raises).
    """
    seeds = list(seeds)
    budgets = None if budgets is None else list(budgets)
    n_cfg = len(seeds) * (len(budgets) if budgets is not None else 1)
    if mesh is not None and cfg.sweep_sharded is False:
        raise ValueError("run_sweep: mesh= requests the sharded path but "
                         "cfg.sweep_sharded=False disables it — drop one")
    if mesh is not None or _dispatch_sharded(cfg, n_cfg):
        return run_sweep_sharded(algo, preds, y, costs, T, cfg, seeds,
                                 budgets, mesh=mesh, scenario=scenario)
    with obs.span("engine.run_sweep", items=n_cfg) as call:
        with obs.span("engine.prepare"):
            preds = jnp.asarray(preds, jnp.float32)
            y = jnp.asarray(y, jnp.float32)
            costs = jnp.asarray(costs, jnp.float32)
            keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
            sched, scale = _lane_schedules(scenario, T, cfg, len(seeds))
            scheduled = sched is not None
            if budgets is None:
                fn = _get_scan(algo, T, cfg, sweep="seeds",
                               scheduled=scheduled)
                args = (preds, y, costs, keys, jnp.float32(cfg.budget))
                budgets_arr = np.float64(cfg.budget)
            else:
                budgets_j = jnp.asarray(budgets, jnp.float32)
                fn = _get_scan(algo, T, cfg, sweep="grid",
                               scheduled=scheduled)
                args = (preds, y, costs, keys, budgets_j)
                budgets_arr = np.asarray(budgets_j)
            if scheduled:
                args += (sched,)
        outs = _run_program(fn, *args)
        with obs.span("engine.unpack"):
            res = SweepResult(_host_outs(outs, preds.shape[0]), seeds,
                              budgets_arr, T, budget_scale=scale)
            if algo == "eflfg":
                _count_graph_steps(call, [res.graph_iters])
            return res
