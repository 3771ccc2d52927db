#!/usr/bin/env python3
"""Bring-up smoke run of the EFL-FG system on a TPU, at the paper's full
``energy`` configuration.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: the mesh-sharded sweep
                                     # and its one-chip comparison only

Configuration: the ``energy`` surrogate (19,735 x 27 rows, generated from
a fixed seed), 10 % pre-training split, the full 22-expert pool with
uncapped anchors, 100 clients, 5 per round, budget B = 3, T = 3000.

One-chip phases, in one process, one line each:

* ``device``  platform, kind and count as JAX reports them;
* ``pool``    fit + predict at full width, with four kernel experts and
              both MLPs checked against float64 NumPy (and the same
              float32 code on the host CPU) on a stream slice;
* ``kernels`` the compiled ``client_eval`` / ``server_plan`` /
              ``server_update`` kernels against their ``ref.py`` on real
              round inputs;
* ``solo``    ``run_simulation`` for EFL-FG and FedBoost with the default
              ``SimConfig`` (compiled fused client eval), and EFL-FG with
              the unfused client eval for comparison;
* ``fused_server``  EFL-FG with ``use_fused_server=True``;
* ``batch`` / ``sweep``  ``run_batch`` over 16 seeds and ``run_sweep``
              over 4 budgets x 4 seeds, both algorithms;
* ``scenario``  one scheduled run (``bursty_outage``);
* ``served``  an in-process ``SimServer`` answering 32 mixed requests,
              one lane checked against ``run_sweep`` and one exact
              request against the solo run.

Timings are host wall clock around calls whose results are on the host
(or ended in ``block_until_ready``); ``compile_s`` is the first call
minus the warm one.  Any failed check raises: the script then exits
non-zero and prints no result line.  It also fails when JAX finds no TPU.
The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

T = 3000                 # PAPER_EFL.rounds["energy"]
SEED = 0                 # data, weights and every run derive from it
KERNEL_RTOL = 1e-5       # compiled kernel vs ref.py (float32 outputs)
KERNEL_ATOL = 1e-5
POOL_TOL = 3e-5          # expert predictions vs float64, scaled (phase_pool)
CHECKED_EXPERTS = ("gaussian[0.1]", "laplacian[1.0]", "poly[3]",
                   "sigmoid[0.1]", "mlp[1x25]", "mlp[2x25]")


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(phase: str, **fields) -> None:
    parts = []
    for k, v in fields.items():
        parts.append(f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}")
    print(f"[{phase}] " + " ".join(parts), flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# setup: data and expert pool
# ---------------------------------------------------------------------------

def build_stream(jax):
    """Fit the pool and predict the stream once: ``(pool, x_pre, y_pre,
    x_stream, y_stream, preds, fit_s, predict_s)``, times of these first
    (compiling) calls."""
    from repro.configs import PAPER_EFL
    from repro.data import make_dataset, pretrain_split
    from repro.experts import build_paper_pool, pool_predict_all
    ds = make_dataset("energy", seed=SEED)
    (xp, yp), (xs, ys) = pretrain_split(ds, frac=PAPER_EFL.pretrain_frac,
                                        seed=SEED)
    pool, fit_s = timed(lambda: jax.block_until_ready(
        build_paper_pool(xp, yp, seed=SEED)))
    preds, predict_s = timed(
        lambda: pool_predict_all(pool, xs).block_until_ready())
    return pool, xp, yp, xs, ys, preds, fit_s, predict_s


def _np_terms(e, x):
    """Float64 NumPy forward pass of one expert on ``x``: the prediction
    and the summed magnitude of the last contraction's terms."""
    import numpy as np
    from repro.experts import KernelExpert
    f64 = lambda a: np.asarray(a, np.float64)
    if isinstance(e, KernelExpert):
        a = f64(e.anchors)
        if e.kind == "gaussian":
            k = np.exp(-e.param * ((x[:, None, :] - a[None]) ** 2).sum(-1))
        elif e.kind == "laplacian":
            k = np.exp(-e.param * np.abs(x[:, None, :] - a[None]).sum(-1))
        elif e.kind == "polynomial":
            k = (x @ a.T + 1.0) ** e.param
        else:
            k = np.tanh(e.param * (x @ a.T) + 1.0)
        return k @ f64(e.alpha), np.abs(k) @ np.abs(f64(e.alpha))
    h = x
    for w, b in e.params[:-1]:
        h = np.maximum(h @ f64(w) + f64(b), 0.0)
    w, b = (f64(v) for v in e.params[-1])
    return (h @ w + b)[:, 0], (np.abs(h) @ np.abs(w) + np.abs(b))[:, 0]


def phase_pool(jax, stream):
    import numpy as np
    from repro.experts import build_paper_pool, pool_predict_all
    pool, xp, yp, xs, ys, preds, fit_cold, pred_cold = stream
    _, fit_warm = timed(lambda: jax.block_until_ready(
        build_paper_pool(xp, yp, seed=SEED)))
    _, pred_warm = timed(
        lambda: pool_predict_all(pool, xs).block_until_ready())
    check(preds.shape == (22, xs.shape[0]), f"preds shape {preds.shape}")
    check(bool(np.isfinite(np.asarray(preds)).all()), "non-finite preds")

    # the pool's unclipped predictions vs float64 NumPy on a slice, with
    # the same float32 code on the host CPU as the yardstick.  The error is
    # scaled by the summed magnitude of the last contraction's terms:
    # float32 rounding stays near 1e-6 of it, while bfloat16 operand
    # rounding (the TPU's default matmul precision) reaches 1e-4 and more
    cpu = jax.devices("cpu")[0]
    xsl = xs[:256]
    rows = [pool.names.index(n) for n in CHECKED_EXPERTS]
    dev = np.asarray(pool_predict_all(pool, xsl, clip=None))[rows]
    to_host = lambda a: np.asarray(a) if isinstance(a, jax.Array) else a
    with jax.default_device(cpu):
        host = np.asarray(pool_predict_all(jax.tree.map(to_host, pool), xsl,
                                           clip=None))[rows]
    err_dev = err_cpu = 0.0
    for i, r in enumerate(rows):
        ref, scale = _np_terms(pool.experts[r], xsl.astype(np.float64))
        err_dev = max(err_dev, float((np.abs(dev[i] - ref) / scale).max()))
        err_cpu = max(err_cpu, float((np.abs(host[i] - ref) / scale).max()))
    check(err_dev <= POOL_TOL,
          f"expert predictions off float64 by {err_dev:.3g} (scaled) on "
          f"the chip, {err_cpu:.3g} for float32 on the CPU")
    say("pool", experts=len(pool.experts), stream=xs.shape[0],
        anchors=int(pool.experts[0].anchors.shape[0]),
        fit_cold_s=fit_cold, fit_warm_s=fit_warm,
        predict_cold_s=pred_cold, predict_warm_s=pred_warm,
        scaled_err_vs_f64=err_dev, cpu_f32_scaled_err_vs_f64=err_cpu,
        tol=POOL_TOL)


# ---------------------------------------------------------------------------
# kernels vs ref.py on real round inputs
# ---------------------------------------------------------------------------

def _close(name, got, want, exact=False):
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    if exact:
        check(np.array_equal(got, want), f"{name}: {got} != {want}")
        return 0.0
    check(np.allclose(got, want, rtol=KERNEL_RTOL, atol=KERNEL_ATOL),
          f"{name}: max diff {np.abs(got - want).max():.3g}")
    return float(np.abs(got.astype(float) - want.astype(float)).max())


def phase_kernels(jax, preds, y, costs):
    import numpy as np
    import jax.numpy as jnp
    from repro.federated import SimConfig
    from repro.federated.simulation import make_round_body
    from repro.kernels.client_eval import ops as ce_ops, ref as ce_ref
    from repro.kernels.server_round import ops as sr_ops, ref as sr_ref

    cfg = SimConfig(seed=SEED, use_fused=False)
    eta, xi = (jnp.float32(r) for r in cfg.rates(T))
    budget = jnp.float32(cfg.budget)
    K, n_stream = preds.shape

    @jax.jit
    def carries(preds, y, costs, key):
        body, init = make_round_body("eflfg", preds, y, costs, cfg, budget,
                                     eta, xi)
        return jax.lax.scan(lambda c, _: (body(c, None)[0], c), init(key),
                            None, length=64)[1]

    states, _, (cursors, _) = carries(preds, y, costs,
                                      jax.random.PRNGKey(SEED))
    ext = {W: ce_ops.extend_stream(preds, y, W) for W in (5, 100)}
    worst = 0.0
    n_checked = 0
    rng = np.random.default_rng(SEED)
    for r in (0, 1, 7, 31, 63):
        st = jax.tree.map(lambda a: a[r], states)
        gumbel = jax.random.gumbel(jax.random.PRNGKey(1000 + r), (K,))
        plan = sr_ops.server_plan(st.log_w, st.log_u, st.prev_adj,
                                  costs, budget, gumbel, xi)
        plan_ref = sr_ref.server_plan_ref(st.log_w, st.log_u, st.prev_adj,
                                          costs, budget, gumbel, xi)
        for f in ("adj", "dom", "drawn", "sel", "graph_iters"):
            _close(f"server_plan.{f} r{r}", getattr(plan, f),
                   getattr(plan_ref, f), exact=True)
        for f in ("p", "mix", "round_cost"):
            worst = max(worst, _close(f"server_plan.{f} r{r}",
                                      getattr(plan, f), getattr(plan_ref, f)))
        # real cursor of this round, plus aligned, unaligned and wrapping
        # positions for the compiled kernel's lane frame
        for cursor in (int(cursors[r]), 0, 127, 128, 5000, n_stream - 3,
                       n_stream - 1):
            for W, n_t, with_grad in ((5, 5, False), (5, 5, True),
                                      (100, 37, False)):
                pe, ye = ext[W]
                args = (pe, ye, jnp.int32(cursor), jnp.int32(n_t),
                        plan_ref.mix)
                kw = dict(loss_scale=cfg.loss_scale, window=W,
                          with_grad=with_grad)
                sched = dict(
                    active=jnp.asarray(rng.random(W) < 0.7).at[0].set(True),
                    shift=jnp.float32(0.25))
                for extra in ({}, sched):
                    out = ce_ops.client_eval(*args, **kw, **extra)
                    want = ce_ref.client_eval_ref(*args, cfg.loss_scale, W,
                                                  **extra)
                    for f in out._fields:
                        if getattr(out, f) is None:
                            continue
                        worst = max(worst, _close(
                            f"client_eval.{f} r{r} c{cursor} W{W}",
                            getattr(out, f), getattr(want, f)))
                    n_checked += 1
        ev = ce_ref.client_eval_ref(*ext[5], cursors[r], jnp.int32(5),
                                    plan_ref.mix, cfg.loss_scale, 5)
        upd_args = (plan_ref.adj, plan_ref.p, plan_ref.sel, plan_ref.drawn,
                    ev.model_losses, ev.ens_norm, st.log_w, st.log_u, eta)
        upd = sr_ops.server_update(*upd_args)
        upd_ref = sr_ref.server_update_ref(*upd_args)
        for f in upd._fields:
            worst = max(worst, _close(f"server_update.{f} r{r}",
                                      getattr(upd, f), getattr(upd_ref, f)))
    say("kernels", rounds=5, client_eval_calls=n_checked,
        max_abs_diff=worst, rtol=KERNEL_RTOL, atol=KERNEL_ATOL)


# ---------------------------------------------------------------------------
# engine paths
# ---------------------------------------------------------------------------

def _assert_compiled_kernels(jax, algo, cfg, preds, y, costs):
    from repro.federated import engine
    scan = engine._get_scan(algo, T, cfg)
    text = scan.lower(preds, y, costs, jax.random.PRNGKey(cfg.seed),
                      jax.numpy.float32(cfg.budget)).as_text()
    n = text.count("tpu_custom_call")
    check(n > 0, f"{algo}: no tpu_custom_call in the fused scan")
    return n


def _first_diff(a, b):
    import numpy as np
    rows = np.nonzero(np.any(a != b, axis=tuple(range(1, a.ndim))))[0]
    return int(rows[0]) if rows.size else None


def phase_solo(jax, preds, y, costs):
    import numpy as np
    from repro.federated import SimConfig, run_simulation
    cfg = SimConfig(seed=SEED)
    out = {}
    for algo in ("eflfg", "fedboost"):
        run = lambda: run_simulation(algo, preds, y, costs, T=T, cfg=cfg)
        res, cold = timed(run)
        again, warm = timed(run)
        check(again.identical_to(res), f"{algo}: re-run not bit-equal")
        check(bool(np.isfinite(res.mse_curve).all()), f"{algo}: non-finite")
        if algo == "eflfg":
            check(res.budget_violations == 0,
                  f"eflfg: {res.budget_violations} budget violations")
        kernels = _assert_compiled_kernels(jax, algo, cfg, preds, y, costs)
        say(f"solo.{algo}", MSE_T=res.final_mse,
            violation_share=res.violation_frac,
            mean_sel=float(res.sel_sizes.mean()), compile_s=cold - warm,
            warm_s=warm, tpu_custom_calls=kernels)
        out[algo] = res
    unf = run_simulation("eflfg", preds, y, costs, T=T,
                         cfg=replace(cfg, use_fused=False))
    rel = abs(unf.final_mse - out["eflfg"].final_mse) / unf.final_mse
    check(unf.budget_violations == 0 and rel < 0.1,
          f"unfused eflfg: {unf.budget_violations} violations, "
          f"MSE_T off the fused run by {rel:.3g}")
    say("solo.eflfg_unfused", MSE_T=unf.final_mse, rel_diff_vs_fused=rel,
        first_sel_diff_round=_first_diff(unf.sel_masks,
                                         out["eflfg"].sel_masks))
    return out


def phase_fused_server(jax, preds, y, costs, solo):
    from repro.federated import SimConfig, run_simulation
    cfg = SimConfig(seed=SEED, use_fused_server=True)
    run = lambda: run_simulation("eflfg", preds, y, costs, T=T, cfg=cfg)
    res, cold = timed(run)
    _, warm = timed(run)
    kernels = _assert_compiled_kernels(jax, "eflfg", cfg, preds, y, costs)
    check(kernels >= 3, f"fused server scan has {kernels} kernels, "
          "expected client_eval + server_plan + server_update")
    base = solo["eflfg"].final_mse
    rel = abs(res.final_mse - base) / base
    check(res.budget_violations == 0 and rel < 0.1,
          f"fused server: {res.budget_violations} violations, MSE_T off "
          f"the unfused-server run by {rel:.3g}")
    say("fused_server", MSE_T=res.final_mse, violation_share=res.violation_frac,
        mean_sel=float(res.sel_sizes.mean()), compile_s=cold - warm,
        warm_s=warm, tpu_custom_calls=kernels, rel_diff_vs_solo=rel,
        first_sel_diff_round=_first_diff(res.sel_masks,
                                         solo["eflfg"].sel_masks))


def phase_batched(jax, preds, y, costs):
    import numpy as np
    from repro.federated import SimConfig, run_batch, run_sweep
    cfg = SimConfig(seed=SEED)
    seeds = list(range(SEED, SEED + 16))
    for algo in ("eflfg", "fedboost"):
        run = lambda: run_batch(algo, preds, y, costs, T=T, cfg=cfg,
                                seeds=seeds)
        res, cold = timed(run)
        _, warm = timed(run)
        mse = np.array([r.final_mse for r in res])
        viol = sum(r.budget_violations for r in res)
        check(bool(np.isfinite(mse).all()), f"batch {algo}: non-finite")
        check(algo != "eflfg" or viol == 0, f"batch eflfg: {viol} violations")
        say(f"batch.{algo}", lanes=len(res), mean_MSE_T=float(mse.mean()),
            violations=viol, compile_s=cold - warm, warm_s=warm)
    budgets = [2.0, 3.0, 4.0, 5.0]
    for algo in ("eflfg", "fedboost"):
        run = lambda: run_sweep(algo, preds, y, costs, T=T, cfg=cfg,
                                seeds=seeds[:4], budgets=budgets)
        sw, cold = timed(run)
        _, warm = timed(run)
        check(sw.final_mse.shape == (4, 4), f"sweep {algo}: shape")
        check(bool(np.isfinite(sw.final_mse).all()),
              f"sweep {algo}: non-finite")
        check(algo != "eflfg" or int(sw.violations.sum()) == 0,
              f"sweep eflfg: {int(sw.violations.sum())} violations")
        say(f"sweep.{algo}", configs=16, mean_MSE_T=float(sw.final_mse.mean()),
            violations=int(sw.violations.sum()), compile_s=cold - warm,
            warm_s=warm)


def phase_scenario(jax, preds, y, costs):
    import numpy as np
    from repro.federated import SimConfig, run_simulation
    from repro.scenarios import resolve
    cfg = SimConfig(seed=SEED)
    run = lambda: run_simulation("eflfg", preds, y, costs, T=T, cfg=cfg,
                                 scenario="bursty_outage")
    res, cold = timed(run)
    _, warm = timed(run)
    check(bool(np.isfinite(res.mse_curve).all()), "scenario: non-finite")
    # outages cut the budget below the cheapest expert, so the mandatory
    # self-loop transmit violates it; EFL-FG's guarantee is that nothing
    # else does: every violation is an outage round with |S_t| = 1
    scale = resolve("bursty_outage").compile(T, cfg).scale
    viol = res.round_costs > cfg.budget * scale + 1e-6
    check(bool(np.all(scale[viol] < 1) and np.all(res.sel_sizes[viol] == 1)),
          "scenario eflfg: a violation outside an outage round, or with "
          "more than the drawn model")
    say("scenario.bursty_outage", MSE_T=res.final_mse,
        violation_share=res.violation_frac,
        outage_rounds=int((scale < 1).sum()),
        mean_sel=float(res.sel_sizes.mean()), compile_s=cold - warm,
        warm_s=warm)


def phase_served(jax, preds, y, costs, solo):
    from repro.federated import SimConfig, run_sweep
    from repro.serve import SimServer
    cfg = SimConfig(seed=SEED)
    specs = []
    for i in range(32):
        algo = ("eflfg", "fedboost")[i % 2]
        specs.append(dict(algo=algo, seed=SEED + i // 2, T=T,
                          budget=(3.0, 2.5)[(i // 2) % 2], cfg=cfg))
    specs[-1] = dict(algo="eflfg", seed=SEED, T=T, budget=3.0, cfg=cfg,
                     exact=True)
    t0 = time.perf_counter()
    with SimServer(max_batch=16, max_wait_ms=5.0) as server:
        server.register_stream("energy", preds, y, costs)
        futs = [server.submit(stream="energy", **s) for s in specs]
        results = [f.result(1200) for f in futs]
        stats = server.stats()
    wall = time.perf_counter() - t0
    check(stats["served"] == 32 and stats["failed"] == 0, f"served {stats}")
    # served batched lanes == the run_sweep lanes (served_equals_sweep):
    # the EFL-FG requests at budget 3.0 are seeds seed, seed+2, ...
    lanes = [i for i, s in enumerate(specs[:-1])
             if s["algo"] == "eflfg" and s["budget"] == 3.0]
    sw = run_sweep("eflfg", preds, y, costs, T, replace(cfg, sweep_sharded=False),
                   seeds=[specs[i]["seed"] for i in lanes])
    equal = all(results[i].identical_to_sweep_lane(sw, j)
                for j, i in enumerate(lanes))
    check(equal, "served batched lanes differ from the run_sweep lanes")
    exact_equal = results[-1].identical_to(solo["eflfg"])
    check(exact_equal, "served exact request differs from the solo run")
    say("served", requests=32, wall_s=wall, batches=stats["batches"],
        served_equals_sweep=equal, exact_equals_solo=exact_equal)


# ---------------------------------------------------------------------------
# four chips: the mesh-sharded sweep against the one-chip vmap
# ---------------------------------------------------------------------------

def phase_mesh(jax, preds, y, costs):
    import numpy as np
    import jax.numpy as jnp
    from repro.federated import SimConfig, engine, run_sweep
    from repro.launch.mesh import make_sweep_mesh
    preds = np.asarray(preds)          # uncommitted: replicated per mesh
    seeds, budgets = list(range(SEED, SEED + 4)), [2.0, 3.0, 4.0, 5.0]
    cases = (
        # (label, mesh, cfg of the sharded run, cfg of the one-chip vmap)
        ("sweep4", make_sweep_mesh(), SimConfig(seed=SEED),
         SimConfig(seed=SEED, sweep_sharded=False)),
        # W=5 does not divide the data axis: every device evaluates the
        # full window with the default client eval
        ("sweep2xdata2", make_sweep_mesh(2), SimConfig(seed=SEED),
         SimConfig(seed=SEED, sweep_sharded=False)),
        # the bandwidth formula's W=100 does: data-parallel unfused eval
        ("sweep2xdata2_w100", make_sweep_mesh(2),
         SimConfig(seed=SEED, uplink_bandwidth=40.0),
         SimConfig(seed=SEED, uplink_bandwidth=40.0, use_fused=False,
                   sweep_sharded=False)),
    )
    for label, mesh, cfg_mesh, cfg_one in cases:
        for algo in ("eflfg", "fedboost"):
            sharded, t_mesh = timed(lambda: run_sweep(
                algo, preds, y, costs, T=T, cfg=cfg_mesh, seeds=seeds,
                budgets=budgets, mesh=mesh))
            with jax.default_device(jax.devices()[0]):
                one, t_one = timed(lambda: run_sweep(
                    algo, preds, y, costs, T=T, cfg=cfg_one, seeds=seeds,
                    budgets=budgets))
            fields = sharded.identical_fields(one)
            first = _first_diff(
                np.asarray(sharded.sel_sizes).reshape(16, T).T,
                np.asarray(one.sel_sizes).reshape(16, T).T)
            unequal = ",".join(k for k, v in fields.items() if not v)
            check(not unequal,
                  f"{label} {algo}: sharded sweep differs from one chip in "
                  f"{unequal} (first sel-size diff round {first})")
            check(bool(np.isfinite(sharded.final_mse).all()),
                  f"{label} {algo}: non-finite")
            check(algo != "eflfg" or int(sharded.violations.sum()) == 0,
                  f"{label} eflfg: violations")
            # where the outputs live: one shard of the config axis per chip
            fn = engine._get_sharded_sweep(algo, T, cfg_mesh, mesh)
            keys = jnp.stack([jax.random.PRNGKey(s) for s in seeds] * 4)
            outs = fn(jnp.asarray(preds), jnp.asarray(y), jnp.asarray(costs),
                      keys, jnp.repeat(jnp.asarray(budgets, jnp.float32), 4))
            leaf = outs["cost"]
            n_dev = len({s.device for s in leaf.addressable_shards})
            check(n_dev == 4, f"{label} {algo}: outputs on {n_dev} devices")
            say(f"mesh.{label}.{algo}", configs=16, devices=n_dev,
                bit_equal=True, first_sel_size_diff_round=first,
                max_mse_rel_diff=float(np.max(np.abs(
                    sharded.final_mse - one.final_mse) / one.final_mse)),
                mesh_s=t_mesh, one_chip_s=t_one)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh path and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)

    from repro.launch.cache import use_compile_cache
    import jax
    use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SmokeFailure(f"JAX found no TPU (default platform "
                           f"{dev.platform!r})")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} but JAX sees {len(devices)} chip(s)")
    say("device", platform=dev.platform, kind=dev.device_kind,
        count=len(devices))

    stream = build_stream(jax)
    pool, ys, preds = stream[0], stream[4], stream[5]
    if args.chips == 4:
        phase_mesh(jax, preds, ys, pool.costs)
    else:
        phase_pool(jax, stream)
        phase_kernels(jax, preds, ys, pool.costs)
        solo = phase_solo(jax, preds, ys, pool.costs)
        phase_fused_server(jax, preds, ys, pool.costs, solo)
        phase_batched(jax, preds, ys, pool.costs)
        phase_scenario(jax, preds, ys, pool.costs)
        phase_served(jax, preds, ys, pool.costs, solo)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
