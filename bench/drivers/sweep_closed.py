"""Closed-loop seed x budget sweeps through ``repro.federated.run_sweep``.

A researcher's grid: each call runs ``seeds_per_call`` fresh seeds under
every budget of the traffic file for the configuration's horizon ``T``, as
one program (vmapped on one chip; on several chips ``run_sweep`` shards the
grid over its 1-D sweep mesh).  Calls run back to back until the window's
``--seconds`` have passed; the window ends with the last call's results on
the host.

Traffic keys: ``algo``, ``seeds_per_call``, ``budgets``, ``trace_calls``
(the calls a traced run's profiler sees), ``check_lanes``
(lanes compared with the reference, drawn from the seed across the
window's calls, the same number under each budget) and ``limits``.
"""

from __future__ import annotations

import time

import numpy as np

from bench import check, stream


def _keep_masks(engine):
    """Make ``run_sweep`` keep the per-round transmit sets and the clients'
    per-round MSE it already copies to the host (its ``SweepResult`` keeps
    only their sums): the comparison needs them.  Observation only."""
    base = engine.SweepResult
    if getattr(base, "keeps_rounds", False):
        return

    class SweepResult(base):
        keeps_rounds = True

        def __init__(self, outs, *args, **kw):
            super().__init__(outs, *args, **kw)
            self.sel = np.asarray(outs["sel"])
            self.ens_sq_mean = np.asarray(outs["ens_sq_mean"])

    engine.SweepResult = SweepResult


class Driver:
    def __init__(self, cell):
        self.cell = cell
        self.cfg, tr = cell.config, cell.traffic
        self.algo = tr["algo"]
        self.budgets = [float(b) for b in tr["budgets"]]
        self.n_seeds = int(tr["seeds_per_call"])
        self.T = int(self.cfg["T"])
        self.rng = np.random.default_rng(cell.seed)
        self.data_seed = int(self.rng.integers(2**31 - 1))
        self.calls = []

    def _call_seeds(self):
        return [int(s) for s in self.rng.integers(0, 2**31 - 1, self.n_seeds)]

    def _sweep(self, seeds):
        import jax
        from repro.federated import run_sweep
        s = self.stream
        with jax.profiler.TraceAnnotation("bench.run_sweep"):
            return run_sweep(self.algo, s.preds, s.y, s.costs, self.T,
                             self.sim_cfg, seeds=seeds, budgets=self.budgets)

    def setup(self):
        from repro.federated import SimConfig, engine
        _keep_masks(engine)
        self.stream = stream.build(self.cfg, self.data_seed)
        self.sim_cfg = SimConfig(
            n_clients=self.cfg["n_clients"],
            clients_per_round=self.cfg["clients_per_round"],
            budget=self.cfg["budget"], loss_scale=self.cfg["loss_scale"])

    def warm(self):
        # the window's one shape: the same grid, on seeds it never uses
        warm = np.random.default_rng(self.data_seed)
        self._sweep([int(s) for s in warm.integers(0, 2**31 - 1,
                                                   self.n_seeds)])

    def window(self, seconds: float) -> dict:
        """Calls back to back until ``seconds`` have passed.  Traced, the
        profiler sees only the first ``trace_calls`` calls."""
        t0 = time.monotonic()
        self.traced_calls = 0
        self.cell.start_trace()
        while True:
            seeds = self._call_seeds()
            self.calls.append((seeds, self._sweep(seeds)))
            if (self.cell.tracing and self.traced_calls == 0 and len(
                    self.calls) == int(self.cell.traffic["trace_calls"])):
                self.cell.end_trace()
                self.traced_calls = len(self.calls)
            if time.monotonic() - t0 >= seconds:
                break
        elapsed = time.monotonic() - t0
        lanes = len(self.budgets) * self.n_seeds
        return {"metrics": {"lane_rounds_per_s":
                            len(self.calls) * lanes * self.T / elapsed},
                "attempted": len(self.calls) * lanes, "failed": 0}

    def stats(self) -> dict:
        """Counts of the window for the per-layer readers."""
        return {"traced_scan_rounds": (self.traced_calls
                                       or len(self.calls)) * self.T,
                "lanes_per_call": len(self.budgets) * self.n_seeds,
                "with_grad": self.algo == "fedboost",
                "window": self.cfg["clients_per_round"],
                "K": int(self.stream.preds.shape[0])}

    def release(self):
        """Keep the host copies the check needs, free the device arrays."""
        s = self.stream
        self.data = (s.preds_host, s.y_host, s.ref_costs)
        self.stream = None

    def sample(self):
        """The lanes compared with the reference: ``check_lanes`` of them,
        drawn from the seed across the window's calls, the same number
        under each budget.  Returns ``(lanes, prog)``: ``(algo, seed,
        budget)`` per lane and the program's ``sel``/``ens_sq_mean``."""
        n = int(self.cell.traffic["check_lanes"])
        rng = np.random.default_rng([self.cell.seed, 1])
        rows = np.arange(n) % len(self.budgets)
        calls = rng.integers(0, len(self.calls), n)
        cols = rng.integers(0, self.n_seeds, n)
        lanes = [(self.algo, self.calls[c][0][s], self.budgets[r])
                 for c, r, s in zip(calls, rows, cols)]
        try:
            prog = {k: np.stack([getattr(self.calls[c][1], k)[r, s]
                                 for c, r, s in zip(calls, rows, cols)])
                    for k in ("sel", "ens_sq_mean")}
        except (IndexError, ValueError):
            prog = {"sel": np.zeros(0), "ens_sq_mean": np.zeros(0)}
        return lanes, prog

    def check(self) -> list:
        K = self.data[0].shape[0]
        shape = (len(self.budgets), self.n_seeds, self.T)
        bad = violations = 0
        for _, res in self.calls:
            if (res.ens_sq_mean.shape != shape
                    or res.sel.shape != shape + (K,)):
                bad += shape[0] * shape[1]
            else:
                bad += int((~np.isfinite(res.ens_sq_mean).all(-1)).sum())
            violations += int(np.sum(res.violations))
        lanes, prog = self.sample()
        self.ref = check.reference_lanes(lanes, self.data, self.cfg)
        self.numbers = {"lanes_bad": bad, "violations": violations,
                        **check.compare(prog, self.ref)}
        return check.checks(self.numbers, self.cell.traffic["limits"])
