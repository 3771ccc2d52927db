"""Open-loop Poisson traffic against an in-process ``SimServer``.

Tenants submit simulation requests through ``SimClient.submit`` on their
own schedule; the server's batcher groups them by algorithm into padded
buckets of up to ``max_batch`` lanes and its one dispatch thread runs each
bucket as one ``run_batch`` program.  Each request is timed from its
scheduled arrival to its result, so queueing and batching delay count.

Traffic keys: ``rate_hz`` (offered load, fixed: 0.8 x the knee measured on
the chip, see PERF.md), ``mix`` (share of requests per algorithm),
``budgets`` (drawn uniformly), ``max_batch``, ``max_wait_ms``,
``trace_seconds`` (the window's last seconds, which a traced run's
profiler sees),
``check_requests`` (requests compared with the reference, drawn from the
seed) and ``limits``.

Every seed offers the same work: ``round(rate_hz * seconds)`` requests, the
exact algorithm and budget counts the mix gives, arriving as a Poisson
process of that count in the window (uniform arrival times, sorted); the
seed orders them and seeds each request.
"""

from __future__ import annotations

import math
import threading
import time

import numpy as np

from bench import check, stream


def run_open_loop(submit, specs, arrivals, timeout_s: float = 300.0) -> dict:
    """Submit ``specs[i]`` at ``t0 + arrivals[i]`` whatever the earlier
    requests are doing, and time each from that scheduled moment to its
    result (no coordinated omission).

    Returns ``{"t0", "lat": [s or None if failed], "done": [monotonic],
    "late": [s the submit ran behind its schedule], "results"}``.
    """
    n = len(specs)
    lat = [None] * n
    done = [None] * n
    late = [0.0] * n
    results = [None] * n
    errors = []
    lock = threading.Lock()
    all_done = threading.Event()
    remaining = [n]

    def settle(i, t_sched, fut=None, exc=None):
        now = time.monotonic()
        with lock:
            if exc is None:
                try:
                    results[i] = fut.result(timeout=0)
                    lat[i] = now - t_sched
                except Exception as e:          # noqa: BLE001 - tallied
                    exc = e
            if exc is not None:
                errors.append(type(exc).__name__)
            done[i] = now
            remaining[0] -= 1
            if remaining[0] == 0:
                all_done.set()

    t0 = time.monotonic() + 0.005
    for i, (spec, at) in enumerate(zip(specs, arrivals)):
        t_sched = t0 + at
        delay = t_sched - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        late[i] = max(0.0, time.monotonic() - t_sched)
        try:
            fut = submit(spec)
        except Exception as exc:                # noqa: BLE001 - tallied
            settle(i, t_sched, exc=exc)
            continue
        fut.add_done_callback(
            lambda f, i=i, ts=t_sched: settle(i, ts, fut=f))
    if n and not all_done.wait(timeout_s):
        raise TimeoutError(f"{remaining[0]} of {n} requests outstanding "
                           f"{timeout_s} s after the last arrival")
    return {"t0": t0, "lat": lat, "done": done, "late": late,
            "results": results, "errors": errors}


def _counts(total: int, shares) -> list:
    """Split ``total`` by ``shares`` into whole counts that sum to it."""
    raw = [total * s for s in shares]
    out = [math.floor(r) for r in raw]
    for i in np.argsort([o - r for o, r in zip(out, raw)])[:total - sum(out)]:
        out[i] += 1
    return out


class Driver:
    def __init__(self, cell):
        self.cell = cell
        self.cfg, self.tr = cell.config, cell.traffic
        self.T = int(self.cfg["T"])
        self.rng = np.random.default_rng(cell.seed)
        self.data_seed = int(self.rng.integers(2**31 - 1))
        self.algos = sorted(self.tr["mix"])
        self.budgets = [float(b) for b in self.tr["budgets"]]

    def setup(self):
        from repro.federated import SimConfig
        from repro.serve import SimClient, SimServer
        self.stream = stream.build(self.cfg, self.data_seed)
        self.sim_cfg = SimConfig(
            n_clients=self.cfg["n_clients"],
            clients_per_round=self.cfg["clients_per_round"],
            budget=self.cfg["budget"], loss_scale=self.cfg["loss_scale"])
        self.server = SimServer(max_batch=int(self.tr["max_batch"]),
                                max_wait_ms=float(self.tr["max_wait_ms"]))
        s = self.stream
        self.server.register_stream("default", s.preds, s.y, s.costs)
        self.server.start()
        self.client = SimClient(self.server)

    def _submit(self, spec):
        import jax
        algo, seed, budget = spec
        with jax.profiler.TraceAnnotation("bench.submit"):
            return self.client.submit(algo, seed, T=self.T, budget=budget,
                                      cfg=self.sim_cfg)

    def _submit_traced(self, spec):
        """Submit; the traced part is the window's last ``trace_seconds``
        of arrivals, and the server's counters are read up to its start,
        so that the profiler's drag on the host does not reach them."""
        if (self.snap_traced is None and time.monotonic() - self.t_window
                >= self.seconds - float(self.tr["trace_seconds"])):
            self.snap_traced = self.server.metrics.snapshot()
            self.t_traced = time.monotonic()
            self.cell.start_trace()
        return self._submit(spec)

    def warm(self):
        """Every lane width the traffic can dispatch: the batcher pads a
        group to a bucket (powers of two up to ``max_batch``), and for
        EFL-FG ``run_batch`` splits a bucket into one program per budget
        of two lanes or more, gathering each group's keys and budgets, so
        any width and any group size from 2 to ``max_batch``."""
        from repro.federated import run_batch
        from repro.serve.batcher import bucket_sizes
        s, mb = self.stream, int(self.tr["max_batch"])
        b0, b1 = self.budgets[:2]
        for algo in self.algos:
            widths = (range(2, mb + 1) if algo == "eflfg"
                      else bucket_sizes(mb))
            for w in widths:
                run_batch(algo, s.preds, s.y, s.costs, self.T, self.sim_cfg,
                          seeds=list(range(w)), budgets=[b0] * w)
            if algo == "eflfg":
                for g in range(2, mb):
                    run_batch(algo, s.preds, s.y, s.costs, self.T,
                              self.sim_cfg, seeds=list(range(g + 2)),
                              budgets=[b0] * g + [b1] * 2)
        futs = [self._submit((a, i, self.budgets[i % len(self.budgets)]))
                for i, a in enumerate(self.algos * 4)]
        for f in futs:
            f.result(600)

    def _schedule(self, seconds: float):
        n = int(round(float(self.tr["rate_hz"]) * seconds))
        algos = np.repeat(self.algos, _counts(
            n, [self.tr["mix"][a] for a in self.algos]))
        budgets = np.repeat(self.budgets, _counts(
            n, [1 / len(self.budgets)] * len(self.budgets)))
        algos = self.rng.permutation(algos)
        budgets = self.rng.permutation(budgets)
        seeds = self.rng.integers(0, 2**31 - 1, n)
        arrivals = np.sort(self.rng.uniform(0.0, seconds, n))
        specs = [(str(a), int(s), float(b))
                 for a, s, b in zip(algos, seeds, budgets)]
        return specs, arrivals

    def window(self, seconds: float) -> dict:
        specs, arrivals = self._schedule(seconds)
        self.specs = specs
        before = self.server.metrics.snapshot()
        self.seconds, self.snap_traced = seconds, None
        self.t_window = self.t_traced = time.monotonic()
        raw = run_open_loop(self._submit_traced if self.cell.tracing
                            else self._submit, specs, arrivals)
        self.cell.end_trace()
        after = self.snap_traced or self.server.metrics.snapshot()
        self.raw, self.snaps = raw, (before, after)
        self.counted_s = (self.t_traced if self.snap_traced
                          else time.monotonic()) - self.t_window
        ok = [x for x in raw["lat"] if x is not None]
        failed = len(specs) - len(ok)
        end = max(raw["done"]) if specs else raw["t0"]
        self.elapsed = end - raw["t0"]
        lats = sorted(x if x is not None else math.inf for x in raw["lat"])
        p95 = lats[max(0, math.ceil(0.95 * len(lats)) - 1)] if lats else 0.0
        late = np.asarray(raw["late"])
        self.cell.note(
            f"generator_late_s p50={float(np.median(late))!r} "
            f"p95={float(np.quantile(late, 0.95))!r} max={float(late.max())!r} "
            f"requests={len(specs)} errors={sorted(set(raw['errors']))}")
        return {"metrics": {"req_per_s": len(ok) / self.elapsed,
                            "p95_latency_s": p95},
                "attempted": len(specs), "failed": failed}

    def stats(self) -> dict:
        before, after = self.snaps

        def delta(kind, name, key=None):
            a, b = after[kind].get(name), before[kind].get(name)
            if key is not None:
                a, b = a[key], b[key] if b else 0
            return a - (b or 0)

        return {"batched_lanes": delta("counters", "server.batched_lanes"),
                "padded_lanes": delta("counters", "server.padded_lanes"),
                "queue_wait_sum": delta("histograms", "server.queue.wait_s",
                                        "sum"),
                "queue_wait_count": delta("histograms",
                                          "server.queue.wait_s", "count"),
                "counted_s": self.counted_s}

    def release(self):
        self.server.stop()
        s = self.stream
        self.data = (s.preds_host, s.y_host, s.ref_costs)
        self.server = self.client = self.stream = None

    def sample(self):
        """``check_requests`` requests drawn from the seed: ``(lanes,
        prog)`` with ``(algo, seed, budget)`` per request and the
        program's ``sel``/``ens_sq_mean`` (``None`` where it failed)."""
        res = self.raw["results"]
        rng = np.random.default_rng([self.cell.seed, 1])
        n = min(int(self.tr["check_requests"]), len(res))
        pick = sorted(rng.choice(len(res), n, replace=False))
        lanes = [self.specs[i] for i in pick]
        if any(res[i] is None for i in pick):
            return lanes, None
        prog = {"sel": np.stack([res[i].sel_masks for i in pick]),
                "ens_sq_mean": np.stack([
                    np.diff(res[i].mse_curve * np.arange(1, self.T + 1),
                            prepend=0.0) for i in pick])}
        return lanes, prog

    def check(self) -> list:
        res = self.raw["results"]
        numbers = {"failed": sum(r is None for r in res),
                   "violations": sum(r.budget_violations
                                     for r, (a, _, _) in zip(res, self.specs)
                                     if r is not None and a == "eflfg")}
        lanes, prog = self.sample()
        if prog is None:
            numbers.update(eval_gap=float("nan"), agree_min=0)
        else:
            self.ref = check.reference_lanes(lanes, self.data, self.cfg)
            numbers.update(check.compare(prog, self.ref))
        self.numbers = numbers
        return check.checks(numbers, self.tr["limits"])
