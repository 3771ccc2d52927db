"""The serving loop: streams registry, executable cache, dispatch thread.

``SimServer`` accepts many concurrent simulation requests (EFL-FG /
FedBoost config + seed + budget), coalesces them with the dynamic
batcher into bucketed batch shapes, and dispatches each bucket as ONE
engine call:

* batched buckets go through ``repro.federated.run_batch`` — a single
  vmapped (or, when the dispatch plan says so, mesh-sharded) flat batch
  whose padded width is the bucket size;
* exact buckets run each lane with the solo cached
  ``run_simulation_scan`` program — bit-equal to a direct call, the
  reproducibility mode.

A compiled-executable cache keyed by (mode, stream name + registration
version + shape, algorithm, T, W, static config, schedule class
(stationary vs scheduled — scenarios themselves are per-lane jit
arguments, not key material), bucket size, sharded) makes steady-state
traffic
re-use a handful of compiled programs: every key is built (and its
program compiled) exactly once, then hit forever — the engine's own
scan cache plus the fixed bucket shapes guarantee no retracing
underneath.  See docs/serving.md for the request lifecycle and the
determinism contract.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, replace
from typing import Callable, Optional

import numpy as np

from .. import obs

__all__ = ["SimServer", "ExecutableCache", "Stream"]


class ExecutableCache:
    """Executable registry with hit/miss accounting.

    Values are dispatch closures over compiled engine programs; a key's
    builder runs once (the compile), after which every bucket with the
    same shape is a hit.  ``info()`` is the observability surface the
    tests and the bench assert on.
    """

    def __init__(self):
        self._fns: dict = {}
        self.hits = 0
        self.misses = 0
        self._lock = threading.Lock()

    def get_or_build(self, key: tuple, builder: Callable) -> Callable:
        with self._lock:
            fn = self._fns.get(key)
            if fn is not None:
                self.hits += 1
                return fn
            self.misses += 1
        fn = builder()            # compile outside the lock
        with self._lock:
            self._fns.setdefault(key, fn)
            return self._fns[key]

    def evict(self, predicate: Callable) -> int:
        """Drop every entry whose key matches; returns the count.  Used
        when a stream is re-registered — superseded closures would
        otherwise pin the old device arrays for the server's lifetime."""
        with self._lock:
            dead = [k for k in self._fns if predicate(k)]
            for k in dead:
                del self._fns[k]
            return len(dead)

    def info(self) -> dict:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "size": len(self._fns)}


@dataclass(frozen=True)
class Stream:
    """A registered tenant stream: the precomputed expert predictions the
    simulations run against (see ``run_simulation_scan`` for shapes).
    ``version`` counts registrations under this name — it rides in every
    executable-cache key so re-registering a stream (even with identical
    shapes) can never serve stale data from an old closure."""
    name: str
    preds: object          # (K, n_stream) jnp.float32
    y: object              # (n_stream,)   jnp.float32
    costs: object          # (K,)          jnp.float32
    version: int = 1

    @property
    def K(self) -> int:
        return self.preds.shape[0]

    @property
    def n_stream(self) -> int:
        return self.preds.shape[1]


class SimServer:
    """In-process multi-tenant simulation server.

    Lifecycle: ``register_stream`` the expert streams, ``start()`` the
    dispatch thread (or use the context manager), ``submit`` requests
    from any number of threads, read results from the returned
    ``SimFuture``s, ``stop()`` to drain and shut down.  Submissions
    before ``start()`` simply queue up — the first drain takes them all,
    which is also the deterministic way to measure batching (see
    ``benchmarks/engine_bench.py``).

    ``max_batch`` bounds the flat batch width (buckets are the powers of
    two up to it); ``max_wait_ms`` is the coalescing window — how long
    the batcher lingers after the first queued request so a concurrent
    burst lands in one drain.  Latency-sensitive deployments shrink it,
    throughput-oriented ones grow it (docs/serving.md#tuning).

    ``mesh`` pins a pure-``sweep`` mesh for batched buckets wide enough
    to give every shard at least two lanes; narrower buckets fall back
    to the default dispatch (same batched program family either way).
    By default the engine's dispatch plan decides per bucket
    (``repro.federated.engine.batch_dispatch_plan``).
    """

    def __init__(self, max_batch: int = 16, max_wait_ms: float = 2.0,
                 mesh=None, poll_s: float = 0.05):
        from .queue import RequestQueue
        from .batcher import DynamicBatcher
        if mesh is not None:
            from repro.federated import sweep_sharding
            _, n_data = sweep_sharding.mesh_axes(mesh)
            if n_data > 1:
                raise ValueError("SimServer: serving meshes must be pure "
                                 "sweep partitions (got data axis size "
                                 f"{n_data})")
        self.mesh = mesh
        self.cache = ExecutableCache()
        # Counters live on the registry (one catalogue entry per name —
        # repro.obs.catalog.SERVER_COUNTERS); each instrument carries
        # its own lock, so increments are race-free without holding the
        # server lock.  stats() rebuilds the legacy flat-dict shape
        # from the same table.
        self.metrics = obs.MetricsRegistry()
        self._c = obs.catalog.register_counters(
            self.metrics, "server", obs.catalog.SERVER_COUNTERS)
        self._queue = RequestQueue(registry=self.metrics, prefix="server",
                                   spans=True)
        self._batcher = DynamicBatcher(self._queue, max_batch=max_batch,
                                       max_wait_ms=max_wait_ms,
                                       registry=self.metrics)
        self._poll_s = poll_s
        self._streams: dict = {}
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()

    # -- tenant streams ---------------------------------------------------

    def register_stream(self, name: str, preds, y, costs) -> Stream:
        """Register (or replace) a tenant stream the server can simulate
        against.  Arrays are converted to device-resident float32 once,
        here — not per request."""
        import jax.numpy as jnp
        preds = jnp.asarray(preds, jnp.float32)
        y = jnp.asarray(y, jnp.float32)
        costs = jnp.asarray(costs, jnp.float32)
        if preds.ndim != 2 or y.shape != (preds.shape[1],) \
                or costs.shape != (preds.shape[0],):
            raise ValueError(
                f"stream {name!r}: expected preds (K, n_stream), y "
                f"(n_stream,), costs (K,); got {preds.shape}, {y.shape}, "
                f"{costs.shape}")
        with self._lock:
            prev = self._streams.get(name)
            stream = Stream(name, preds, y, costs,
                            version=(prev.version + 1) if prev else 1)
            self._streams[name] = stream
        if prev is not None:
            # cache keys are (mode, stream-name, version, ...): drop the
            # superseded versions so their closures stop pinning the old
            # arrays (in-flight buckets already hold their own refs)
            self.cache.evict(
                lambda k: k[1] == name and k[2] != stream.version)
        return stream

    # -- submission -------------------------------------------------------

    def submit(self, algo: str, seed: int, *, T: int,
               budget: Optional[float] = None, stream: str = "default",
               cfg=None, exact: bool = False, scenario=None,
               priority: int = 0, trace=None):
        """Enqueue one simulation request; returns its ``SimFuture``.

        Thread-safe.  Client-side mistakes (unknown stream/algo/scenario,
        bad T) raise here, synchronously; server-side dispatch failures
        surface through ``SimFuture.result()``.

        ``scenario`` is a registered scenario name or a
        ``repro.scenarios.Scenario`` (resolved here, so unknown names
        fail the submitter, not a co-tenant's bucket).  Requests batch
        by schedule *class*, not by scenario: tenants on different
        non-stationary schedules coalesce into one bucket, whose
        compiled per-lane schedule rows stack along the batch axis
        (``run_batch``).  All-neutral scenarios (``"constant"``) are
        normalized to ``None`` here, so they ride the stationary
        program — bit-equal to scenario-free traffic by construction.
        ``priority`` (higher first) orders bucket dispatch — see
        docs/serving.md#priority.

        ``trace`` is an optional ``repro.obs`` trace context (a
        ``{"trace_id", "span_id"}`` dict): passed by the worker/daemon
        tier so spans stitch across processes, minted fresh here for
        direct in-process submitters (a no-op when observability is
        disabled).  Observe-only — it never affects batching or bits.
        """
        from .queue import SimRequest, SimFuture
        from .batcher import group_key
        with self._lock:
            if stream not in self._streams:
                raise ValueError(
                    f"unknown stream {stream!r}; registered: "
                    f"{sorted(self._streams)} (register_stream first)")
        budget = None if budget is None else float(budget)
        if scenario is not None:
            from repro.scenarios import resolve
            scenario = resolve(scenario)
        if trace is None:
            trace = obs.mint()
        req = SimRequest(algo=algo, seed=int(seed), T=int(T), budget=budget,
                         stream=stream, cfg=cfg, exact=exact,
                         scenario=scenario, priority=int(priority),
                         trace=trace)
        try:
            group_key(req)          # exercises cfg.static_key/cfg.rates
        except Exception as exc:
            raise ValueError(
                f"cfg must be a SimConfig (or None), got {type(cfg)!r}: "
                f"{exc}") from exc
        if scenario is not None:
            # cfg validated above: compile (cached engine-side — warms
            # the schedule the dispatch will use) and normalize neutral
            # schedules to the stationary class
            from repro.federated import SimConfig
            from repro.federated.engine import _compile_scenario
            comp = _compile_scenario(
                scenario, req.T, cfg if cfg is not None else SimConfig())
            if comp.neutral:
                req.scenario = None
        fut = SimFuture(req)
        self._queue.put(req, fut)
        self._c["submitted"].inc()
        obs.TRACER.event("serve.submitted", trace,
                         attrs={"algo": req.algo, "seed": req.seed,
                                "stream": req.stream})
        return fut

    # -- lifecycle --------------------------------------------------------

    def start(self) -> "SimServer":
        if self._thread is not None:
            raise RuntimeError("server already started")
        self._thread = threading.Thread(target=self._serve_loop,
                                        name="simserver", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        """Close the queue, serve everything already submitted, join."""
        self._queue.close()
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def __enter__(self) -> "SimServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- dispatch ---------------------------------------------------------

    def _serve_loop(self) -> None:
        while True:
            try:
                buckets = self._batcher.next_buckets(wait_s=self._poll_s)
            except Exception:                     # noqa: BLE001
                # planning must never kill the serve thread: a dead
                # thread hangs every outstanding and future SimFuture.
                # (The batcher quarantines malformed requests onto their
                # own futures; this guard is the last line of defense.)
                continue
            if not buckets:
                if self._queue.closed:
                    return
                continue
            for bucket in buckets:
                self._dispatch(bucket)

    def _resolve(self, bucket):
        """(stream, cfg, per-lane budgets, per-lane scenarios — padding
        included) for a bucket.

        The bucket's group key guarantees every request shares the same
        *static* config, so ``req0.cfg`` can shape the program — but
        ``budget`` and ``scenario`` are per-lane knobs excluded from the
        key: a ``budget=None`` request must fall back to its OWN
        config's default, never a co-tenant's, and each lane runs its
        own schedule (padding lanes repeat the last request's, a valid
        configuration whose results are dropped).
        """
        from repro.federated import SimConfig
        req0 = bucket.requests[0][0]
        with self._lock:
            stream = self._streams.get(req0.stream)
        if stream is None:
            raise ValueError(f"stream {req0.stream!r} was unregistered "
                             "while queued")
        cfg = req0.cfg if req0.cfg is not None else SimConfig()
        default_budget = SimConfig.budget
        budgets = [r.budget if r.budget is not None
                   else (r.cfg.budget if r.cfg is not None
                         else default_budget)
                   for r, _ in bucket.requests]
        budgets += [budgets[-1]] * bucket.n_padding
        scenarios = [r.scenario for r, _ in bucket.requests]
        scenarios += [scenarios[-1]] * bucket.n_padding
        return stream, cfg, budgets, scenarios

    def _dispatch(self, bucket) -> None:
        """Run one bucket under its ``serve.dispatch`` span (the engine's
        spans nest inside it)."""
        traces = [r.trace for r, _ in bucket.requests if r.trace]
        with obs.span("serve.dispatch", trace=traces, items=bucket.n) as sp:
            self._run_bucket(bucket, sp)

    def _run_bucket(self, bucket, sp) -> None:
        from repro.federated import run_simulation_scan, run_batch
        from repro.federated.engine import batch_buckets, batch_dispatch_plan
        from repro.federated.simulation import eval_window
        seq = self._c["dispatch_seq"].inc() - 1      # atomic allocation
        meta = {"mode": "exact" if bucket.exact else "batched",
                "bucket": bucket.size, "n_requests": bucket.n,
                "n_padding": bucket.n_padding, "sharded": False,
                "seq": seq}
        try:
            with obs.span("server.resolve"):
                stream, cfg, budgets, scens = self._resolve(bucket)
            req0 = bucket.requests[0][0]
            scheduled = bucket.scheduled  # group key: the schedule CLASS
            meta["scheduled"] = scheduled
            meta["n_scenarios"] = len({r.scenario
                                       for r, _ in bucket.requests})
            W = eval_window(cfg)
            base_key = (req0.stream, stream.version, stream.K,
                        stream.n_stream, req0.algo, req0.T, W,
                        bucket.key[4], scheduled)
            if bucket.exact:
                key = ("exact", *base_key)
                def build_exact():
                    def run(seed, budget, scenario):
                        return run_simulation_scan(
                            req0.algo, stream.preds, stream.y, stream.costs,
                            req0.T, replace(cfg, seed=int(seed),
                                            budget=float(budget)),
                            scenario=scenario)
                    return run
                run = self.cache.get_or_build(key, build_exact)
                results = [run(r.seed, b, s) for (r, _), b, s
                           in zip(bucket.requests, budgets, scens)]
            else:
                mesh = self.mesh
                if mesh is not None and cfg.sweep_sharded is None:
                    from repro.federated import sweep_sharding
                    n_sweep, _ = sweep_sharding.mesh_axes(mesh)
                    if bucket.size < 2 * n_sweep:
                        # a pinned mesh must not make quiet-period
                        # traffic unservable: buckets too narrow for
                        # >= 2 lanes per shard fall back to the default
                        # dispatch (same batched program family, so the
                        # lanes' bits don't change — only the placement)
                        mesh = None
                sharded, mesh = batch_dispatch_plan(cfg, bucket.size, mesh)
                meta["sharded"] = sharded
                # budget compaction happens inside run_batch on the vmap
                # path; surface the plan so clients can see how their
                # lane was grouped (None = single mixed dispatch)
                meta["budget_buckets"] = (None if sharded else
                                          batch_buckets(req0.algo, budgets))
                key = ("batched", *base_key, bucket.size, sharded)
                def build_batched():
                    def run(seeds, budgets, scenarios):
                        return run_batch(
                            req0.algo, stream.preds, stream.y, stream.costs,
                            req0.T, cfg, seeds, budgets, mesh=mesh,
                            scenario=scenarios)
                    return run
                run = self.cache.get_or_build(key, build_batched)
                results = run(bucket.seeds(), budgets,
                              scens if scheduled else None)[:bucket.n]
        except Exception as exc:                        # noqa: BLE001
            self._c["failed"].inc(bucket.n)
            self._trace_dispatch(sp, bucket, meta, "error")
            with obs.span("server.settle"):
                for _, fut in bucket.requests:
                    if not fut.done():
                        fut.set_exception(exc, execution=dict(meta))
            return
        # register_stream may have replaced the stream between _resolve
        # and get_or_build, in which case get_or_build re-inserted a key
        # for the superseded version AFTER registration's eviction ran.
        # The results (computed against the stream the requests were
        # submitted under) are fine — but the stale entry would pin the
        # old arrays forever, so drop it here, in the same thread that
        # inserted it.
        with self._lock:
            current = self._streams.get(req0.stream)
        if current is None or current.version != stream.version:
            self.cache.evict(lambda k: k[1] == req0.stream
                             and k[2] == stream.version)
        self._c["served"].inc(bucket.n)
        self._c["batches"].inc()
        if bucket.exact:
            self._c["exact_requests"].inc(bucket.n)
        else:
            self._c["batched_lanes"].inc(bucket.size)
            self._c["padded_lanes"].inc(bucket.n_padding)
            if meta["sharded"]:
                self._c["sharded_batches"].inc()
        self._trace_dispatch(sp, bucket, meta, "ok")
        with obs.span("server.settle"):
            for (_, fut), res in zip(bucket.requests, results):
                fut.set_result(res, execution=dict(meta))

    @staticmethod
    def _trace_dispatch(sp, bucket, meta: dict, outcome: str) -> None:
        """End each traced request's ``serve.dispatch`` span before its
        future settles — attrs carry the bucket metadata plus the
        co-tenant seeds ("batched-with-whom").  Observe-only: reads
        request metadata, never results."""
        if not obs.enabled():
            return
        attrs = {k: meta[k] for k in ("mode", "bucket", "n_requests",
                                      "n_padding", "sharded", "seq")}
        attrs["outcome"] = outcome
        attrs["co_seeds"] = [r.seed for r, _ in bucket.requests[:32]]
        sp.record_requests(attrs)

    # -- observability ----------------------------------------------------

    def stats(self) -> dict:
        """Counters + cache info; ``mean_occupancy`` is real requests per
        batched lane (1.0 = no padding waste).  The flat legacy keys are
        rebuilt from the registry instruments (the catalogue is the one
        source of names); ``SimServer.metrics.snapshot()`` is the full
        typed tree."""
        s = {short: self._c[short].value
             for short in obs.catalog.SERVER_COUNTERS}
        lanes = s["batched_lanes"]
        s["mean_occupancy"] = ((lanes - s["padded_lanes"]) / lanes
                               if lanes else None)
        s["cache"] = self.cache.info()
        return s
