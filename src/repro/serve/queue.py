"""Request queue and futures for the in-process serving stack.

Pure stdlib (no jax): a ``SimRequest`` describes one simulation to run,
``SimFuture`` is the caller's handle to its eventual ``SimResult``, and
``RequestQueue`` is the thread-safe buffer between submitting clients
and the server's dispatch thread.  The dynamic batcher
(``repro.serve.batcher``) drains the queue and coalesces compatible
requests; ``repro.serve.server.SimServer`` owns the dispatch loop.
"""

from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Optional

from ..obs import clock as obs_clock, state as obs_state, trace as obs_trace

__all__ = ["ALGOS", "SimRequest", "SimFuture", "RequestQueue",
           "QueueClosed"]

ALGOS = ("eflfg", "fedboost")


@dataclass
class SimRequest:
    """One tenant's simulation request.

    ``seed`` and ``budget`` are the per-request knobs (the flat batch
    axis); everything else must match for two requests to share a batch
    (see ``repro.serve.batcher.group_key``).  ``budget=None`` means the
    config's default.  ``cfg`` is a ``repro.federated.SimConfig`` (or
    ``None`` for the defaults); its own ``seed``/``budget`` fields are
    ignored in favor of the request's — the request IS the
    configuration axis.

    ``scenario`` is ``None`` (stationary) or a
    ``repro.scenarios.Scenario`` — ``SimServer.submit`` resolves
    registered names before enqueueing, so by the time a request reaches
    the batcher the field is hashable and group-keyable: requests only
    share a batch when they run the SAME schedule.

    ``priority`` (int, default 0, higher = sooner) orders *buckets* at
    dispatch time: the batcher plans higher-priority buckets first, FIFO
    within a bucket.  It never changes results — only who waits.

    ``exact=True`` asks for the exact execution mode: the request is
    still queued and coalesced, but executed with the solo cached
    program, so its trajectories are bit-equal to a direct
    ``run_simulation_scan`` call.  The default batched mode is the
    throughput path: bit-equal to the engine's batched sweep family,
    float32-close to solo runs (docs/serving.md#determinism).
    """
    algo: str
    seed: int
    T: int
    budget: Optional[float] = None
    stream: str = "default"
    cfg: Any = None                   # SimConfig | None (server default)
    exact: bool = False
    scenario: Any = None              # Scenario | None (stationary)
    priority: int = 0                 # bucket dispatch order; higher first
    deadline: Optional[float] = None  # absolute time.monotonic() bound; the
                                      # remote daemon drops expired requests
                                      # before dispatch (None = no deadline)
    trace: Any = None                 # repro.obs trace context dict (or
                                      # None = untraced); observe-only —
                                      # never part of the batch group key
    # Clock discipline (docs/observability.md#clocks): ``submitted_at``
    # is a ``time.monotonic()`` reading, so queue wait and age are exact
    # monotonic differences WITHIN this process — it is meaningless in
    # any other process.  Cross-process consumers use ``submitted_wall``,
    # the conversion through this process's one wall anchor.
    submitted_at: float = field(default_factory=time.monotonic)

    @property
    def submitted_wall(self) -> float:
        """Wall-clock submit time via the per-process anchor
        (``repro.obs.clock.to_wall``) — safe to compare across
        processes, unlike ``submitted_at``."""
        return obs_clock.to_wall(self.submitted_at)

    def __post_init__(self):
        if self.algo not in ALGOS:
            raise ValueError(f"unknown algo {self.algo!r}; expected one "
                             f"of {ALGOS}")
        if self.T <= 0:
            raise ValueError(f"T must be positive, got {self.T}")
        self.priority = int(self.priority)


class SimFuture:
    """Write-once future for a served request.

    The server thread fulfills it with ``set_result``/``set_exception``
    (double fulfillment raises — write-once is enforced, not assumed);
    callers block on ``result()``.  ``execution`` is filled at
    fulfillment time with dispatch metadata (mode, bucket size, padded
    lanes, sharded flag, dispatch ``seq``) — observability for tests and
    tuning.

    ``add_done_callback`` is the thread-free notification hook: each
    callback fires exactly once with the future, in the fulfilling
    thread (immediately, in the calling thread, if already done).
    Callback exceptions are swallowed — a subscriber must never be able
    to break fulfillment or kill the dispatch thread.  This is what the
    asyncio facade (``SimClient.aio_submit``) bridges from, instead of
    parking a waiter thread per request.

    Deliberately NOT a ``concurrent.futures.Future``: serving futures
    have no cancellation story (an in-flight XLA dispatch cannot be
    aborted) and no executor integration; this keeps exactly the
    surface the serving contract defines.
    """

    def __init__(self, request: SimRequest):
        self.request = request
        self.execution: dict = {}
        self._done = threading.Event()
        self._result = None
        self._exception: Optional[BaseException] = None
        self._cb_lock = threading.Lock()
        self._callbacks: list = []

    def done(self) -> bool:
        return self._done.is_set()

    def add_done_callback(self, fn) -> None:
        """Call ``fn(self)`` when the future fulfills (immediately if it
        already has).  Exceptions from ``fn`` are swallowed."""
        with self._cb_lock:
            if not self._done.is_set():
                self._callbacks.append(fn)
                return
        self._run_callback(fn)

    def _run_callback(self, fn) -> None:
        try:
            fn(self)
        except Exception:                       # noqa: BLE001
            pass    # subscribers must not break fulfillment

    def _fire_callbacks(self) -> None:
        with self._cb_lock:
            callbacks, self._callbacks = self._callbacks, []
        for fn in callbacks:
            self._run_callback(fn)

    def _claim(self) -> None:
        # BEFORE any mutation: a rejected double fulfillment must leave
        # the first result observable, not half-overwritten
        if self._done.is_set():
            raise RuntimeError("SimFuture is write-once and already "
                               "fulfilled")

    def set_result(self, result, execution: Optional[dict] = None) -> None:
        self._claim()
        self._result = result
        if execution is not None:
            self.execution = execution
        self._done.set()
        self._fire_callbacks()

    def set_exception(self, exc: BaseException,
                      execution: Optional[dict] = None) -> None:
        self._claim()
        self._exception = exc
        if execution is not None:
            self.execution = execution
        self._done.set()
        self._fire_callbacks()

    def result(self, timeout: Optional[float] = None):
        """Block until fulfilled; raises the server-side exception if the
        dispatch failed, or ``TimeoutError`` on timeout."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self.request.algo}/seed={self.request.seed} not "
                f"served within {timeout}s")
        if self._exception is not None:
            raise self._exception
        return self._result


class QueueClosed(RuntimeError):
    """Raised by ``RequestQueue.put`` after ``close()``."""


class RequestQueue:
    """Thread-safe FIFO of ``(SimRequest, SimFuture)`` pairs.

    ``drain`` implements the dynamic batcher's waiting discipline: block
    up to ``wait_s`` for the first item, then *linger* ``linger_s`` so a
    concurrent burst of submissions coalesces into one drain, then take
    everything queued (up to ``max_n``).  A closed queue drains its
    remainder and then returns empty lists forever.

    ``restore`` is the requeue half of the remote tier's
    requeue-or-fail contract: a drainer that claimed a batch and then
    lost its peer (worker died mid-flight) puts the claim back at the
    FRONT of the queue — and it works on a *closed* queue, because
    ``close()`` only stops NEW submissions.  Without it, a claim taken
    just before shutdown had nowhere to go (``put`` raises
    ``QueueClosed``) and its futures hung forever — the latent shutdown
    race ``tests/test_served_daemon.py`` pins.
    """

    def __init__(self, registry=None, prefix: str = "queue",
                 spans: bool = False):
        """``registry`` (a ``repro.obs.MetricsRegistry``) opts the queue
        into instrumentation under ``<prefix>.queue.depth`` /
        ``<prefix>.queue.oldest_age_s`` (callback gauges — zero cost
        per enqueue) and ``<prefix>.queue.wait_s`` (queue residency,
        observed at claim time — the admission-queue signal pool
        autoscaling needs).  Traced requests additionally get a
        retroactive ``<prefix>.queued`` span per claim.  ``spans``
        makes ``drain`` time its waits as process spans:
        ``<prefix>.wait_work`` while blocked on an empty queue,
        ``<prefix>.coalesce`` for the linger."""
        self._items: list = []
        self._cv = threading.Condition()
        self._closed = False
        self._wait_hist = None
        self._span_name = f"{prefix}.queued"
        self._spans = spans
        self._wait_span = f"{prefix}.wait_work"
        self._linger_span = f"{prefix}.coalesce"
        if registry is not None:
            self._wait_hist = registry.histogram(f"{prefix}.queue.wait_s")
            registry.gauge(f"{prefix}.queue.depth").set_fn(self.__len__)
            registry.gauge(f"{prefix}.queue.oldest_age_s").set_fn(
                self.oldest_age)

    def __len__(self) -> int:
        with self._cv:
            return len(self._items)

    def oldest_age(self) -> float:
        """Seconds the head-of-line request has been queued (0.0 when
        empty).  A restored (requeued) item keeps its original submit
        time, so age reflects total time since submission."""
        with self._cv:
            if not self._items:
                return 0.0
            head = self._items[0][0]
        return max(0.0, time.monotonic() - head.submitted_at)

    @property
    def closed(self) -> bool:
        with self._cv:
            return self._closed

    def put(self, request: SimRequest, future: SimFuture) -> None:
        with self._cv:
            if self._closed:
                raise QueueClosed("queue is closed")
            self._items.append((request, future))
            self._cv.notify_all()

    def _span(self, name: str):
        return (obs_trace.span(name) if self._spans
                else contextlib.nullcontext())

    def drain(self, max_n: int, wait_s: float = 0.1,
              linger_s: float = 0.0) -> list:
        """Return up to ``max_n`` queued ``(request, future)`` pairs.

        Empty list means: nothing arrived within ``wait_s`` (poll again,
        or stop if ``closed``).
        """
        deadline = time.monotonic() + wait_s
        with self._cv:
            if not self._items and not self._closed and wait_s > 0:
                with self._span(self._wait_span):
                    while not self._items and not self._closed:
                        remaining = deadline - time.monotonic()
                        if remaining <= 0 or not self._cv.wait(remaining):
                            break
            if not self._items:
                return []
        if linger_s > 0:
            with self._span(self._linger_span):
                time.sleep(linger_s)
        with self._cv:
            taken, self._items = (self._items[:max_n],
                                  self._items[max_n:])
        if taken and self._wait_hist is not None and obs_state.enabled():
            # claim-time residency; a requeued item is observed once per
            # claim, each time with its cumulative age since submission
            now = time.monotonic()
            for req, _ in taken:
                self._wait_hist.observe(max(0.0, now - req.submitted_at))
                if req.trace:
                    obs_trace.TRACER.record(self._span_name, req.trace,
                                            t0=req.submitted_at, t1=now,
                                            attrs={"stream": req.stream,
                                                   "seed": req.seed})
        return taken

    def restore(self, items: list) -> None:
        """Put claimed ``(request, future)`` pairs back at the front of
        the queue (original order preserved), waking any drainer.

        Unlike ``put`` this succeeds on a closed queue: ``close()``
        rejects new submissions, but a restored item is not new — it
        was admitted once and its future is owned by a waiting client.
        Items whose future is already fulfilled (e.g. failed by a
        deadline sweep while in flight) are dropped, which is what makes
        a requeue-or-fail race settle each future exactly once.
        """
        with self._cv:
            live = [(r, f) for r, f in items if not f.done()]
            if live:
                self._items[:0] = live
                self._cv.notify_all()

    def close(self) -> None:
        """Stop accepting new requests; queued ones remain drainable."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
