"""Dynamic batcher: coalesce compatible requests into bucketed batches.

Pure planning logic (no jax, no threads — the server composes this with
``RequestQueue``): requests are grouped by their *static* configuration
(everything that shapes the compiled program, plus the schedule CLASS —
stationary vs scheduled; per-lane schedule stacking means a batch can
mix scenarios — and the priority class), chunked to the
server's ``max_batch``, and padded up to a small set of bucket sizes so
steady-state traffic re-uses a handful of compiled executables instead
of tracing one per batch occupancy.  Planned buckets come back in
dispatch order: higher-priority buckets first, FIFO within a bucket.

Bucketing rules (docs/serving.md#bucketing):

* bucket sizes are the powers of two ``2, 4, 8, ... , max_batch`` (plus
  ``max_batch`` itself when it is not a power of two);
* a batched chunk of ``n`` requests is padded to the smallest bucket
  ``>= n`` by repeating the last request's (seed, budget) lane — a valid
  configuration, so the padded lanes trace and execute identically and
  their results are simply dropped;
* the minimum bucket is 2, even for a lone request: batch width 1 would
  execute the *solo* program family and a request's bits would then
  depend on how busy the server was (see docs/serving.md#determinism);
* ``exact`` buckets are never padded — each lane runs the solo cached
  program anyway, so padding would buy nothing.

>>> bucket_sizes(16)
(2, 4, 8, 16)
>>> bucket_sizes(12)
(2, 4, 8, 12)
>>> bucket_size(5, bucket_sizes(16))
8
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

from .. import obs
from .queue import SimRequest

__all__ = ["bucket_sizes", "bucket_size", "group_key", "plan_buckets",
           "Bucket", "DynamicBatcher"]


def bucket_sizes(max_batch: int) -> Tuple[int, ...]:
    """Allowed padded batch widths for a given ``max_batch`` (>= 2)."""
    if max_batch < 2:
        raise ValueError(f"max_batch must be >= 2, got {max_batch}")
    sizes = []
    b = 2
    while b < max_batch:
        sizes.append(b)
        b *= 2
    sizes.append(max_batch)
    return tuple(sizes)


def bucket_size(n: int, sizes: Sequence[int]) -> int:
    """Smallest allowed bucket >= ``n`` (``n`` must not exceed the max)."""
    for b in sizes:
        if b >= n:
            return b
    raise ValueError(f"chunk of {n} exceeds the largest bucket "
                     f"{sizes[-1]} — chunk to max_batch first")


def _cfg_static_key(cfg, T: int) -> tuple:
    """The SimConfig fields that shape the compiled program, via the one
    shared definition ``SimConfig.static_key`` (duck-typed — no jax
    import here), plus the ``sweep_sharded`` dispatch knob so requests
    that pin a dispatch never share a bucket with ones that don't."""
    if cfg is None:
        return ("default",)
    return cfg.static_key(T) + (cfg.sweep_sharded,)


def group_key(req: SimRequest) -> tuple:
    """Requests sharing this key can ride in one batch: same stream
    (= same (K, n_stream) arrays), same algorithm, same horizon, same
    static config, same execution mode, same **schedule class**
    (stationary vs scheduled — NOT the scenario itself: compiled
    schedules stack per lane as jit arguments, so `run_batch` serves any
    mix of scenarios in one program and tenants on different schedules
    coalesce into one bucket), and same priority (a bucket dispatches as
    a unit, so a low-priority co-tenant would otherwise ride ahead of
    its class).  Seed, budget and scenario — the flat batch axis — are
    deliberately absent.

    The class bit is ``req.scenario is not None``: scheduled and
    stationary requests compile different programs, and keeping the
    stationary class pure preserves the by-construction bit-equality of
    scenario-free traffic (``SimServer.submit`` normalizes all-neutral
    scenarios like ``"constant"`` to ``None``, so they land here too).
    """
    return (req.stream, req.algo, req.T, req.exact,
            _cfg_static_key(req.cfg, req.T), req.scenario is not None,
            req.priority)


@dataclass
class Bucket:
    """One planned dispatch: ``n`` real requests padded to ``size`` lanes
    (``size == n`` for exact buckets)."""
    key: tuple
    requests: list                     # [(SimRequest, SimFuture)]
    size: int

    @property
    def n(self) -> int:
        return len(self.requests)

    @property
    def n_padding(self) -> int:
        return self.size - self.n

    @property
    def exact(self) -> bool:
        return self.key[3]

    @property
    def scheduled(self) -> bool:
        """True when the bucket's lanes run (per-lane) scenario
        schedules; each request carries its own ``scenario``."""
        return self.key[5]

    @property
    def priority(self) -> int:
        return self.key[6]

    def seeds(self) -> list:
        """Per-lane seeds, padding included (repeat of the last lane)."""
        seeds = [r.seed for r, _ in self.requests]
        return seeds + [seeds[-1]] * self.n_padding


def plan_buckets(items: Sequence, max_batch: int = 16) -> list:
    """Coalesce drained ``(request, future)`` pairs into ``Bucket``s.

    Buckets come back in dispatch order: **higher-priority buckets
    first** (``SimRequest.priority``; the stable sort preserves arrival
    order between equal priorities), FIFO within each bucket.  Within a
    priority class, arrival order is preserved within and across groups
    (first-come first-batched); each group is chunked to ``max_batch``
    and each chunk padded to its bucket size.  This is pure planning —
    no waiting, no dispatch.
    """
    sizes = bucket_sizes(max_batch)
    groups: dict = {}
    order = []
    for req, fut in items:
        key = group_key(req)
        if key not in groups:
            groups[key] = []
            order.append(key)
        groups[key].append((req, fut))
    buckets = []
    for key in order:
        pending = groups[key]
        for i in range(0, len(pending), max_batch):
            chunk = pending[i:i + max_batch]
            size = (len(chunk) if key[3]          # exact: no padding
                    else bucket_size(len(chunk), sizes))
            buckets.append(Bucket(key=key, requests=chunk, size=size))
    buckets.sort(key=lambda b: -b.priority)       # stable: FIFO per class
    return buckets


class DynamicBatcher:
    """Drain-and-plan loop: the server thread's view of the queue.

    ``max_wait_ms`` is the coalescing window: once at least one request
    is queued, the batcher lingers that long so a concurrent burst of
    submissions lands in the same drain (and therefore the same
    buckets).  Zero disables lingering — whatever is queued at drain
    time forms the batch.
    """

    def __init__(self, queue, max_batch: int = 16,
                 max_wait_ms: float = 2.0, registry=None):
        if max_batch < 2:
            raise ValueError(f"max_batch must be >= 2, got {max_batch}")
        self.queue = queue
        self.max_batch = max_batch
        self.max_wait_ms = max_wait_ms
        # observe-only: counts requests quarantined off a drain (see
        # next_buckets) — planning itself stays untouched
        self._quarantined = (None if registry is None
                             else registry.counter("server.quarantined"))

    def next_buckets(self, wait_s: float = 0.1) -> list:
        """Block up to ``wait_s`` for traffic; return planned buckets
        (empty list if none arrived — poll again or shut down).

        A request whose group key cannot even be computed (a malformed
        ``cfg`` that slipped past submit-side validation) is quarantined
        onto its own future instead of poisoning the drain: one bad
        request must never lose its co-drained neighbors or kill the
        dispatch thread."""
        items = self.queue.drain(max_n=1_000_000, wait_s=wait_s,
                                 linger_s=self.max_wait_ms / 1e3)
        if not items:
            return []
        with obs.span("server.plan"):
            good = []
            for req, fut in items:
                try:
                    group_key(req)
                except Exception as exc:            # noqa: BLE001
                    if self._quarantined is not None:
                        self._quarantined.inc()
                    fut.set_exception(exc)
                    continue
                good.append((req, fut))
            return plan_buckets(good, self.max_batch)
