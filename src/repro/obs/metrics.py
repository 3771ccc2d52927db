"""Thread-safe typed metrics: ``Counter`` / ``Gauge`` / ``Histogram``
behind a ``MetricsRegistry``, with mergeable snapshots.

Stdlib-only — the serve daemon composes this and must keep its
never-imports-jax property.  Three design rules:

* **Fixed, log-spaced histogram bounds** (:func:`log_bounds`).  Two
  histograms with the same bounds merge by summing bucket counts, so
  fleet-level percentiles (:func:`quantile`) come from merged
  per-worker snapshots without any process ever storing samples.
* **Snapshots are plain JSON-able dicts** — they ride the existing
  ``stats`` RPC unchanged, merge anywhere (:meth:`MetricsRegistry.merge`),
  and render to Prometheus text exposition (:func:`render_prometheus`).
* **Observe-only.**  Instruments record counts and seconds; they never
  hold references to engine results.
"""

from __future__ import annotations

import bisect
import math
import re
import threading
from typing import Callable, Dict, Iterable, Optional, Sequence, Tuple

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "log_bounds", "quantile", "render_prometheus",
]


def log_bounds(lo: float = 1e-4, hi: float = 1e3,
               per_decade: int = 3) -> Tuple[float, ...]:
    """Log-spaced histogram bucket upper bounds, ``lo`` .. ``hi``
    inclusive, ``per_decade`` buckets per decade.  The default covers
    100µs .. ~17min — queue waits and dispatch times across the fleet —
    in 22 buckets.  Every histogram sharing one bounds tuple is
    mergeable by bucket-count addition."""
    if not (lo > 0 and hi > lo and per_decade >= 1):
        raise ValueError(f"bad bounds spec lo={lo} hi={hi} "
                         f"per_decade={per_decade}")
    n = max(1, round(math.log10(hi / lo) * per_decade))
    return tuple(lo * (hi / lo) ** (i / n) for i in range(n + 1))


DEFAULT_BOUNDS = log_bounds()


class Counter:
    """Monotonically increasing integer.  ``inc`` returns the
    post-increment value (usable as an atomic sequence source)."""

    kind = "counter"
    __slots__ = ("name", "_lock", "_value")

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self._value = 0

    def inc(self, n: int = 1) -> int:
        with self._lock:
            self._value += n
            return self._value

    @property
    def value(self) -> int:
        with self._lock:
            return self._value


class Gauge:
    """A point-in-time value: either ``set()`` explicitly or backed by
    a callback (``set_fn``) evaluated lazily at snapshot time — the
    zero-per-event flavor used for queue depth/age."""

    kind = "gauge"
    __slots__ = ("name", "_lock", "_value", "_fn")

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self._value = 0.0
        self._fn: Optional[Callable[[], float]] = None

    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)
            self._fn = None

    def set_fn(self, fn: Callable[[], float]) -> None:
        with self._lock:
            self._fn = fn

    @property
    def value(self) -> float:
        with self._lock:
            fn = self._fn
            if fn is None:
                return self._value
        try:                                    # outside the lock: the
            return float(fn())                  # callback may take its
        except Exception:                       # owner's own lock
            return float("nan")


class Histogram:
    """Bucketed distribution over fixed bounds.  ``counts[i]`` is the
    number of observations ``<= bounds[i]``; the final slot is the
    overflow bucket.  Sum/min/max ride along for exact means."""

    kind = "histogram"
    __slots__ = ("name", "bounds", "_lock", "_counts", "_count", "_sum",
                 "_min", "_max")

    def __init__(self, name: str, bounds: Optional[Sequence[float]] = None):
        self.name = name
        self.bounds = tuple(bounds) if bounds is not None else DEFAULT_BOUNDS
        if list(self.bounds) != sorted(self.bounds) or not self.bounds:
            raise ValueError(f"histogram bounds must be sorted, non-empty: "
                             f"{self.bounds!r}")
        self._lock = threading.Lock()
        self._counts = [0] * (len(self.bounds) + 1)
        self._count = 0
        self._sum = 0.0
        self._min = math.inf
        self._max = -math.inf

    def observe(self, value: float) -> None:
        v = float(value)
        i = bisect.bisect_left(self.bounds, v)
        with self._lock:
            self._counts[i] += 1
            self._count += 1
            self._sum += v
            if v < self._min:
                self._min = v
            if v > self._max:
                self._max = v

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "bounds": list(self.bounds),
                "counts": list(self._counts),
                "count": self._count,
                "sum": self._sum,
                "min": None if self._count == 0 else self._min,
                "max": None if self._count == 0 else self._max,
            }


def quantile(hist: dict, q: float) -> Optional[float]:
    """Approximate quantile from a histogram *snapshot* (possibly the
    merge of many).  Linear interpolation inside the covering bucket;
    exact at the recorded min/max edges; ``None`` when empty."""
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile must be in [0, 1], got {q}")
    total = hist["count"]
    if total == 0:
        return None
    bounds, counts = hist["bounds"], hist["counts"]
    rank = q * total
    seen = 0
    for i, c in enumerate(counts):
        if seen + c >= rank and c > 0:
            hi = hist["max"] if i >= len(bounds) else bounds[i]
            lo = bounds[i - 1] if i > 0 else hist["min"]
            lo = min(lo, hi)
            frac = (rank - seen) / c
            # interpolate, clamped to the observed range (bucket upper
            # bounds can overshoot the true max)
            return max(hist["min"], min(hist["max"], lo + (hi - lo) * frac))
        seen += c
    return hist["max"]


class MetricsRegistry:
    """Get-or-create instrument registry.  Names are dotted
    (``daemon.admitted``, ``server.queue.wait_s``); re-requesting a
    name returns the same instrument, re-requesting it as a different
    type raises."""

    def __init__(self):
        self._lock = threading.Lock()
        self._instruments: Dict[str, object] = {}

    def _get(self, name: str, cls, *args):
        with self._lock:
            inst = self._instruments.get(name)
            if inst is None:
                inst = cls(name, *args)
                self._instruments[name] = inst
            elif not isinstance(inst, cls):
                raise ValueError(
                    f"instrument {name!r} already registered as "
                    f"{type(inst).__name__}, requested {cls.__name__}")
            return inst

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str,
                  bounds: Optional[Sequence[float]] = None) -> Histogram:
        return self._get(name, Histogram, bounds)

    def snapshot(self) -> dict:
        """A plain-dict snapshot: ``{"counters": {...}, "gauges": {...},
        "histograms": {...}}``.  JSON-able, wire-safe, mergeable."""
        with self._lock:
            insts = list(self._instruments.values())
        snap: dict = {"counters": {}, "gauges": {}, "histograms": {}}
        for inst in insts:
            if isinstance(inst, Counter):
                snap["counters"][inst.name] = inst.value
            elif isinstance(inst, Gauge):
                snap["gauges"][inst.name] = inst.value
            elif isinstance(inst, Histogram):
                snap["histograms"][inst.name] = inst.snapshot()
        return snap

    @staticmethod
    def merge(snapshots: Iterable[dict]) -> dict:
        """Merge snapshots from many processes: counters and gauges sum
        (depth gauges across workers add up to fleet depth), histograms
        sum bucket-wise.  A malformed or bounds-mismatched snapshot
        raises ``ValueError`` — callers merging over a fleet should
        validate/skip per worker so one partial snapshot (a worker
        SIGKILLed mid-reply) cannot wedge the merge."""
        out: dict = {"counters": {}, "gauges": {}, "histograms": {}}
        for snap in snapshots:
            for name, v in snap.get("counters", {}).items():
                out["counters"][name] = out["counters"].get(name, 0) + int(v)
            for name, v in snap.get("gauges", {}).items():
                v = float(v)
                if v != v:                      # skip NaN callback reads
                    continue
                out["gauges"][name] = out["gauges"].get(name, 0.0) + v
            for name, h in snap.get("histograms", {}).items():
                acc = out["histograms"].get(name)
                if acc is None:
                    out["histograms"][name] = {
                        "bounds": list(h["bounds"]),
                        "counts": list(h["counts"]),
                        "count": int(h["count"]),
                        "sum": float(h["sum"]),
                        "min": h["min"], "max": h["max"],
                    }
                    continue
                if list(h["bounds"]) != acc["bounds"]:
                    raise ValueError(f"histogram {name!r}: bounds mismatch, "
                                     "snapshots are not mergeable")
                if len(h["counts"]) != len(acc["counts"]):
                    raise ValueError(f"histogram {name!r}: counts length "
                                     "mismatch")
                acc["counts"] = [a + int(b)
                                 for a, b in zip(acc["counts"], h["counts"])]
                acc["count"] += int(h["count"])
                acc["sum"] += float(h["sum"])
                for key, pick in (("min", min), ("max", max)):
                    a, b = acc[key], h[key]
                    acc[key] = (b if a is None else
                                a if b is None else pick(a, b))
        return out


_PROM_NAME = re.compile(r"[^a-zA-Z0-9_:]")


def _prom_name(name: str, prefix: str) -> str:
    full = f"{prefix}_{name}" if prefix else name
    return _PROM_NAME.sub("_", full)


def render_prometheus(snapshot: dict, prefix: str = "repro") -> str:
    """Prometheus text exposition (version 0.0.4) of a snapshot.
    Dotted instrument names flatten to underscores; counters carry the
    conventional ``_total`` suffix; histograms emit the cumulative
    ``_bucket{le=...}`` / ``_sum`` / ``_count`` series."""
    lines = []
    for name in sorted(snapshot.get("counters", {})):
        pn = _prom_name(name, prefix) + "_total"
        lines.append(f"# TYPE {pn} counter")
        lines.append(f"{pn} {snapshot['counters'][name]}")
    for name in sorted(snapshot.get("gauges", {})):
        pn = _prom_name(name, prefix)
        lines.append(f"# TYPE {pn} gauge")
        lines.append(f"{pn} {snapshot['gauges'][name]:.9g}")
    for name in sorted(snapshot.get("histograms", {})):
        h = snapshot["histograms"][name]
        pn = _prom_name(name, prefix)
        lines.append(f"# TYPE {pn} histogram")
        cum = 0
        for bound, c in zip(h["bounds"], h["counts"]):
            cum += c
            lines.append(f'{pn}_bucket{{le="{bound:.9g}"}} {cum}')
        lines.append(f'{pn}_bucket{{le="+Inf"}} {h["count"]}')
        lines.append(f"{pn}_sum {h['sum']:.9g}")
        lines.append(f"{pn}_count {h['count']}")
    return "\n".join(lines) + "\n"
