"""Span-based request tracing with a bounded ring buffer.

A *trace context* is a tiny JSON-able dict ``{"trace_id", "span_id"}``
minted once per request (:func:`mint`, at ``SimClient.submit`` or on
first touch server-side) and carried as an optional field of the RPC
wire envelope — so one request's timeline (submitted → admitted →
queued → routed/spilled/preempted → dispatched → batched-with-whom →
completed) stitches across the client, daemon, and worker processes.

Each process records spans into its own :class:`Tracer` — a
``collections.deque(maxlen=...)`` ring buffer, so memory is bounded
and old spans fall off the back.  Spans store monotonic times
(durations are exact within a process) plus a wall-clock conversion
through the per-process anchor (``repro.obs.clock``) for cross-process
alignment, and export to chrome://tracing / Perfetto JSON
(:func:`to_perfetto`).

Recording is gated on the global switch (``repro.obs.state``): when
disabled, :func:`mint` returns ``None`` and recorders no-op — the
hook that makes the bit-equality pin and the overhead bench's
"uninstrumented" arm honest.

*Process spans* (:func:`span`) time the program's own steps — an
engine call and its phases, the serve dispatch thread's waits and
buckets — on every call, traced request or not.  Each is a
``jax.profiler.TraceAnnotation`` when JAX is loaded, so it lands on
the profiler's host plane on the device ops' clock, and its duration
goes into one process-wide registry (:data:`SPANS`), keyed by its path
from its root span.  A root tree in which JAX compiled is counted
under ``<root>.compiled`` instead of being observed, so the
histograms hold steady-state steps only (:func:`watch_compiles`).
"""

from __future__ import annotations

import collections
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from . import clock, state
from .metrics import MetricsRegistry, log_bounds

__all__ = ["mint", "Tracer", "TRACER", "set_service", "to_perfetto",
           "DEFAULT_CAPACITY", "span", "SPANS", "SPAN_BOUNDS",
           "watch_compiles"]

DEFAULT_CAPACITY = 4096


def _hex_id(n_bytes: int) -> str:
    return os.urandom(n_bytes).hex()


def mint(parent: Optional[dict] = None) -> Optional[dict]:
    """Mint a trace context.  With a ``parent`` context, the trace id
    is inherited and a fresh span id allocated; otherwise both are new.
    Returns ``None`` when observability is disabled — callers pass the
    context along unconditionally and ``None`` flows through as
    "untraced"."""
    if not state.enabled():
        return None
    if parent and parent.get("trace_id"):
        return {"trace_id": str(parent["trace_id"]),
                "span_id": _hex_id(4)}
    return {"trace_id": _hex_id(8), "span_id": _hex_id(4)}


class Tracer:
    """Per-process span recorder over a bounded ring buffer."""

    def __init__(self, service: Optional[str] = None,
                 capacity: int = DEFAULT_CAPACITY):
        self.service = service or f"pid{os.getpid()}"
        self._lock = threading.Lock()
        self._spans: collections.deque = collections.deque(maxlen=capacity)

    # -- recording ---------------------------------------------------

    def record(self, name: str, trace: Optional[dict],
               t0: Optional[float] = None, t1: Optional[float] = None,
               attrs: Optional[dict] = None) -> None:
        """Record one span.  ``trace`` is a context dict (no-op when
        ``None`` or when observability is disabled).  ``t0``/``t1`` are
        ``time.monotonic()`` readings from THIS process; both default
        to now, making the span an instant event.  Retroactive spans
        (e.g. queue residency, recorded at claim time with the enqueue
        timestamp as ``t0``) are the intended use of passing ``t0``."""
        if trace is None or not state.enabled():
            return
        now = time.monotonic()
        m0 = now if t0 is None else t0
        m1 = now if t1 is None else t1
        span = {
            "name": name,
            "trace_id": trace.get("trace_id"),
            "span_id": _hex_id(4),
            "parent_id": trace.get("span_id"),
            "service": self.service,
            "pid": os.getpid(),
            "t0": m0,
            "t0_wall": clock.to_wall(m0),
            "dur_s": max(0.0, m1 - m0),
            "attrs": dict(attrs) if attrs else {},
        }
        with self._lock:
            self._spans.append(span)

    def event(self, name: str, trace: Optional[dict],
              attrs: Optional[dict] = None) -> None:
        """An instant (zero-duration) span."""
        self.record(name, trace, attrs=attrs)

    # -- reading -----------------------------------------------------

    def spans(self, trace_id: Optional[str] = None,
              limit: Optional[int] = None) -> List[dict]:
        """Recorded spans, oldest first, optionally filtered to one
        trace.  Returns copies — safe to mutate/serialize."""
        with self._lock:
            out = [dict(s) for s in self._spans
                   if trace_id is None or s["trace_id"] == trace_id]
        if limit is not None:
            out = out[-limit:]
        return out

    def dump(self, trace_id: Optional[str] = None,
             limit: Optional[int] = None) -> dict:
        """Wire-ready dump: the process anchor plus span list.  This is
        what the worker ``trace`` RPC returns and what the daemon
        stitches across processes."""
        return {"service": self.service, "anchor": clock.anchor(),
                "spans": self.spans(trace_id, limit)}

    def traces(self, limit: int = 50) -> List[dict]:
        """Most-recent distinct traces (newest first): id, span count,
        first/last wall time, and the span names seen."""
        by_id: Dict[str, dict] = {}
        order: List[str] = []
        for s in self.spans():
            tid = s["trace_id"]
            rec = by_id.get(tid)
            if rec is None:
                rec = by_id[tid] = {"trace_id": tid, "n_spans": 0,
                                    "t0_wall": s["t0_wall"],
                                    "t1_wall": s["t0_wall"], "names": []}
                order.append(tid)
            rec["n_spans"] += 1
            rec["t0_wall"] = min(rec["t0_wall"], s["t0_wall"])
            rec["t1_wall"] = max(rec["t1_wall"], s["t0_wall"] + s["dur_s"])
            if s["name"] not in rec["names"]:
                rec["names"].append(s["name"])
        return [by_id[tid] for tid in reversed(order)][:limit]

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()


# The per-process default tracer.  Components record into this unless
# handed an explicit Tracer; daemon/worker mains name it via
# set_service so merged timelines read "daemon" / "worker3".
TRACER = Tracer()


def set_service(name: str) -> None:
    TRACER.service = str(name)


def to_perfetto(spans: List[dict]) -> dict:
    """Convert span dicts (from any mix of processes) to
    chrome://tracing "trace event" JSON — load the result in Perfetto
    or chrome://tracing.  Rows group by (service, trace) so each
    request reads as one horizontal timeline per process."""
    events = []
    pids: Dict[str, int] = {}
    tids: Dict[str, int] = {}
    for s in spans:
        svc = str(s.get("service", s.get("pid", "?")))
        pid = pids.setdefault(svc, len(pids) + 1)
        tid = tids.setdefault(str(s.get("trace_id")), len(tids) + 1)
        args: Dict[str, Any] = {"trace_id": s.get("trace_id"),
                                "span_id": s.get("span_id")}
        args.update(s.get("attrs") or {})
        dur_us = float(s.get("dur_s", 0.0)) * 1e6
        events.append({
            "name": s["name"],
            "cat": svc,
            "ph": "X",
            "ts": float(s["t0_wall"]) * 1e6,
            "dur": max(dur_us, 1.0),        # sub-µs spans stay visible
            "pid": pid,
            "tid": tid,
            "args": args,
        })
        pids.setdefault(svc, pid)
    meta = [{"name": "process_name", "ph": "M", "pid": p,
             "args": {"name": svc}} for svc, p in pids.items()]
    return {"traceEvents": meta + events, "displayTimeUnit": "ms"}


# ---------------------------------------------------------------------------
# Process spans
# ---------------------------------------------------------------------------

# Every process span's duration, keyed by its path from its root span
# (``serve.dispatch/engine.run_batch/engine.device_wait``); work counts
# under ``<path>.items``, compiled root trees under ``<root>.compiled``.
SPANS = MetricsRegistry()
SPAN_BOUNDS = log_bounds(1e-6, 1e3, 10)      # 1 µs .. 17 min, 10 a decade

# The JAX events that mean "this call compiled": the ones the
# benchmark harness counts as compiles in its window.
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")

_local = threading.local()
_watch_lock = threading.Lock()
_watching = False


def _open_spans() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class Span:
    """One open process span; use through :func:`span`.

    ``parent`` is the innermost span open on this thread when it
    entered, ``root`` the outermost.  A root buffers its tree's
    observations and flushes them when it closes — into the histograms,
    or, if JAX compiled anywhere in the tree, into one
    ``<root>.compiled`` count."""

    __slots__ = ("name", "path", "parent", "root", "t0", "t1", "items",
                 "traces", "attrs", "compiled", "_buf", "_ann")

    def __init__(self, name: str, trace=None, items: int = 0):
        self.name = name
        self.items = items
        if isinstance(trace, dict):
            self.traces = [trace]
        else:
            self.traces = [t for t in trace or () if t]
        self.attrs: dict = {}
        self._ann = None

    def __enter__(self) -> "Span":
        stack = _open_spans()
        self.parent = stack[-1] if stack else None
        if self.parent is None:
            self.path, self.root = self.name, self
            self.compiled, self._buf = False, []
        else:
            self.path = f"{self.parent.path}/{self.name}"
            self.root = self.parent.root
        jax = sys.modules.get("jax")
        if jax is not None:
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        stack.append(self)
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.monotonic()
        _open_spans().pop()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self.record_requests()
        root = self.root
        root._buf.append((self.path, self.t1 - self.t0, self.items))
        if root is self:
            _flush(self)
        return False

    def count(self, name: str, n: int) -> None:
        """Add ``n`` to the counter ``<path>.<name>`` when the tree
        flushes, under the same rule as ``items``: not at all if JAX
        compiled in the tree."""
        self.root._buf.append((f"{self.path}.{name}", None, n))

    def record_requests(self, attrs: Optional[dict] = None) -> None:
        """Record each traced request's ``TRACER`` span now, from the
        span's start: at the latest when the span closes, earlier when
        a request's timeline must end before its future settles."""
        if attrs is not None:
            self.attrs = attrs
        traces, self.traces = self.traces, []
        now = time.monotonic()
        for trace in traces:
            TRACER.record(self.name, trace, t0=self.t0, t1=now,
                          attrs=self.attrs)


class _NoSpan:
    """What :func:`span` returns with the switch off: records nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def count(self, name: str, n: int) -> None:
        pass

    def record_requests(self, attrs: Optional[dict] = None) -> None:
        pass


_NO_SPAN = _NoSpan()


def span(name: str, trace=None, items: int = 0):
    """A process span around a step of the program::

        with obs.span("engine.run_sweep", items=n_lanes):
            ...

    ``trace`` is a request's trace context (or a list of them, one per
    request the step serves): each also gets a ``TRACER`` span of the
    same name.  ``items`` counts the step's work (requests, lanes)
    under ``<path>.items``.  A no-op when the switch is off."""
    if not state.enabled():
        return _NO_SPAN
    return Span(name, trace, items)


def _flush(root: Span) -> None:
    if root.compiled:
        SPANS.counter(f"{root.path}.compiled").inc()
        return
    for path, dur, items in root._buf:
        if dur is None:                 # a count (Span.count)
            SPANS.counter(path).inc(items)
            continue
        SPANS.histogram(path, SPAN_BOUNDS).observe(dur)
        if items:
            SPANS.counter(f"{path}.items").inc(items)


def _on_compile(event: str, duration: float, **_) -> None:
    if event not in COMPILE_EVENTS or not state.enabled():
        return
    stack = getattr(_local, "stack", None)
    if stack:
        stack[0].compiled = True
        SPANS.histogram("jax.compile_s", SPAN_BOUNDS).observe(duration)


def watch_compiles(monitoring) -> None:
    """Mark the open root span of the thread that compiles (``monitoring``
    is ``jax.monitoring``; the JAX side calls this once, and repeats are
    no-ops — ``repro.obs`` itself never imports JAX)."""
    global _watching
    with _watch_lock:
        if _watching:
            return
        _watching = True
    monitoring.register_event_duration_secs_listener(_on_compile)

