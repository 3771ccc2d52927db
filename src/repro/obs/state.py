"""The one global observability switch.

``repro.obs`` is observe-only by contract (docs/observability.md): no
engine result ever flows through it, so turning it off must change
*nothing* but the telemetry.  The switch exists for exactly two
consumers — the pinned bit-equality test (instrumented run == plain
run) and the ``serve.obs_overhead`` bench cell (enabled vs disabled
timing on the same traffic) — and it gates the *per-request* work
(span recording, trace-context minting, latency-histogram
observations) and the process spans (``repro.obs.span``).  Counters
and gauges that back ``status()``/``stats()`` stay live either way;
they replaced the old ad-hoc dicts and the control plane reads them.

The initial state comes from ``REPRO_OBS`` (default on; ``0``,
``false``, ``no``, ``off`` disable), so a subprocess fleet inherits
the choice through the environment.
"""

from __future__ import annotations

import contextlib
import os
import threading

__all__ = ["enabled", "enable", "disable", "set_enabled", "scoped"]

_lock = threading.Lock()
_enabled = os.environ.get("REPRO_OBS", "1").strip().lower() not in (
    "0", "false", "no", "off")


def enabled() -> bool:
    """Is per-request telemetry (spans, latency histograms) on?"""
    return _enabled


def set_enabled(flag: bool) -> bool:
    """Flip the switch; returns the previous state."""
    global _enabled
    with _lock:
        prev = _enabled
        _enabled = bool(flag)
    return prev


def enable() -> None:
    set_enabled(True)


def disable() -> None:
    set_enabled(False)


@contextlib.contextmanager
def scoped(flag: bool):
    """Temporarily force the switch (bench/test helper)."""
    prev = set_enabled(flag)
    try:
        yield
    finally:
        set_enabled(prev)
