"""The profiler's trace of a traced window, reduced to the numbers the
per-layer metrics read.

``Tracer`` runs JAX's profiler over the part of the window its driver
chooses (``cell.start_trace()`` / ``cell.end_trace()``), so that the trace
stays small, and marks it with a host annotation (``bench.traced``).  ``reduce``
reads the ``.xplane.pb`` file with ``jax.profiler.ProfileData``:

* the device planes (``/device:TPU:<n>``) of the chips the cell uses, and on
  each its ``XLA Ops`` line (one event per operation run) and its
  ``XLA Modules`` line (one event per executable run);
* busy time: the union of the operation intervals inside the traced part,
  averaged over the chips;
* per operation name and per executable name, the device time summed over
  the chips; the ``client_eval`` kernel's time and launch count (operations
  whose name or HLO metadata names ``client_eval``); the breakdown ranks
  operations without the loops and calls whose events span other
  operations;
* the longest idle gaps on the first chip, each named by the innermost
  ``bench.*`` host annotation open at its middle.
"""

from __future__ import annotations

import glob
import os
import re

KERNEL = re.compile(r"client_eval")
CONTAINER = re.compile(r"%?(while|conditional|call)\b")
HOST_PREFIX = "bench."


class Tracer:
    """The traced part of a window: ``start`` and ``end`` it once each (both
    are idempotent; ``end`` before ``start`` traces nothing)."""

    def __init__(self, tdir: str):
        self.tdir = tdir
        self.ann = None
        self.done = False

    def start(self) -> None:
        import jax
        if self.ann is not None or self.done:
            return
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.tdir, profiler_options=opts)
        self.ann = jax.profiler.TraceAnnotation("bench.traced")
        self.ann.__enter__()

    def end(self) -> None:
        import jax
        if self.ann is None or self.done:
            self.done = True
            return
        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.done = True


def _union(intervals):
    total, end = 0, None
    start = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            if end is not None:
                total += end - start
            start, end = s, e
        else:
            end = max(end, e)
    if end is not None:
        total += end - start
    return total


def _gaps(intervals, w0, w1):
    out, cur = [], w0
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if w1 > cur:
        out.append((cur, w1))
    return out


def _stat_text(event) -> str:
    try:
        return " ".join(str(v) for _, v in event.stats)
    except Exception:                           # noqa: BLE001 - no stats
        return ""


def reduce(tdir: str, chips: int) -> dict:
    """Reduce the trace the profiler wrote under ``tdir``."""
    import jax
    files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {tdir}")
    return reduce_profile(jax.profiler.ProfileData.from_file(
        max(files, key=os.path.getmtime)), chips)


def reduce_profile(data, chips: int) -> dict:
    """Reduce a ``jax.profiler.ProfileData`` (see the module docstring)."""
    host = []                                   # (start, end, name)
    devices = []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            devices.append((int(m.group(1)), plane))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((ev.start_ns, ev.end_ns, ev.name))
    window = [h for h in host if h[2] == "bench.traced"]
    if not window:
        raise RuntimeError("no bench.traced annotation in the trace")
    w0, w1 = window[0][0], window[0][1]
    devices = [p for _, p in sorted(devices, key=lambda d: d[0])][:chips]
    ops_s, modules_s, busy = {}, {}, []
    kernel_s, kernel_n, first_busy = 0.0, 0, []
    is_kernel = {}
    for i, plane in enumerate(devices):
        lines = {ln.name: ln for ln in plane.lines}
        spans = []
        for ev in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e <= s:
                continue
            spans.append((s, e))
            d = (e - s) / 1e9
            # a TPU op event is named by its whole HLO instruction: keep
            # the instruction's name, before " = "
            name = ev.name.split(" = ", 1)[0]
            ops_s[name] = ops_s.get(name, 0.0) + d
            if name not in is_kernel:
                is_kernel[name] = bool(KERNEL.search(ev.name)
                                       or KERNEL.search(_stat_text(ev)))
            if is_kernel[name]:
                kernel_s += d
                kernel_n += 1
        for ev in (lines["XLA Modules"].events
                   if "XLA Modules" in lines else ()):
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e > s:
                name = re.sub(r"\(\d+\)$", "", ev.name)
                modules_s[name] = modules_s.get(name, 0.0) + (e - s) / 1e9
        busy.append(_union(spans) / 1e9)
        if i == 0:
            first_busy = spans
    gaps = []
    longest = sorted(_gaps(first_busy, w0, w1), key=lambda g: g[0] - g[1])
    for s, e in longest[:10]:
        mid = (s + e) / 2
        inner = [h for h in host if h[0] <= mid <= h[1]
                 and h[2] != "bench.traced"]
        label = (min(inner, key=lambda h: h[1] - h[0])[2] if inner
                 else "no bench span open")
        gaps.append((label, (e - s) / 1e9))
    # a loop's event spans the ops of its body: rank the ops themselves
    top_ops = sorted(((k, v) for k, v in ops_s.items()
                      if not CONTAINER.match(k)), key=lambda kv: -kv[1])
    n = max(len(devices), 1)
    return {"window_s": (w1 - w0) / 1e9,
            "busy_s": sum(busy) / n,
            "busy_per_chip_s": busy,
            "chips_traced": len(devices),
            "ops_s": ops_s, "modules_s": modules_s,
            "kernel_s": kernel_s, "kernel_launches": kernel_n,
            "kernel_ops": sorted(k for k, v in is_kernel.items() if v),
            "breakdown": {"device_ops": [[k, v] for k, v in top_ops[:10]],
                          "idle_gaps": [[k, v] for k, v in gaps[:10]]}}
