"""The benchmark's harness: one cell, one process, one result line.

Everything that belongs to one configuration, traffic mix or per-layer metric
lives in a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``   one deployment (sizes, guarantees, source);
* ``bench/traffic/<traffic>.json``  one traffic mix; its ``driver`` key names
  the way load is offered;
* ``bench/drivers/<driver>.py``     one way of offering load: a ``Driver``
  class with ``setup``, ``warm``, ``window``, ``stats``, ``release`` and
  ``check`` (see ``drivers/sweep_closed.py``);
* ``bench/metrics/<metric>.py``     one per-layer metric: ``read(run)``
  returns the number, or ``None`` when the run has nothing to read.

A run: set up and warm every shape the window uses (``setup_s``), measure
for ``--seconds`` with no compile inside, read the device's peak memory,
free the program's state, and compare what the window produced with the
plain reference (``bench/reference.py``).  With ``--trace 1`` the window runs
under the profiler and the line carries the per-layer metrics instead of
the end-to-end ones.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional

ROOT = Path(__file__).resolve().parent.parent


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Check:
    """One number that decides ``correct``, beside its limit."""
    name: str
    value: float
    limit: float
    at_least: bool = False        # the number must reach the limit

    @property
    def ok(self) -> bool:
        if math.isnan(self.value):
            return False
        return (self.value >= self.limit if self.at_least
                else self.value <= self.limit)

    def line(self) -> str:
        op = ">=" if self.at_least else "<="
        return (f"check {self.name} {self.value!r} {op} {self.limit!r} "
                f"{'ok' if self.ok else 'FAILED'}")


@dataclass
class Cell:
    """What a driver is given: the cell's files, its seed and its chips."""
    name: str
    config: dict
    traffic: dict
    seed: int
    chips: int
    rehearsal: bool = False       # CPU run at the sizes of ``overrides``
    log: list = field(default_factory=list)
    tracer: Optional[object] = None

    @property
    def tracing(self) -> bool:
        return self.tracer is not None

    def start_trace(self) -> None:
        """Start the traced part of the window here (a traced run only)."""
        if self.tracer is not None:
            self.tracer.start()

    def end_trace(self) -> None:
        """End the traced part of the window here; the rest runs untraced,
        so that the trace stays small."""
        if self.tracer is not None:
            self.tracer.end()

    def note(self, text: str) -> None:
        """A line for standard error, printed before the checks."""
        self.log.append(text)


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_module(path: Path):
    """Import a file by path (names may hold dots, e.g. ``serve.occupancy``)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(spec: dict, workload: str, root: Path = ROOT):
    """The cell's entry, configuration file, traffic file and driver, found
    by name under ``root``."""
    bench = root / "bench"
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((bench / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    driver = load_module(bench / "drivers" / f"{traffic['driver']}.py")
    return w, config, traffic, driver


def cell_metrics(spec: dict, workload: str, trace: bool) -> list:
    """The metric entries this cell reports: its end-to-end metrics, or
    with ``trace`` its per-layer metrics."""
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if workload in m.get("workloads", [workload] if m["moves"]
                                 in moved else [])]


def enable_compile_cache(jax) -> None:
    """JAX's persistent cache at the checkout's fixed ``.jax_cache``, for
    every program however quick it compiles."""
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts JAX's traces and backend compiles while armed."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.armed = False
        self.counts = {e.rsplit("/", 1)[1]: 0 for e in self.EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed and event in self.EVENTS:
            self.counts[event.rsplit("/", 1)[1]] += 1


def peak_memory(jax, devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def open_cell(workload: str, seed: int, overrides: Optional[dict] = None,
              root: Path = ROOT):
    """Find the cell's files, check the chips and build its driver:
    ``(entry, cell, driver)``.

    ``overrides`` is the rehearsal argument the tests use: it lets the run
    go on without a TPU, on the CPU, with the configuration's and traffic's
    keys replaced by the values it gives (``{"config": {...},
    "traffic": {...}}``) so that the whole run fits a test's time.
    ``root`` is the checkout whose ``BENCHMARK.json`` and ``bench/`` files
    name the cell.
    """
    spec = load_spec(root)
    entry, config, traffic, driver_mod = resolve(spec, workload, root)
    rehearsal = overrides is not None
    if rehearsal:
        config = {**config, **overrides.get("config", {})}
        traffic = {**traffic, **overrides.get("traffic", {})}
    import jax
    if not rehearsal:
        enable_compile_cache(jax)
    devices = jax.devices()
    if not rehearsal and devices[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform "
                            f"{devices[0].platform!r})")
    if len(devices) < entry["chips"]:
        raise NoAccelerator(f"the cell asks for {entry['chips']} chips, "
                            f"JAX sees {len(devices)}")
    cell = Cell(workload, config, traffic, seed, entry["chips"], rehearsal)
    return entry, cell, driver_mod.Driver(cell)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, overrides: Optional[dict] = None,
             root: Path = ROOT) -> tuple:
    """Run one cell; returns ``(result, stderr_lines)``.  ``overrides``
    and ``root`` as for ``open_cell``."""
    import jax
    spec = load_spec(root)
    entry, cell, driver = open_cell(workload, seed, overrides, root)
    devices = jax.devices()
    dev = devices[0]
    counter = CompileCounter(jax)
    t_jax = time.monotonic()
    driver.setup()
    t_data = time.monotonic()
    driver.warm()
    t_warm = time.monotonic()
    setup_s = t_warm - t_start
    cell.note(f"setup_s start_to_jax={t_jax - t_start!r} "
              f"data_and_pool={t_data - t_jax!r} warm={t_warm - t_data!r}")

    if trace:
        from bench import trace as tracing
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        cell.tracer = tracing.Tracer(tdir)
    counter.armed = True
    out = driver.window(seconds)
    counter.armed = False
    reduction = None
    if trace:
        cell.end_trace()
        reduction = tracing.reduce(tdir, entry["chips"])
        shutil.rmtree(tdir, ignore_errors=True)
    cell.note("compiles_in_window " + json.dumps(counter.counts))
    mem = peak_memory(jax, devices[:entry["chips"]])
    stats = driver.stats()
    driver.release()
    checks = driver.check()
    correct = bool(checks) and all(c.ok for c in checks)

    units = {m["name"]: m["unit"] for m in spec["end_to_end"]
             + spec["per_layer"]}
    metrics = {}
    if not trace:
        values = dict(out["metrics"], setup_s=setup_s)
        for m in cell_metrics(spec, workload, trace=False):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": units[m["name"]]}
    else:
        run = {"workload": workload, "stats": stats, "trace": reduction,
               "device_kind": dev.device_kind, "chips": entry["chips"]}
        cell.note("trace " + json.dumps(
            {k: v for k, v in reduction.items()
             if k not in ("breakdown", "ops_s")}))
        for m in cell_metrics(spec, workload, trace=True):
            reader = load_module(root / "bench" / "metrics"
                                 / f"{m['name']}.py")
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduction["busy_s"]
        device["window_s"] = reduction["window_s"]
        result["breakdown"] = reduction["breakdown"]
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    lines = cell.log + [c.line() for c in checks]
    return result, lines


def main(argv, t_start: float) -> int:
    ap = argparse.ArgumentParser(prog="bench/run.py",
                                 description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, lines = run_cell(args.workload, args.seed, args.seconds,
                                 bool(args.trace), t_start)
    except NoAccelerator as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
