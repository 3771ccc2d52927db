"""Process spans (``repro.obs.span``): the step timings of the engine and
the serve dispatch thread, on the profiler's clock.

* **span units** — nesting, paths, self time, work counts, request
  spans, the switch, and ``repro.obs`` importing without JAX;
* **compile tagging** — a root tree in which JAX compiled is counted
  under ``<root>.compiled`` and kept out of the histograms;
* **the profiler clock** — a span is an annotation on the profiler's
  host plane, inside the session;
* **the program's spans** — every path the engine and the server
  record is catalogued, the worker's ``stats`` reply carries them, and
  runs with spans on are bit-equal to runs with spans off;
* **the benchmark's readers** — the four per-layer metrics that read
  the span record, on CPU rehearsals of their cells.
"""

from __future__ import annotations

import glob
import json
import math
import os
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import jax

from repro import obs

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _fresh_spans(monkeypatch):
    fresh = obs.MetricsRegistry()
    monkeypatch.setattr(obs.trace, "SPANS", fresh)
    monkeypatch.setattr(obs, "SPANS", fresh)
    prev = obs.set_enabled(True)
    obs.TRACER.clear()
    obs.watch_compiles(jax.monitoring)
    yield
    obs.set_enabled(prev)
    obs.TRACER.clear()


def _hists():
    return obs.SPANS.snapshot()["histograms"]


def _counters():
    return obs.SPANS.snapshot()["counters"]


def _self_times():
    """Per span path, its total less its direct children's totals."""
    h = _hists()
    own = {path: row["sum"] for path, row in h.items()}
    for path, row in h.items():
        parent = path.rpartition("/")[0]
        if parent in own:
            own[parent] -= row["sum"]
    return own


# ---------------------------------------------------------------------------
# span units
# ---------------------------------------------------------------------------

def test_span_nesting_paths_self_time_and_items():
    with obs.span("t.root", items=3) as root:
        time.sleep(0.01)
        with obs.span("t.a") as a:
            time.sleep(0.02)
            with obs.span("t.b", items=5):
                time.sleep(0.005)
        with obs.span("t.a"):
            pass
    assert a.parent is root and a.root is root and root.parent is None
    h = _hists()
    assert set(h) == {"t.root", "t.root/t.a", "t.root/t.a/t.b"}
    assert h["t.root"]["count"] == 1 and h["t.root/t.a"]["count"] == 2
    assert h["t.root"]["sum"] >= h["t.root/t.a"]["sum"] \
        >= h["t.root/t.a/t.b"]["sum"] >= 0.005
    assert _counters() == {"t.root.items": 3, "t.root/t.a/t.b.items": 5}
    own = _self_times()
    assert own["t.root"] == pytest.approx(
        h["t.root"]["sum"] - h["t.root/t.a"]["sum"])
    assert own["t.root/t.a"] == pytest.approx(
        h["t.root/t.a"]["sum"] - h["t.root/t.a/t.b"]["sum"])
    assert 0.008 <= own["t.root"] < h["t.root"]["sum"]
    assert own["t.root/t.a"] >= 0.015


def test_spans_on_two_threads_keep_their_own_trees():
    def worker():
        with obs.span("t.other"):
            with obs.span("t.leaf"):
                pass

    with obs.span("t.main"):
        th = threading.Thread(target=worker)
        th.start()
        th.join()
    assert set(_hists()) == {"t.main", "t.other", "t.other/t.leaf"}


def test_request_spans_record_once_each():
    a, b = obs.mint(), obs.mint()
    with obs.span("t.dispatch", trace=[a, None, b]) as sp:
        time.sleep(0.005)
        sp.record_requests({"outcome": "ok"})
        time.sleep(0.01)
    for ctx in (a, b):
        (s,) = obs.TRACER.spans(ctx["trace_id"])
        assert s["name"] == "t.dispatch" and s["attrs"] == {"outcome": "ok"}
        assert 0.005 <= s["dur_s"] < 0.015
    with obs.span("t.solo", trace=a):
        pass
    assert [s["name"] for s in obs.TRACER.spans(a["trace_id"])] == \
        ["t.dispatch", "t.solo"]


def test_switch_off_records_nothing():
    ctx = obs.mint()
    f = jax.jit(lambda x: x * 5 - 2)
    with obs.scoped(False):
        with obs.span("t.off", trace=ctx, items=4) as sp:
            with obs.span("t.child"):
                f(np.float32(1.0)).block_until_ready()
            sp.record_requests({"outcome": "ok"})
    assert obs.SPANS.snapshot() == {"counters": {}, "gauges": {},
                                    "histograms": {}}
    assert obs.TRACER.spans() == []


def test_obs_imports_without_jax():
    code = ("import sys, repro.obs as o\n"
            "with o.span('t.x', items=1):\n"
            "    pass\n"
            "assert o.SPANS.snapshot()['histograms']['t.x']['count'] == 1\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


# ---------------------------------------------------------------------------
# compile tagging
# ---------------------------------------------------------------------------

def test_compiled_tree_is_counted_not_observed():
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = np.float32(2.0)
    with obs.span("t.step"):
        with obs.span("t.call"):
            f(x).block_until_ready()
    assert _counters() == {"t.step.compiled": 1}
    h = _hists()
    assert set(h) == {"jax.compile_s"} and h["jax.compile_s"]["count"] >= 1
    with obs.span("t.step"):
        with obs.span("t.call"):
            f(x).block_until_ready()
    h = _hists()
    assert h["t.step"]["count"] == 1 and h["t.step/t.call"]["count"] == 1
    assert _counters() == {"t.step.compiled": 1}


def test_compile_on_another_thread_leaves_this_tree_steady():
    f = jax.jit(lambda x: x - 7.0)
    with obs.span("t.steady"):
        th = threading.Thread(
            target=lambda: f(np.float32(1.0)).block_until_ready())
        th.start()
        th.join()
    assert _hists()["t.steady"]["count"] == 1
    assert "t.steady.compiled" not in _counters()


# ---------------------------------------------------------------------------
# the profiler clock
# ---------------------------------------------------------------------------

def test_span_lands_on_the_profilers_host_plane():
    tdir = tempfile.mkdtemp(prefix="obs_spans_")
    t0 = time.monotonic()
    jax.profiler.start_trace(tdir)
    try:
        time.sleep(0.005)
        with obs.span("t.profiled"):
            with obs.span("t.inner"):
                time.sleep(0.01)
        time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    session_ns = (time.monotonic() - t0) * 1e9
    (path,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    events = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in ("t.profiled", "t.inner"):
                        events[ev.name] = (ev.start_ns, ev.end_ns)
    assert set(events) == {"t.profiled", "t.inner"}
    (s, e), (si, ei) = events["t.profiled"], events["t.inner"]
    assert 0 <= s <= si < ei <= e <= session_ns
    assert e - s >= 0.01e9


# ---------------------------------------------------------------------------
# the program's spans
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def stream_arrays():
    rng = np.random.default_rng(3)
    K, n = 4, 64
    return (rng.normal(size=(K, n)), rng.normal(size=n),
            np.abs(rng.normal(size=K)) + 0.1)


def _catalogued(name: str) -> bool:
    return name in obs.catalog.SPANS


def test_program_spans_are_catalogued_and_steady_after_warmup(
        stream_arrays):
    from repro.federated import SimConfig, run_batch, run_sweep
    from repro.serve import SimClient, SimServer
    from repro.serve.worker import WorkerHandlers
    preds, y, costs = stream_arrays
    cfg = SimConfig()
    for _ in range(2):
        run_sweep("fedboost", preds, y, costs, 24, cfg, seeds=[1, 2],
                  budgets=[1.0, 2.0])
        run_batch("eflfg", preds, y, costs, 24, cfg, seeds=[1, 2, 3, 4],
                  budgets=[1.0, 1.0, 2.0, 2.0])
    with SimServer(max_batch=4, max_wait_ms=1.0) as srv:
        srv.register_stream("default", preds, y, costs)
        client = SimClient(srv)
        for wave in range(2):
            futs = [client.submit(algo="fedboost", seed=s, T=24)
                    for s in range(3)]
            for f in futs:
                f.result(timeout=600.0)
        stats = WorkerHandlers(srv).stats({}, {})
    snap = obs.SPANS.snapshot()
    names = list(snap["histograms"]) + list(snap["counters"])
    assert [n for n in names if not _catalogued(n)] == []
    h = snap["histograms"]
    for root in ("engine.run_sweep", "engine.run_batch", "serve.dispatch"):
        assert snap["counters"][f"{root}.compiled"] >= 1
        assert h[root]["count"] >= 1
    for child in ("prepare", "launch", "device_wait", "fetch", "unpack"):
        assert h[f"engine.run_sweep/engine.{child}"]["count"] >= 1
        assert h[f"serve.dispatch/engine.run_batch/engine.{child}"][
            "count"] >= 1
    for path in ("serve.dispatch/server.resolve",
                 "serve.dispatch/server.settle", "server.plan",
                 "server.coalesce"):
        assert h[path]["count"] >= 1
    assert snap["counters"]["serve.dispatch.items"] == \
        3 * h["serve.dispatch"]["count"]
    # the worker's stats reply carries the spans beside the server's
    # instruments, so `served metrics` shows serve.dispatch
    assert stats["metrics"]["histograms"]["serve.dispatch"]["count"] >= 1
    assert "server.queue.wait_s" in stats["metrics"]["histograms"]


def test_graph_lockstep_counters(stream_arrays):
    """An EFL-FG call counts its graph builder's own append steps and
    the steps its lockstep groups ran; their difference is the sweep's
    ``lockstep_waste``, and a compacted batch's buckets are groups of
    their own.  FedBoost builds no graph and counts nothing."""
    from repro.federated import SimConfig, run_batch, run_sweep
    preds, y, costs = stream_arrays
    cfg = SimConfig()
    sweep = ("eflfg", preds, y, costs, 24, cfg)
    grid = dict(seeds=[1, 2], budgets=[0.5, 1.5, 3.0])
    def steady(call, *args, **kw):
        """The counts of one call made after a warm-up call."""
        call(*args, **kw)
        before = _counters()
        out = call(*args, **kw)
        after = _counters()
        return out, {k: v - before.get(k, 0) for k, v in after.items()}

    res, c = steady(run_sweep, *sweep, **grid)
    appends = c["engine.run_sweep.graph_appends"]
    assert appends == int(res.graph_iters.sum()) > 0
    assert c["engine.run_sweep.graph_trips"] - appends \
        == res.lockstep_waste > 0

    # the same four lanes as a compacted batch: one bucket per budget
    whole = run_sweep(*sweep, seeds=[1, 2], budgets=[0.5, 3.0])
    _, c = steady(run_batch, *sweep, seeds=[1, 2, 1, 2],
                  budgets=[0.5, 0.5, 3.0, 3.0])
    appends = c["engine.run_batch.graph_appends"]
    trips = c["engine.run_batch.graph_trips"]
    assert appends == int(whole.graph_iters.sum())
    assert trips == sum(2 * int(it.max(0).sum()) for it in whole.graph_iters)
    # each bucket idles only behind its own slowest lane
    assert trips - appends < whole.lockstep_waste

    _, c = steady(run_sweep, "fedboost", preds, y, costs, 24, cfg,
                  seeds=[1, 2])
    assert c["engine.run_sweep.items"] == 2
    assert not any("graph" in k for k in c if c[k])


def test_only_the_dispatch_threads_queue_records_spans():
    """``server.wait_work`` / ``server.coalesce`` time the serve dispatch
    thread's waits; the daemon's admission queue polls without spans,
    since nothing in the daemon exports its span record."""
    from repro.serve.queue import RequestQueue
    for queue in (RequestQueue(prefix="daemon"),
                  RequestQueue(prefix="server", spans=True)):
        assert queue.drain(max_n=4, wait_s=0.005) == []
    assert set(_hists()) == {"server.wait_work"}
    assert _hists()["server.wait_work"]["sum"] >= 0.004


@pytest.mark.parametrize("sharded", [False, True])
def test_engine_spans_are_observe_only(stream_arrays, sharded):
    """THE observe-only pin for the engine: run_sweep and run_batch with
    spans on are bit-equal to the same calls with spans off."""
    from repro.federated import SimConfig, run_batch, run_sweep
    preds, y, costs = stream_arrays
    cfg = SimConfig(sweep_sharded=sharded)
    runs = {}
    for enabled in (True, False):
        with obs.scoped(enabled):
            runs[enabled] = (
                run_sweep("fedboost", preds, y, costs, 30, cfg,
                          seeds=[4, 5], budgets=[1.0, 3.0]),
                run_batch("eflfg", preds, y, costs, 30, cfg,
                          seeds=[1, 2, 3, 4], budgets=[1.0, 1.0, 2.0, 2.0]))
    (sweep_on, batch_on), (sweep_off, batch_off) = runs[True], runs[False]
    assert sweep_on.identical_to(sweep_off)
    assert sweep_on.sharded == sharded
    for lane, (on, off) in enumerate(zip(batch_on, batch_off)):
        assert on.identical_to(off), f"lane {lane} drifted"
    # the spans-on calls were recorded (compiled, since they ran first)
    assert _counters()["engine.run_sweep.compiled"] == 1
    assert _counters()["engine.run_batch.compiled"] == 1


# ---------------------------------------------------------------------------
# the benchmark's readers
# ---------------------------------------------------------------------------

TINY_LIMITS = {"eval_gap": 5e-3, "agree_min": 1}
REHEARSALS = {
    "energy.sweep_fedboost": (
        {"config": {"rows": 2000, "T": 60},
         "traffic": {"seeds_per_call": 2, "check_lanes": 4}},
        0.5, ("engine.host_share.sweep", "engine.prepare_share.sweep")),
    "ccpp.serve_fedboost": (
        {"config": {"rows": 2000, "T": 60},
         "traffic": {"rate_hz": 20.0, "max_batch": 4,
                     "check_requests": 4}},
        1.0, ("serve.host_share", "serve.thread_req_per_s")),
}


def _readers(names):
    from bench import harness
    return {n: harness.load_module(ROOT / "bench" / "metrics" / f"{n}.py")
            for n in names}


@pytest.mark.parametrize("workload", sorted(REHEARSALS))
def test_span_readers_on_cpu_rehearsals(workload):
    sys.path[:0] = [str(ROOT)]
    from bench import harness
    overrides, seconds, names = REHEARSALS[workload]
    traffic = json.loads((ROOT / "bench" / "traffic" / (
        workload.split(".", 1)[1] + ".json")).read_text())
    overrides = {"config": overrides["config"],
                 "traffic": {**overrides["traffic"],
                             "limits": {**traffic["limits"], **{
                                 k: v for k, v in TINY_LIMITS.items()
                                 if k in traffic["limits"]}}}}
    result, lines = harness.run_cell(workload, 2**31 + 29, seconds, True,
                                     time.monotonic(), overrides=overrides)
    metrics = result["metrics"]
    for name in names:
        assert name in metrics, (name, sorted(metrics), lines)
        value = metrics[name]["value"]
        assert math.isfinite(value) and value > 0, (name, value)
        if metrics[name]["unit"] == "%":
            assert 0.0 <= value <= 100.0, (name, value)


def test_span_readers_are_silent_without_spans(monkeypatch):
    sys.path[:0] = [str(ROOT)]
    readers = _readers(["engine.host_share.sweep",
                        "engine.prepare_share.sweep", "serve.host_share",
                        "serve.thread_req_per_s"])
    for reader in readers.values():
        assert reader.read({}) is None          # nothing recorded yet
    monkeypatch.delattr(obs, "SPANS")           # a program without spans
    for reader in readers.values():
        assert reader.read({}) is None
