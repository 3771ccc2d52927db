"""``repro.obs`` — metrics, tracing, and the observe-only contract.

Four layers, cheapest first:

* **metrics units** — counter/gauge/histogram semantics, thread safety,
  the merge algebra (counters and gauges sum, histograms add
  bucket-wise, NaN gauge reads are skipped, mismatched bounds raise),
  merged-quantile accuracy, and both render surfaces (deterministic
  JSON, Prometheus text exposition).
* **tracer units** — context minting and inheritance, the disabled
  no-op path, the bounded ring buffer, retroactive spans, and the
  Perfetto export shape.
* **queue instruments** — ``RequestQueue`` registers live depth/age
  gauges and a claim-time wait histogram; a seeded concurrency stress
  (producer vs racing drainers, mirroring the restore stress in
  ``test_served_daemon``) pins that instrument counts stay consistent
  under real interleavings.
* **in-process serve** — ``SimServer`` on the registry: ``stats()``
  keeps its legacy flat keys AND exposes the typed snapshot; a traced
  request's timeline reads submitted → queued → dispatch; and THE
  contract: a wave served with tracing enabled is bit-equal
  (``identical_to``) to the same wave with tracing disabled.  (The
  sustained-load version of that pin is the ``serve.obs_overhead``
  BENCH cell; this is the fast deterministic twin.)
"""

from __future__ import annotations

import json
import math
import random
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.serve.queue import RequestQueue, SimFuture, SimRequest


@pytest.fixture(autouse=True)
def _obs_enabled():
    """Force a known switch state per test and isolate the ring."""
    prev = obs.set_enabled(True)
    obs.TRACER.clear()
    yield
    obs.set_enabled(prev)
    obs.TRACER.clear()


def _req(seed: int = 0, **kw) -> SimRequest:
    return SimRequest(algo="eflfg", seed=seed, T=8, **kw)


# ---------------------------------------------------------------------------
# metrics units
# ---------------------------------------------------------------------------

def test_counter_inc_is_atomic_under_threads():
    reg = obs.MetricsRegistry()
    c = reg.counter("t.hits")
    seen = []

    def worker():
        for _ in range(1000):
            seen.append(c.inc())

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000
    # inc() returns the post-increment value: usable as a sequence
    assert sorted(seen) == list(range(1, 8001))


def test_gauge_set_fn_evaluated_at_snapshot_and_nan_on_error():
    reg = obs.MetricsRegistry()
    g = reg.gauge("t.depth")
    backing = [3]
    g.set_fn(lambda: backing[0])
    assert reg.snapshot()["gauges"]["t.depth"] == 3
    backing[0] = 7
    assert reg.snapshot()["gauges"]["t.depth"] == 7      # live, not cached
    g.set_fn(lambda: 1 / 0)
    assert math.isnan(reg.snapshot()["gauges"]["t.depth"])
    g.set(2.5)                                           # explicit wins
    assert reg.snapshot()["gauges"]["t.depth"] == 2.5


def test_registry_type_conflict_raises_and_get_or_create_is_stable():
    reg = obs.MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")


def test_histogram_merge_and_fleet_quantiles():
    """The load-bearing property: percentiles of MERGED per-worker
    snapshots track the pooled sample distribution without any process
    storing samples."""
    rng = random.Random(7)
    samples = [rng.lognormvariate(-3.0, 1.5) for _ in range(4000)]
    regs = [obs.MetricsRegistry() for _ in range(4)]
    for i, v in enumerate(samples):
        regs[i % 4].histogram("t.wait_s").observe(v)
    merged = obs.MetricsRegistry.merge([r.snapshot() for r in regs])
    h = merged["histograms"]["t.wait_s"]
    assert h["count"] == len(samples)
    assert h["sum"] == pytest.approx(sum(samples))
    assert h["min"] == pytest.approx(min(samples))
    assert h["max"] == pytest.approx(max(samples))
    ordered = sorted(samples)
    for q in (0.5, 0.9, 0.99):
        est = obs.quantile(h, q)
        exact = ordered[int(q * (len(ordered) - 1))]
        # log-spaced buckets (3/decade): estimates land within a bucket
        # width — a factor ~2.2 — of the exact sample quantile
        assert exact / 2.3 <= est <= exact * 2.3, (q, est, exact)
        assert h["min"] <= est <= h["max"]          # clamped to observed


def test_merge_sums_counters_and_gauges_and_skips_nan():
    a = {"counters": {"n": 2}, "gauges": {"d": 1.0}, "histograms": {}}
    b = {"counters": {"n": 3, "m": 1}, "gauges": {"d": float("nan")},
         "histograms": {}}
    merged = obs.MetricsRegistry.merge([a, b])
    assert merged["counters"] == {"n": 5, "m": 1}
    assert merged["gauges"] == {"d": 1.0}           # NaN read skipped


def test_merge_rejects_mismatched_bounds():
    r1, r2 = obs.MetricsRegistry(), obs.MetricsRegistry()
    r1.histogram("h").observe(0.1)
    r2.histogram("h", bounds=(1.0, 2.0)).observe(1.5)
    with pytest.raises(ValueError, match="bounds mismatch"):
        obs.MetricsRegistry.merge([r1.snapshot(), r2.snapshot()])


def test_log_bounds_cover_the_documented_range():
    b = obs.log_bounds()
    assert b[0] == pytest.approx(1e-4) and b[-1] == pytest.approx(1e3)
    assert list(b) == sorted(b) and len(b) == 22
    with pytest.raises(ValueError):
        obs.log_bounds(lo=-1.0)


def test_render_surfaces_json_and_prometheus():
    reg = obs.MetricsRegistry()
    reg.counter("daemon.admitted").inc(4)
    reg.gauge("daemon.queue.depth").set(2)
    reg.histogram("daemon.queue.wait_s").observe(0.25)
    snap = reg.snapshot()
    # snapshots are plain JSON-able dicts: they round-trip unchanged
    assert json.loads(json.dumps(snap, sort_keys=True)) == snap
    text = obs.render_prometheus(snap)
    assert "# TYPE repro_daemon_admitted_total counter" in text
    assert "repro_daemon_admitted_total 4" in text
    assert "repro_daemon_queue_depth 2" in text
    # cumulative le-buckets with the +Inf terminator, sum and count
    assert 'repro_daemon_queue_wait_s_bucket{le="+Inf"} 1' in text
    assert "repro_daemon_queue_wait_s_count 1" in text
    assert text.endswith("\n")


# ---------------------------------------------------------------------------
# tracer units
# ---------------------------------------------------------------------------

def test_mint_child_inherits_trace_id_with_fresh_span_id():
    root = obs.mint()
    assert set(root) == {"trace_id", "span_id"}
    assert len(root["trace_id"]) == 16 and len(root["span_id"]) == 8
    kid = obs.mint(parent=root)
    assert kid["trace_id"] == root["trace_id"]
    assert kid["span_id"] != root["span_id"]
    # no parent (or an untraced one): a fresh trace, never an error
    orphan = obs.mint(parent=None)
    assert orphan["trace_id"] != root["trace_id"]


def test_disabled_mint_and_record_are_noops():
    tr = obs.Tracer("test")
    with obs.scoped(False):
        assert obs.mint() is None
        tr.record("x", {"trace_id": "aa", "span_id": "bb"})
    assert tr.spans() == []
    tr.record("x", None)                    # untraced request: no-op
    assert tr.spans() == []


def test_ring_buffer_is_bounded_and_oldest_falls_off():
    tr = obs.Tracer("test", capacity=10)
    ctx = obs.mint()
    for i in range(25):
        tr.event(f"e{i}", ctx)
    names = [s["name"] for s in tr.spans()]
    assert names == [f"e{i}" for i in range(15, 25)]


def test_retroactive_span_and_wall_clock_anchor():
    tr = obs.Tracer("test")
    ctx = obs.mint()
    t0 = time.monotonic() - 0.5
    tr.record("queued", ctx, t0=t0, attrs={"stream": "default"})
    (s,) = tr.spans(ctx["trace_id"])
    assert s["dur_s"] == pytest.approx(0.5, abs=0.05)
    assert s["t0_wall"] == pytest.approx(obs.clock.to_wall(t0))
    assert s["attrs"] == {"stream": "default"}
    assert s["parent_id"] == ctx["span_id"]


def test_traces_lists_distinct_ids_newest_first():
    tr = obs.Tracer("test")
    a, b = obs.mint(), obs.mint()
    tr.event("first", a)
    tr.event("second", b)
    tr.event("third", a)
    recent = tr.traces()
    assert [r["trace_id"] for r in recent] == [b["trace_id"],
                                               a["trace_id"]]
    assert recent[1]["n_spans"] == 2
    assert recent[1]["names"] == ["first", "third"]


def test_perfetto_export_shape():
    tr = obs.Tracer("daemon")
    ctx = obs.mint()
    tr.record("dispatch", ctx, t0=time.monotonic() - 0.01,
              attrs={"worker": 1})
    doc = obs.to_perfetto(tr.spans())
    events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    metas = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
    assert len(events) == 1 and len(metas) == 1
    (e,) = events
    assert e["name"] == "dispatch" and e["cat"] == "daemon"
    assert e["dur"] >= 1.0 and e["args"]["worker"] == 1
    assert metas[0]["args"]["name"] == "daemon"
    json.dumps(doc)                         # chrome://tracing-loadable


def test_wire_trace_field_is_sanitized():
    from repro.serve.wire import valid_trace
    assert valid_trace({"trace_id": "ab", "span_id": "cd"}) == \
        {"trace_id": "ab", "span_id": "cd"}
    assert valid_trace(None) is None
    assert valid_trace("junk") is None
    assert valid_trace({"trace_id": "", "span_id": "x"}) is None
    assert valid_trace({"trace_id": "a" * 65, "span_id": "x"}) is None
    assert valid_trace({"trace_id": 7, "span_id": "x"}) is None


# ---------------------------------------------------------------------------
# queue instruments
# ---------------------------------------------------------------------------

def test_queue_registers_depth_age_and_wait_instruments():
    reg = obs.MetricsRegistry()
    q = RequestQueue(registry=reg, prefix="daemon")
    r0, r1 = _req(0), _req(1)
    q.put(r0, SimFuture(r0))
    time.sleep(0.02)
    q.put(r1, SimFuture(r1))
    snap = reg.snapshot()
    assert snap["gauges"]["daemon.queue.depth"] == 2
    assert snap["gauges"]["daemon.queue.oldest_age_s"] >= 0.02
    assert snap["histograms"]["daemon.queue.wait_s"]["count"] == 0
    q.drain(max_n=8, wait_s=0.0)
    snap = reg.snapshot()
    assert snap["gauges"]["daemon.queue.depth"] == 0
    assert snap["gauges"]["daemon.queue.oldest_age_s"] == 0.0
    h = snap["histograms"]["daemon.queue.wait_s"]
    assert h["count"] == 2 and h["max"] >= 0.02


def test_queue_records_queued_span_at_claim_time():
    ctx = obs.mint()
    q = RequestQueue(registry=obs.MetricsRegistry(), prefix="daemon")
    r = _req(0, trace=ctx)
    q.put(r, SimFuture(r))
    time.sleep(0.01)
    q.drain(max_n=4, wait_s=0.0)
    spans = obs.TRACER.spans(ctx["trace_id"])
    assert [s["name"] for s in spans] == ["daemon.queued"]
    assert spans[0]["dur_s"] >= 0.01
    assert spans[0]["attrs"]["stream"] == "default"


def test_queue_submitted_wall_is_anchored_monotonic():
    """Clock discipline: ``submitted_at`` is monotonic-only; wall time
    is derived through the per-process anchor, never read per event."""
    r = _req(0)
    assert abs(r.submitted_at - time.monotonic()) < 1.0
    assert abs(r.submitted_wall - time.time()) < 1.0
    assert r.submitted_wall == pytest.approx(
        obs.clock.to_wall(r.submitted_at))


@pytest.mark.parametrize("stress_seed", [4321, 99])
def test_queue_metrics_concurrent_stress(stress_seed):
    """Instrumented-queue twin of the restore stress: racing drainers
    against a producer with restores mixed in, the wait histogram's
    count must equal total claims (each item observed exactly once per
    claim) and the live depth gauge must read 0 once everything
    settles — no lost or double-counted observations under real
    interleavings."""
    n = 200
    reg = obs.MetricsRegistry()
    q = RequestQueue(registry=reg, prefix="daemon")
    pairs = [(r := _req(i), SimFuture(r)) for i in range(n)]
    errors: list = []
    claims = [0]
    claims_lock = threading.Lock()

    def producer():
        prng = random.Random(stress_seed)
        try:
            for r, f in pairs:
                q.put(r, f)
                if prng.random() < 0.05:
                    time.sleep(0.0005)
        except Exception as exc:        # noqa: BLE001
            errors.append(exc)

    def drainer(seed):
        prng = random.Random(seed)
        try:
            while not all(f.done() for _, f in pairs):
                batch = q.drain(max_n=prng.randint(1, 7), wait_s=0.005)
                with claims_lock:
                    claims[0] += len(batch)
                if not batch:
                    continue
                if prng.random() < 0.3:
                    q.restore(batch)    # back for a later (re-counted) claim
                else:
                    for _, f in batch:
                        f.set_result("served")
        except Exception as exc:        # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=producer)]
    threads += [threading.Thread(target=drainer, args=(stress_seed + i,))
                for i in range(4)]
    for t in threads:
        t.start()
    threads[0].join(timeout=60.0)
    q.close()
    for t in threads:
        t.join(timeout=60.0)
    assert not any(t.is_alive() for t in threads), "stress wedged"
    assert not errors, errors
    snap = reg.snapshot()
    # every claim observed exactly once — restores produce a fresh
    # observation on the next claim, by design (time-in-queue per stint)
    assert snap["histograms"]["daemon.queue.wait_s"]["count"] == claims[0]
    assert claims[0] >= n
    assert snap["gauges"]["daemon.queue.depth"] == 0


# ---------------------------------------------------------------------------
# in-process serve: legacy stats shape, timeline, and THE bit-equality pin
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def stream_arrays():
    rng = np.random.default_rng(0)
    K, n = 4, 64
    return (rng.normal(size=(K, n)), rng.normal(size=n),
            np.abs(rng.normal(size=K)) + 0.1)


def test_server_stats_keeps_legacy_keys_and_grows_registry(stream_arrays):
    from repro.serve import SimClient, SimServer
    preds, y, costs = stream_arrays
    spans = obs.SPANS.snapshot()
    dispatched_before = (
        spans["histograms"].get("serve.dispatch", {"count": 0})["count"]
        + spans["counters"].get("serve.dispatch.compiled", 0))
    with SimServer(max_batch=8, max_wait_ms=1.0) as srv:
        srv.register_stream("default", preds, y, costs)
        client = SimClient(srv)
        futs = [client.submit(algo="eflfg", seed=s, T=20) for s in range(4)]
        for f in futs:
            f.result(timeout=600.0)
        st = srv.stats()
        # the legacy flat shape every existing caller reads
        for key in ("submitted", "served", "failed", "batches",
                    "batched_lanes", "padded_lanes", "exact_requests",
                    "sharded_batches", "mean_occupancy", "cache"):
            assert key in st, key
        assert st["submitted"] == st["served"] == 4 and st["failed"] == 0
        # ... and the typed registry tree behind it agrees
        snap = srv.metrics.snapshot()
        assert snap["counters"]["server.submitted"] == 4
        assert snap["counters"]["server.served"] == 4
        assert snap["histograms"]["server.queue.wait_s"]["count"] == 4
        # the dispatch time is the serve.dispatch process span: observed
        # when steady, counted under .compiled when the bucket compiled
        spans = obs.SPANS.snapshot()
        after = (spans["histograms"].get("serve.dispatch", {"count": 0})
                 ["count"]
                 + spans["counters"].get("serve.dispatch.compiled", 0))
        assert after - dispatched_before >= 1


def test_traced_request_timeline_in_process(stream_arrays):
    from repro.serve import SimClient, SimServer
    preds, y, costs = stream_arrays
    with SimServer(max_batch=8, max_wait_ms=1.0) as srv:
        srv.register_stream("default", preds, y, costs)
        client = SimClient(srv)
        fut = client.submit(algo="eflfg", seed=1, T=20)
        fut.result(timeout=600.0)
        tid = fut.request.trace["trace_id"]
        spans = obs.TRACER.spans(tid)
        assert [s["name"] for s in spans] == \
            ["serve.submitted", "server.queued", "serve.dispatch"]
        dispatch = spans[-1]
        assert dispatch["attrs"]["outcome"] == "ok"
        assert dispatch["attrs"]["n_requests"] == 1
        assert 1 in dispatch["attrs"]["co_seeds"]


def test_wave_with_tracing_enabled_is_bit_equal_to_disabled(stream_arrays):
    """THE observe-only pin: identical request waves, tracing on vs
    off, must produce ``identical_to``-equal results lane for lane —
    telemetry can never move a bit (docs/observability.md)."""
    from repro.serve import SimClient, SimServer
    preds, y, costs = stream_arrays
    waves = {}
    for enabled in (True, False):
        with obs.scoped(enabled):
            with SimServer(max_batch=8, max_wait_ms=1.0) as srv:
                srv.register_stream("default", preds, y, costs)
                client = SimClient(srv)
                futs = [client.submit(algo=a, seed=s, T=30)
                        for a in ("eflfg", "fedboost") for s in range(3)]
                waves[enabled] = [f.result(timeout=600.0) for f in futs]
                if enabled:
                    assert all(f.request.trace for f in futs)
                else:
                    assert all(f.request.trace is None for f in futs)
    for lane, (on, off) in enumerate(zip(waves[True], waves[False])):
        assert on.identical_to(off), f"lane {lane} drifted"
