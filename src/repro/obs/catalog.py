"""The instrument catalogue: one names-and-types table for the fleet.

Every instrument the serve stack registers is declared here, keyed by
its short (un-prefixed) name with its kind and help string.  The
daemon and server build their instruments *and* their legacy
``status()``/``counters`` JSON keys by iterating these tables, so the
names cannot drift apart again — there is exactly one spelling of
"admitted".

Full dotted instrument names are ``<prefix>.<short>`` —
``daemon.admitted``, ``server.batches``, ``daemon.queue.wait_s`` —
see docs/observability.md for the rendered catalogue.
"""

from __future__ import annotations

from .metrics import MetricsRegistry

__all__ = ["DAEMON_COUNTERS", "SERVER_COUNTERS", "QUEUE_INSTRUMENTS",
           "SPANS", "register_counters"]

# ServeDaemon request-lifecycle counters (previously the ad-hoc
# ``ServeDaemon.counters`` dict).  Order is the legacy JSON key order.
DAEMON_COUNTERS = {
    "admitted": "requests accepted past admission control",
    "rejected": "submits refused with Overloaded (queue full)",
    "expired": "requests dropped at their deadline before dispatch",
    "retried": "requests requeued after their worker was lost",
    "worker_failed": "requests failed WorkerDied with retries exhausted",
    "completed": "requests completed back to the client",
    "spilled": "requests routed off their affine worker (overload spill)",
    "preempted": "backlogged claims yanked back for a higher priority",
}

# SimServer dispatch counters (previously ``SimServer._stats``).
SERVER_COUNTERS = {
    "submitted": "requests accepted by submit()",
    "served": "request lanes completed",
    "failed": "request lanes failed",
    "batches": "buckets dispatched",
    "batched_lanes": "lanes dispatched in batched buckets, padding included",
    "padded_lanes": "padding lanes traced-and-dropped",
    "exact_requests": "lanes served on the exact (solo-program) path",
    "sharded_batches": "buckets dispatched through run_sweep_sharded",
    "dispatch_seq": "dispatch sequence numbers allocated",
    "quarantined": "requests failed at plan time (bad group key)",
}

# RequestQueue instruments, registered per queue under
# ``<prefix>.queue.depth`` / ``.queue.oldest_age_s`` /
# ``.queue.wait_s``.
QUEUE_INSTRUMENTS = {
    "depth": ("gauge", "requests currently queued"),
    "oldest_age_s": ("gauge", "age of the oldest queued request"),
    "wait_s": ("histogram", "queue residency, observed at claim time"),
}


# Process spans (``repro.obs.span``), recorded into ``repro.obs.SPANS``:
# one duration histogram per span path (root first, ``/``-joined), the
# work counts (``<path>.items``), the compiled-tree counts
# (``<root>.compiled``) and the compile time.
_ENGINE_CALL = {
    "engine.prepare": "host inputs up to the jitted call: arrays, the "
                      "per-seed key loop, schedules, program lookup",
    "engine.launch": "the jitted call's enqueue (where compiles land)",
    "engine.device_wait": "blocked until the call's outputs are ready",
    "engine.fetch": "device-to-host copy of the per-round outputs",
    "engine.unpack": "transmit-set words to bool masks, then the "
                     "SweepResult / per-lane SimResults",
}
# EFL-FG calls' graph-builder lockstep, counted from the stacked
# ``graph_iters`` under ``engine.unpack``; trips - appends is
# ``SweepResult.lockstep_waste``.
_GRAPH_STEPS = {
    "graph_appends": "EFL-FG graph-builder append steps the lanes needed "
                     "(each lane's own productive steps)",
    "graph_trips": "append steps their lockstep groups ran: each round, "
                   "the group's slowest lane's steps in every lane",
}
SPANS = {
    "engine.run_sweep": ("span", "one run_sweep / run_sweep_sharded call"),
    **{f"engine.run_sweep/{k}": ("span", v) for k, v in _ENGINE_CALL.items()},
    "engine.run_sweep.items": ("counter", "lanes (seeds x budgets) swept"),
    "engine.run_sweep.compiled": ("counter",
                                  "run_sweep calls in which JAX compiled"),
    **{f"engine.run_sweep.{k}": ("counter", v)
       for k, v in _GRAPH_STEPS.items()},
    "engine.run_batch": ("span", "one run_batch call outside a dispatch"),
    **{f"engine.run_batch/{k}": ("span", v) for k, v in _ENGINE_CALL.items()},
    "engine.run_batch.items": ("counter", "lanes run, padding included"),
    "engine.run_batch.compiled": ("counter",
                                  "run_batch calls in which JAX compiled"),
    **{f"engine.run_batch.{k}": ("counter", v)
       for k, v in _GRAPH_STEPS.items()},
    "serve.dispatch": ("span", "one bucket on the dispatch thread, from "
                               "resolution to its futures settled"),
    "serve.dispatch.items": ("counter", "requests dispatched"),
    "serve.dispatch.compiled": ("counter",
                                "buckets in which JAX compiled"),
    "serve.dispatch/server.resolve": ("span", "stream, config and per-lane "
                                      "budget / schedule resolution"),
    "serve.dispatch/engine.run_batch": ("span", "the bucket's run_batch"),
    **{f"serve.dispatch/engine.run_batch/{k}": ("span", v)
       for k, v in _ENGINE_CALL.items()},
    "serve.dispatch/engine.run_batch.items": ("counter",
                                              "lanes, padding included"),
    **{f"serve.dispatch/engine.run_batch.{k}": ("counter", v)
       for k, v in _GRAPH_STEPS.items()},
    "serve.dispatch/server.settle": ("span", "set_result / set_exception "
                                     "and the done-callbacks they run"),
    "server.plan": ("span", "planning a drain into buckets"),
    "server.wait_work": ("span", "a drain blocked on an empty queue"),
    "server.coalesce": ("span", "the batcher's max_wait_ms linger"),
    "jax.compile_s": ("histogram", "JAX's jaxpr-trace and backend-compile "
                                   "events inside span trees (traces nest)"),
}


def register_counters(registry: MetricsRegistry, prefix: str,
                      table: dict) -> dict:
    """Create (or fetch) one counter per table row; returns
    ``{short_name: Counter}`` for hot-path access without string
    formatting per increment."""
    return {short: registry.counter(f"{prefix}.{short}") for short in table}
