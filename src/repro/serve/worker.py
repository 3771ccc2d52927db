"""The serving worker process: one ``SimServer`` behind an RPC endpoint.

``python -m repro.serve.worker`` starts an in-process ``SimServer``
(dispatch thread, dynamic batcher, **process-local** executable cache)
and exposes it over ``repro.serve.transport.RpcServer``.  The worker is
the only process in the remote tier that imports jax; the daemon
(``repro.serve.daemon``) spawns it, reads the ``WORKER-READY`` handshake
line from its stdout, and forwards client submits to it.

Concurrency model: ``submit`` replies are *deferred* — the handler
enqueues into the ``SimServer`` and returns the ``SimFuture`` bridged
onto an ``RpcFuture``, so any number of submits stay in flight per
connection and the dynamic batcher coalesces them exactly as it would
coalesce local threads.  Requests whose deadline already passed on
arrival are refused with ``DeadlineExceeded`` before they can occupy a
bucket.

RPC methods: ``ping``, ``register_stream``, ``submit``,
``list_streams``, ``stats``, ``trace``, ``shutdown``.
"""

from __future__ import annotations

import argparse
import json
import signal
import sys
import threading
import time

from .. import obs
from .transport import DeadlineExceeded, RpcFuture, RpcServer
from .wire import config_from_wire, result_to_wire

__all__ = ["WorkerHandlers", "main", "READY_PREFIX"]

READY_PREFIX = "WORKER-READY "


class WorkerHandlers:
    """RPC method table over one ``SimServer``.  ``worker_id`` is the
    daemon's pool slot (0 for a standalone worker) — echoed in ``ping``
    and ``stats`` so fleet tooling can tell the processes apart."""

    def __init__(self, server, worker_id: int = 0):
        self.server = server
        self.worker_id = int(worker_id)
        self.started_at = time.monotonic()

    def table(self) -> dict:
        return {"ping": self.ping, "register_stream": self.register_stream,
                "submit": self.submit, "list_streams": self.list_streams,
                "stats": self.stats, "trace": self.trace}

    # -- methods ----------------------------------------------------------

    def ping(self, params, ctx):
        return {"pong": True, "worker_id": self.worker_id,
                "uptime_s": time.monotonic() - self.started_at}

    def register_stream(self, params, ctx):
        stream = self.server.register_stream(
            params["name"], params["preds"], params["y"], params["costs"])
        return {"name": stream.name, "version": stream.version,
                "K": stream.K, "n_stream": stream.n_stream}

    def submit(self, params, ctx):
        if ctx["deadline"] is not None and \
                time.monotonic() >= ctx["deadline"]:
            raise DeadlineExceeded("expired before worker dispatch")
        cfg = config_from_wire(params.get("cfg"))
        fut = self.server.submit(
            params["algo"], params["seed"], T=params["T"],
            budget=params.get("budget"),
            stream=params.get("stream", "default"), cfg=cfg,
            exact=bool(params.get("exact", False)),
            scenario=params.get("scenario"),
            priority=int(params.get("priority", 0)),
            # continue the daemon's trace in this process (same
            # trace_id, worker-side spans parented on the wire span)
            trace=obs.mint(parent=ctx.get("trace")))
        out = RpcFuture()

        def bridge(done):
            try:
                res = done.result(timeout=0)
            except BaseException as exc:        # noqa: BLE001
                out.set_exception(exc)
                return
            out.set_result({"result": result_to_wire(res),
                            "execution": dict(done.execution)})

        fut.add_done_callback(bridge)
        return out

    def list_streams(self, params, ctx):
        with self.server._lock:
            streams = dict(self.server._streams)
        return {name: {"version": s.version, "K": s.K,
                       "n_stream": s.n_stream}
                for name, s in sorted(streams.items())}

    def stats(self, params, ctx):
        s = self.server.stats()
        s["worker_id"] = self.worker_id
        # accepted but not yet settled — the pool router's load signal
        s["depth"] = s["submitted"] - s["served"] - s["failed"]
        # the typed instrument tree rides the same RPC, the process
        # spans (serve.dispatch and the engine's) beside the server's
        # instruments: the daemon's metrics_doc merges these per-worker
        # snapshots fleet-wide
        s["metrics"] = obs.MetricsRegistry.merge(
            [self.server.metrics.snapshot(), obs.SPANS.snapshot()])
        return s

    def trace(self, params, ctx):
        """This worker's span ring buffer (optionally one trace) — the
        daemon stitches it into cross-process timelines."""
        return obs.TRACER.dump(params.get("trace_id"),
                               params.get("limit"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.serve.worker",
        description="simulation worker: SimServer behind a socket RPC "
                    "endpoint (spawned by the serve daemon)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 = ephemeral; the bound port is announced on "
                         "stdout as 'WORKER-READY {json}'")
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--poll-s", type=float, default=0.02)
    ap.add_argument("--worker-id", type=int, default=0,
                    help="pool slot assigned by the spawning daemon")
    args = ap.parse_args(argv)

    obs.set_service(f"worker{args.worker_id}")
    # take the device before announcing readiness: a worker that cannot
    # get its chip fails the spawn instead of the first request
    import jax
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    from .server import SimServer
    server = SimServer(max_batch=args.max_batch,
                       max_wait_ms=args.max_wait_ms, poll_s=args.poll_s)
    server.start()

    handlers = WorkerHandlers(server, worker_id=args.worker_id)
    stop = threading.Event()

    def shutdown(params, ctx):
        # reply first, stop shortly after: the deferred timer lets the
        # ok-response leave the socket before the listener closes
        threading.Timer(0.2, stop.set).start()
        return {"stopping": True}

    table = handlers.table()
    table["shutdown"] = shutdown
    rpc = RpcServer(table, host=args.host, port=args.port).start()

    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    signal.signal(signal.SIGINT, lambda *a: stop.set())

    host, port = rpc.addr
    print(READY_PREFIX + json.dumps({"host": host, "port": port,
                                     "pid": __import__("os").getpid(),
                                     "worker_id": args.worker_id,
                                     "device": device}),
          flush=True)
    stop.wait()
    # graceful drain: no new requests (listener down), everything already
    # queued in the SimServer is served before the process exits
    rpc.stop()
    server.stop()
    return 0


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    sys.exit(main())
