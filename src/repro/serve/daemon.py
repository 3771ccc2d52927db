"""The serving management daemon: the front door of the remote tier.

``ServeDaemon`` owns the client-facing RPC endpoint and supervises a
**pool** of ``repro.serve.worker`` subprocesses (the only processes that
import jax — the daemon itself is stdlib + numpy, so its control loops
never stall behind a compile).  Responsibilities, each pinned by
``tests/test_transport_faults.py`` / ``tests/test_served_daemon.py`` /
``tests/test_router_props.py``:

* **admission control** — a bounded ``RequestQueue``; when
  ``queued + backlogged + in-flight`` reaches ``max_pending`` (or the
  daemon is draining), submits are rejected with a typed ``Overloaded``
  the client can retry after backoff.
* **deadline-aware drop** — each admitted request carries an absolute
  deadline (from the request's remaining-budget ``deadline_ms``); the
  pump fails expired requests with ``DeadlineExceeded`` *before*
  forwarding, so a backed-up queue sheds load instead of computing
  results nobody is waiting for.
* **stream-affinity routing** — the pump assigns each request to the
  rendezvous-hash winner for its ``(stream, version)``
  (``repro.serve.router``), so one worker's process-local executable
  cache serves all of a stream's traffic; when the affine worker's
  depth reaches ``spill_depth`` the request **spills** to the
  least-loaded alive worker instead, which learns the stream lazily.
  Routing never changes bits: any worker's result is bit-equal to any
  other's and to in-process serving (docs/determinism.md row 21).
* **preemption** — priority now acts past the queue: a higher-priority
  arrival routed to a worker whose dispatch window is full may bump the
  lowest-priority request still *backlogged* on that worker back into
  the main queue (``RequestQueue.restore`` — never burning an attempt,
  and never touching a request already dispatched, which preserves
  exactly-once settlement).
* **per-worker liveness** — a heartbeat thread pings every worker; on
  misses (or connection loss) that worker is declared dead, killed, and
  respawned with its *affine slice* of the stream registry replayed
  (the fresh process-local cache starts cold, versions bumped); the
  rest of the pool keeps serving untouched.
* **requeue-or-fail, exactly once** — in-flight requests whose worker
  died are ``RequestQueue.restore``d for one more attempt (idempotent
  submits: re-running a simulation is bit-identical), then failed with
  ``WorkerDied``.  A future settles exactly once: ``restore`` drops
  already-settled futures, and settling is first-wins.
* **one chip per worker** — on a TPU host worker ``i`` sees only chip
  ``i`` (``worker_env``), and a pool larger than the host's chip count
  fails at spawn with ``NoChipForWorker``.
* **graceful drain** — ``drain_and_stop`` rejects new submits, serves
  everything admitted (surviving workers absorb a dead co-worker's
  backlog), shuts every worker down, and only then stops the front
  endpoint; ``repro.launch.served`` wires this to SIGTERM.

Run it in the foreground with ``python -m repro.serve.daemon``;
``repro.launch.served start`` is the detached launcher (pidfile,
ready handshake).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys
import threading
import time
from typing import Optional

from . import router
from .. import obs
from .queue import RequestQueue, SimFuture, SimRequest
from .transport import (ConnectionLost, DeadlineExceeded, Overloaded,
                        RpcClient, RpcServer, TransportError, WorkerDied)

__all__ = ["ServeDaemon", "WorkerHandle", "NoChipForWorker", "worker_env",
           "host_tpu_chips", "main", "READY_PREFIX"]

READY_PREFIX = "DAEMON-READY "


class WorkerHandle:
    """One spawned worker: subprocess + RPC client + spawn epoch.

    ``worker_id`` is the stable pool slot (assigned by the daemon, not
    the factory) and ``streams`` maps stream name -> the daemon version
    last pushed to THIS worker — the pump's lazy-registration check.
    ``device`` is the worker's JAX device as its ready line reported it
    (``{"platform", "kind", "count"}``; ``None`` for stub workers).
    """

    def __init__(self, proc: Optional[subprocess.Popen], client: RpcClient,
                 epoch: int, device: Optional[dict] = None):
        self.proc = proc
        self.client = client
        self.epoch = epoch
        self.device = device
        self.worker_id = 0
        self.streams: dict = {}

    @property
    def pid(self) -> Optional[int]:
        return self.proc.pid if self.proc is not None else None

    @property
    def alive(self) -> bool:
        if not self.client.alive:
            return False
        return self.proc is None or self.proc.poll() is None

    def kill(self) -> None:
        self.client.close()
        if self.proc is None:
            return
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=5.0)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=5.0)


class NoChipForWorker(RuntimeError):
    """The pool has more workers than this host has TPU chips."""


# PCI ids of TPU chips (vendor: Google), as jax's hardware_utils lists
# them — read from sysfs here so the daemon never imports jax
_GOOGLE_PCI_VENDOR = "0x1ae0"
_TPU_PCI_DEVICES = frozenset({"0x0027", "0x0056", "0x005e", "0x0062",
                              "0x0063", "0x006f", "0x0076"})
_TPU_PROCESS_PORT = 8476      # libtpu's default; worker i takes 8476 + i


def host_tpu_chips(sysfs: str = "/sys/bus/pci/devices",
                   dev: str = "/dev") -> int:
    """Number of TPU chips this host lets its processes open.

    A chip on the PCI bus counts when its device node exists:
    ``/dev/vfio/<iommu group>`` (v5e and later) or ``/dev/accel<i>``.  A
    machine may attach more chips than it exposes — a one-chip slice of
    a four-chip host shows four on the bus and one node — and libtpu
    numbers only the chips it can open, ``0..n-1``, for
    ``TPU_VISIBLE_CHIPS``."""
    n = i = 0
    for path in sorted(glob.glob(os.path.join(sysfs, "*"))):
        try:
            with open(os.path.join(path, "vendor")) as f:
                vendor = f.read().strip()
            with open(os.path.join(path, "device")) as f:
                device = f.read().strip()
        except OSError:
            continue
        if vendor != _GOOGLE_PCI_VENDOR or device not in _TPU_PCI_DEVICES:
            continue
        group = os.path.join(path, "iommu_group")
        node = (os.path.join(dev, "vfio",
                             os.path.basename(os.readlink(group)))
                if os.path.islink(group) else os.path.join(dev, f"accel{i}"))
        n += os.path.exists(node)
        i += 1
    return n


def worker_env(worker_id: int, environ=None, chips=None) -> dict:
    """The environment of pool worker ``worker_id``.

    A chip belongs to one process: on a TPU host every worker sees
    exactly one chip through libtpu's visibility variables — worker
    ``i`` takes the ``i``-th of the chips the daemon may use (the
    parent's ``TPU_VISIBLE_CHIPS`` when set, else every chip of the
    host) — and is held to the TPU platform, so a worker that cannot get
    its chip fails instead of falling back to the CPU.  A pool larger
    than that chip list raises ``NoChipForWorker`` naming the missing
    chip, instead of starting a worker that waits on libtpu's lock.  A
    host without TPU chips, or a parent that pins JAX to other platforms
    (``JAX_PLATFORMS=cpu``, as the tests do), passes its environment
    through unchanged.  ``chips`` overrides the host's chip count
    (``host_tpu_chips()``)."""
    env = dict(os.environ if environ is None else environ)
    platforms = env.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return env
    visible = env.get("TPU_VISIBLE_CHIPS", "")
    if visible:
        ids = [c.strip() for c in visible.split(",") if c.strip()]
    else:
        ids = [str(c) for c in range(host_tpu_chips() if chips is None
                                     else chips)]
    if not ids:
        return env
    if worker_id >= len(ids):
        raise NoChipForWorker(
            f"worker {worker_id} needs TPU chip #{worker_id}, but only "
            f"{len(ids)} chip(s) are visible here ({','.join(ids)}): run "
            f"at most {len(ids)} worker(s)")
    port = _TPU_PROCESS_PORT + worker_id
    env.update(JAX_PLATFORMS=platforms or "tpu",
               TPU_VISIBLE_CHIPS=ids[worker_id],
               TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
               TPU_PROCESS_BOUNDS="1,1,1",
               TPU_PROCESS_PORT=str(port),
               TPU_PROCESS_ADDRESSES=f"localhost:{port}")
    return env


def _spawn_worker_subprocess(worker_args: dict, epoch: int) -> WorkerHandle:
    """Default worker factory: ``python -m repro.serve.worker`` with an
    ephemeral port, pinned to its own chip (``worker_env``), handshaken
    via the WORKER-READY stdout line (slow on purpose — the worker
    imports jax)."""
    worker_id = worker_args.get("worker_id", 0)
    env = worker_env(worker_id)
    cmd = [sys.executable, "-m", "repro.serve.worker", "--port", "0",
           "--max-batch", str(worker_args.get("max_batch", 16)),
           "--max-wait-ms", str(worker_args.get("max_wait_ms", 2.0)),
           "--worker-id", str(worker_id)]
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, stderr=None,
                            env=env, text=True)
    from .worker import READY_PREFIX as WORKER_READY
    deadline = time.monotonic() + worker_args.get("spawn_timeout_s", 120.0)
    addr = info = None
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        if line.startswith(WORKER_READY):
            info = json.loads(line[len(WORKER_READY):])
            addr = (info["host"], info["port"])
            break
    if addr is None:
        proc.kill()
        raise WorkerDied("worker failed to announce readiness")
    client = RpcClient(addr, connect_timeout=10.0)
    return WorkerHandle(proc, client, epoch, device=info.get("device"))


class ServeDaemon:
    """See module docstring.  ``worker_factory(worker_args, epoch)`` is
    injectable so the fault tests can stand up stub peers; the daemon
    passes ``worker_args["worker_id"]`` and epochs count per pool slot
    (first spawn of every slot is epoch 1)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 max_pending: int = 256, retry_limit: int = 1,
                 heartbeat_s: float = 1.0, heartbeat_misses: int = 3,
                 poll_s: float = 0.02, linger_s: float = 0.002,
                 workers: int = 1, worker_window: int = 32,
                 spill_depth: int = 32,
                 worker_factory=None, worker_args: Optional[dict] = None):
        self._host, self._port = host, port
        self.max_pending = int(max_pending)
        self.retry_limit = int(retry_limit)
        self.heartbeat_s = float(heartbeat_s)
        self.heartbeat_misses = int(heartbeat_misses)
        self._poll_s, self._linger_s = float(poll_s), float(linger_s)
        self.workers = max(1, int(workers))
        self.worker_window = max(1, int(worker_window))
        self.spill_depth = max(1, int(spill_depth))
        self._worker_factory = worker_factory or _spawn_worker_subprocess
        self._worker_args = dict(worker_args or {})
        # Lifecycle counters live on the registry (one catalogue row per
        # name — repro.obs.catalog.DAEMON_COUNTERS); each instrument
        # self-locks, so increments never race and never need the daemon
        # lock.  The admission queue registers its own depth/age gauges
        # and wait histogram on the same registry.
        self.metrics = obs.MetricsRegistry()
        self._c = obs.catalog.register_counters(
            self.metrics, "daemon", obs.catalog.DAEMON_COUNTERS)
        self._queue = RequestQueue(registry=self.metrics, prefix="daemon")
        self._lock = threading.Lock()
        self._streams: dict = {}        # name -> {preds,y,costs,version}
        ids = range(self.workers)
        self._pool: dict = {wid: None for wid in ids}   # wid -> handle|None
        self._epochs = {wid: 0 for wid in ids}
        self._wmisses = {wid: 0 for wid in ids}
        self._wrestarts = {wid: 0 for wid in ids}
        self._backlog: dict = {wid: [] for wid in ids}  # routed, undispatched
        self._winflight: dict = {wid: {} for wid in ids}  # id(fut)->(req,fut)
        self._restarts = 0
        self._draining = False
        self._stopped = threading.Event()
        self._rpc: Optional[RpcServer] = None
        self._threads: list = []

    @property
    def counters(self) -> dict:
        """Legacy flat view of the lifecycle counters (read-only; the
        live instruments are on ``self.metrics``)."""
        return {short: self._c[short].value
                for short in obs.catalog.DAEMON_COUNTERS}

    # -- lifecycle --------------------------------------------------------

    @property
    def addr(self) -> tuple:
        return self._rpc.addr

    def start(self) -> "ServeDaemon":
        try:
            for wid in range(self.workers):
                self._spawn_worker(wid)
        except BaseException:
            # a failed spawn (no chip for it, no readiness) must not
            # leave the workers already started holding their chips
            for handle in self._pool.values():
                if handle is not None:
                    handle.kill()
            raise
        self._rpc = RpcServer({
            "ping": lambda p, c: {"pong": True},
            "submit": self._h_submit,
            "register_stream": self._h_register_stream,
            "list_streams": self._h_list_streams,
            "status": lambda p, c: self.status(),
            "metrics": lambda p, c: self.metrics_doc(),
            "trace": self._h_trace,
            "stop": self._h_stop,
        }, host=self._host, port=self._port).start()
        for name, target in (("daemon-pump", self._pump_loop),
                             ("daemon-heartbeat", self._heartbeat_loop)):
            t = threading.Thread(target=target, name=name, daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def __enter__(self) -> "ServeDaemon":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.drain_and_stop()

    # -- front handlers ---------------------------------------------------

    def _pending_count(self) -> int:
        with self._lock:
            pending = (sum(len(m) for m in self._winflight.values())
                       + sum(len(b) for b in self._backlog.values()))
        return len(self._queue) + pending

    def _reject(self, why: str):
        self._c["rejected"].inc()
        raise Overloaded(why)

    def _h_submit(self, params, ctx):
        if self._draining:
            self._reject("daemon is draining; submit elsewhere")
        if self._pending_count() >= self.max_pending:
            self._reject(
                f"admission queue full ({self.max_pending} pending)")
        with self._lock:
            known = params.get("stream", "default") in self._streams
        if not known:
            raise ValueError(
                f"unknown stream {params.get('stream', 'default')!r}; "
                "register-stream first")
        scenario = params.get("scenario")
        if scenario is not None and not isinstance(scenario, str):
            raise TypeError("remote scenarios must be registered names")
        # SimRequest validates algo/T synchronously — the submitter gets
        # the ValueError, never a co-tenant.  cfg stays an opaque wire
        # dict here; only the worker materializes a SimConfig.
        # trace context: inherit the client's from the wire envelope
        # (same trace_id, fresh span parent) or mint one locally; None
        # when observability is off — everything downstream no-ops
        tctx = obs.mint(parent=ctx.get("trace"))
        req = SimRequest(
            algo=params["algo"], seed=int(params["seed"]),
            T=int(params["T"]), budget=params.get("budget"),
            stream=params.get("stream", "default"),
            cfg=params.get("cfg"), exact=bool(params.get("exact", False)),
            scenario=scenario, priority=int(params.get("priority", 0)),
            deadline=ctx["deadline"], trace=tctx)
        fut = SimFuture(req)
        fut.attempts = 0
        try:
            self._queue.put(req, fut)
        except Exception as exc:
            self._reject(f"not accepting requests: {exc}")
        self._c["admitted"].inc()
        obs.TRACER.event("daemon.admitted", tctx,
                         attrs={"algo": req.algo, "seed": req.seed,
                                "stream": req.stream, "peer": ctx.get("peer")})
        return fut                      # deferred: replied on fulfillment

    def _h_register_stream(self, params, ctx):
        name = params["name"]
        with self._lock:
            version = self._streams.get(name, {}).get("version", 0) + 1
            self._streams[name] = {"preds": params["preds"],
                                   "y": params["y"],
                                   "costs": params["costs"],
                                   "version": version}
            alive = [wid for wid, h in self._pool.items()
                     if h is not None and h.alive]
            handle = (self._pool[router.affine_worker(name, version, alive)]
                      if alive else None)
        if handle is None:
            raise WorkerDied("no live worker to register the stream with")
        # eager push to the (new version's) affine worker; everyone else
        # learns the stream lazily when traffic spills onto them
        reply = handle.client.call("register_stream", params,
                                   deadline_s=60.0)
        with self._lock:
            handle.streams[name] = version
        return {"name": name, "daemon_version": version,
                "worker_version": reply["version"], "K": reply["K"],
                "n_stream": reply["n_stream"],
                "worker": handle.worker_id}

    def _h_list_streams(self, params, ctx):
        with self._lock:
            handles = [h for _, h in sorted(self._pool.items())
                       if h is not None and h.alive]
            cached = {n: {"version": s["version"]}
                      for n, s in sorted(self._streams.items())}
        merged: dict = {}
        for handle in handles:
            try:
                reply = handle.client.call("list_streams", {},
                                           deadline_s=10.0)
            except TransportError:
                continue
            for sname, meta in reply.items():
                merged.setdefault(sname, meta)
        return merged if merged else cached

    def _h_stop(self, params, ctx):
        threading.Thread(target=self.drain_and_stop,
                         name="daemon-stop", daemon=True).start()
        return {"stopping": True}

    # -- worker supervision -----------------------------------------------

    def _spawn_worker(self, wid: int) -> None:
        with self._lock:
            self._epochs[wid] += 1
            epoch = self._epochs[wid]
        handle = self._worker_factory(
            dict(self._worker_args, worker_id=wid), epoch)
        handle.worker_id = wid
        # replay THIS worker's affine slice of the stream registry (over
        # the full configured pool, so the scope is stable no matter who
        # else is momentarily down): the fresh worker's process-local
        # cache starts cold and must see current data (version bump).
        # Streams it only ever sees as a spill target arrive lazily.
        with self._lock:
            streams = dict(self._streams)
        all_ids = range(self.workers)
        for name, s in sorted(streams.items()):
            if router.affine_worker(name, s["version"], all_ids) != wid:
                continue
            handle.client.call("register_stream",
                               {"name": name, "preds": s["preds"],
                                "y": s["y"], "costs": s["costs"]},
                               deadline_s=60.0)
            handle.streams[name] = s["version"]
        with self._lock:
            self._pool[wid] = handle
            self._wmisses[wid] = 0

    def _declare_worker_dead(self, wid: int, handle: WorkerHandle,
                             why: str) -> None:
        with self._lock:
            if self._pool.get(wid) is not handle:
                return                  # already superseded
            self._pool[wid] = None
            self._restarts += 1
            self._wrestarts[wid] += 1
            backlog, self._backlog[wid] = self._backlog[wid], []
        # closing the client fails its pending RPCs with ConnectionLost,
        # which runs every in-flight request's requeue-or-fail callback;
        # backlogged (never-dispatched) requests go straight back to the
        # main queue without burning an attempt
        handle.kill()
        if backlog:
            self._queue.restore(backlog)
        if self._draining or self._stopped.is_set():
            return
        try:
            self._spawn_worker(wid)
        except Exception:               # noqa: BLE001
            pass                        # heartbeat loop keeps retrying

    def _heartbeat_loop(self) -> None:
        while not self._stopped.wait(self.heartbeat_s):
            if self._draining:
                return
            for wid in range(self.workers):
                with self._lock:
                    handle = self._pool.get(wid)
                if handle is None:
                    try:
                        self._spawn_worker(wid)
                    except Exception:   # noqa: BLE001
                        pass
                    continue
                try:
                    handle.client.call("ping", {},
                                       deadline_s=max(self.heartbeat_s, 0.2))
                    with self._lock:
                        self._wmisses[wid] = 0
                except (TransportError, TimeoutError):
                    with self._lock:
                        self._wmisses[wid] += 1
                        misses = self._wmisses[wid]
                    if misses >= self.heartbeat_misses or not handle.alive:
                        self._declare_worker_dead(
                            wid, handle, f"{misses} missed heartbeats")

    # -- the pump: queue -> router -> worker backlogs ----------------------

    def _pump_loop(self) -> None:
        while True:
            batch = self._queue.drain(max_n=64, wait_s=self._poll_s,
                                      linger_s=self._linger_s)
            if batch:
                self._route_batch(batch)
            self._flush_backlogs()
            if batch:
                continue
            if self._stopped.is_set():
                return
            if (self._draining and self._queue.closed
                    and not self._pending_count()):
                # in-flight work counts: a worker dying mid-drain restores
                # its claims to the (closed) queue, and this loop must
                # still be here to re-route them to a survivor
                return

    def _route_batch(self, batch: list) -> None:
        now = time.monotonic()
        for i, (req, fut) in enumerate(batch):
            if fut.done():
                continue
            if req.deadline is not None and now >= req.deadline:
                self._c["expired"].inc()
                obs.TRACER.event("daemon.expired", req.trace)
                self._settle_exc(fut, DeadlineExceeded(
                    "expired in the admission queue"))
                continue
            if not self._assign(req, fut):
                # no live worker at all: put the whole remaining claim
                # back and let the heartbeat loop respawn — restore works
                # even on a closed (draining) queue
                self._queue.restore(batch[i:])
                time.sleep(self._poll_s)
                return

    def _assign(self, req: SimRequest, fut: SimFuture) -> bool:
        """Route one admitted request onto a worker backlog; returns
        False when no worker is alive (caller restores the claim)."""
        victim = None
        with self._lock:
            alive = [wid for wid, h in self._pool.items()
                     if h is not None and h.alive]
            if not alive:
                return False
            version = self._streams.get(req.stream, {}).get("version", 0)
            depths = {wid: len(self._winflight[wid]) + len(self._backlog[wid])
                      for wid in alive}
            wid = router.route(req.stream, version, alive, depths,
                               self.spill_depth)
            spilled = wid != router.affine_worker(req.stream, version, alive)
            bl = self._backlog[wid]
            # priority insertion: higher class first, FIFO within a class
            idx = len(bl)
            while idx > 0 and bl[idx - 1][0].priority < req.priority:
                idx -= 1
            bl.insert(idx, (req, fut))
            # preemption: the window is full AND something strictly less
            # urgent is still waiting behind it — bump the tail back to
            # the main queue (it was never dispatched: no attempt burned,
            # and on re-route the saturated depth makes it spill)
            if (len(self._winflight[wid]) >= self.worker_window
                    and bl[-1][0].priority < req.priority):
                victim = bl.pop()
        if spilled:
            self._c["spilled"].inc()
        obs.TRACER.event("daemon.routed", req.trace,
                         attrs={"worker": wid, "spilled": spilled,
                                "depth": depths[wid]})
        if victim is not None:
            self._c["preempted"].inc()
            obs.TRACER.event("daemon.preempted", victim[0].trace,
                             attrs={"worker": wid, "by_seed": req.seed,
                                    "by_priority": req.priority})
            self._queue.restore([victim])
        return True

    def _backlog_depth(self) -> int:
        with self._lock:
            return sum(len(b) for b in self._backlog.values())

    def _flush_backlogs(self) -> None:
        for wid in range(self.workers):
            self._flush_worker(wid)

    def _flush_worker(self, wid: int) -> None:
        while True:
            with self._lock:
                if not self._backlog[wid]:
                    return
                handle = self._pool.get(wid)
                if handle is None or not handle.alive:
                    orphaned, self._backlog[wid] = self._backlog[wid], []
                elif len(self._winflight[wid]) >= self.worker_window:
                    return
                else:
                    orphaned = None
                    req, fut = self._backlog[wid].pop(0)
            if orphaned is not None:
                # target died after routing but before dispatch: back to
                # the main queue without burning an attempt
                self._queue.restore(orphaned)
                return
            self._forward(req, fut, handle, wid)

    def _forward(self, req: SimRequest, fut: SimFuture,
                 handle: WorkerHandle, wid: int) -> None:
        if not handle.client.alive:
            # the worker died between the backlog's liveness check and
            # this forward: put the request back without burning an
            # attempt
            self._queue.restore([(req, fut)])
            return
        try:
            self._ensure_stream(handle, req.stream)
        except (TransportError, TimeoutError):
            self._queue.restore([(req, fut)])
            return
        spec = {"algo": req.algo, "seed": req.seed, "T": req.T,
                "budget": req.budget, "stream": req.stream,
                "cfg": req.cfg, "exact": req.exact,
                "scenario": req.scenario, "priority": req.priority}
        remaining = (None if req.deadline is None
                     else max(req.deadline - time.monotonic(), 1e-3))
        with self._lock:
            self._winflight[wid][id(fut)] = (req, fut)
        fut.dispatch_t0 = time.monotonic()   # span anchor, observe-only
        rfut = handle.client.call_async("submit", spec,
                                        deadline_s=remaining,
                                        trace=req.trace)
        rfut.add_done_callback(
            lambda done: self._on_worker_reply(req, fut, done, wid))

    def _ensure_stream(self, handle: WorkerHandle, name: str) -> None:
        """Lazy registration: a spill target (or a worker that respawned
        while a stream re-homed) only learns a stream when traffic for
        it actually lands there."""
        with self._lock:
            s = self._streams.get(name)
            version = s["version"] if s else None
            known = handle.streams.get(name)
        if s is None or known == version:
            return
        handle.client.call("register_stream",
                           {"name": name, "preds": s["preds"],
                            "y": s["y"], "costs": s["costs"]},
                           deadline_s=60.0)
        with self._lock:
            handle.streams[name] = version

    def _on_worker_reply(self, req: SimRequest, fut: SimFuture,
                         rfut, wid: int) -> None:
        with self._lock:
            self._winflight[wid].pop(id(fut), None)
        t0 = getattr(fut, "dispatch_t0", None)
        attempt = getattr(fut, "attempts", 0)
        exc = rfut.exception(timeout=0)
        if exc is None:
            value = rfut.result(timeout=0)
            self._c["completed"].inc()
            # pass-through: the worker's wire tree goes back out to the
            # client verbatim (bit-exact both hops); only the execution
            # METADATA is annotated with who served it
            if isinstance(value, dict):
                execution = value.setdefault("execution", {})
                execution["worker"] = wid
                if req.trace:
                    execution["trace_id"] = req.trace.get("trace_id")
            obs.TRACER.record("daemon.dispatch", req.trace, t0=t0,
                              attrs={"worker": wid, "attempt": attempt,
                                     "outcome": "ok"})
            obs.TRACER.event("daemon.completed", req.trace,
                             attrs={"worker": wid})
            self._settle_result(fut, value)
            return
        obs.TRACER.record("daemon.dispatch", req.trace, t0=t0,
                          attrs={"worker": wid, "attempt": attempt,
                                 "outcome": type(exc).__name__})
        if isinstance(exc, (ConnectionLost, WorkerDied, TimeoutError)):
            expired = (req.deadline is not None
                       and time.monotonic() >= req.deadline)
            fut.attempts = attempt + 1
            if fut.attempts <= self.retry_limit and not expired \
                    and not self._stopped.is_set():
                self._c["retried"].inc()
                obs.TRACER.event("daemon.retried", req.trace,
                                 attrs={"worker": wid,
                                        "attempt": fut.attempts})
                self._queue.restore([(req, fut)])
                return
            self._c["worker_failed"].inc()
            obs.TRACER.event("daemon.failed", req.trace,
                             attrs={"worker": wid,
                                    "attempts": fut.attempts})
            self._settle_exc(fut, WorkerDied(
                f"worker lost after {fut.attempts} attempt(s): {exc}"))
            return
        self._settle_exc(fut, exc)      # typed pass-through (no retry)

    @staticmethod
    def _settle_result(fut: SimFuture, value) -> None:
        try:
            fut.set_result(value)
        except RuntimeError:
            pass                        # lost a settle race: already done

    @staticmethod
    def _settle_exc(fut: SimFuture, exc: BaseException) -> None:
        try:
            fut.set_exception(exc)
        except RuntimeError:
            pass

    # -- observability / shutdown -----------------------------------------

    def status(self) -> dict:
        with self._lock:
            workers = []
            for wid in range(self.workers):
                h = self._pool.get(wid)
                workers.append({
                    "id": wid,
                    "alive": h is not None and h.alive,
                    "pid": h.pid if h else None,
                    "epoch": h.epoch if h else None,
                    "device": h.device if h else None,
                    "restarts": self._wrestarts[wid],
                    "inflight": len(self._winflight[wid]),
                    "backlog": len(self._backlog[wid]),
                    "streams": sorted(h.streams) if h else [],
                })
            inflight = sum(len(m) for m in self._winflight.values())
            backlog = sum(len(b) for b in self._backlog.values())
            streams = {n: s["version"] for n, s in self._streams.items()}
            restarts = self._restarts
        counters = self.counters        # legacy flat view of the registry
        # "worker" stays the single-worker view (slot 0 + pool-wide
        # restarts) so pre-pool tooling and tests keep reading it
        w0 = workers[0]
        out = {"pid": os.getpid(), "draining": self._draining,
               "queued": len(self._queue), "inflight": inflight,
               "backlog": backlog, "streams": streams,
               "counters": counters, "workers": workers,
               "worker": {"alive": w0["alive"], "pid": w0["pid"],
                          "epoch": w0["epoch"], "restarts": restarts}}
        if self._rpc is not None:
            host, port = self._rpc.addr
            out["addr"] = f"{host}:{port}"
        # the full typed metrics tree: daemon instruments merged with
        # every live worker's snapshot (fetched over the stats RPC)
        out["metrics"] = self.metrics_doc(per_worker_deadline_s=0.35)
        return out

    def metrics_doc(self, per_worker_deadline_s: float = 2.0) -> dict:
        """The fleet metrics tree: the daemon's own snapshot, each live
        worker's snapshot (fetched over the existing ``stats`` RPC, in
        parallel), and their merge.

        Fault containment: snapshots are fetched fresh from LIVE workers
        only and never cached, so a SIGKILLed worker simply drops out of
        the merge (no double-count from a stale snapshot), and a partial
        or malformed snapshot from a dying peer is validated by the
        merge and skipped rather than wedging the whole document —
        ``workers_reporting`` says who answered.
        """
        snap = self.metrics.snapshot()
        with self._lock:
            handles = [(wid, h) for wid, h in sorted(self._pool.items())
                       if h is not None and h.alive]
            total = self.workers
        pending = []
        for wid, handle in handles:
            try:
                pending.append((wid, handle.client.call_async(
                    "stats", {}, deadline_s=per_worker_deadline_s)))
            except Exception:           # noqa: BLE001 - dead peer: skip
                continue
        worker_snaps: dict = {}
        merged = self.metrics.merge([snap])
        for wid, rfut in pending:
            try:
                reply = rfut.result(timeout=per_worker_deadline_s + 1.0)
                ws = (reply or {}).get("metrics")
                if ws:
                    # merge incrementally: a torn snapshot (or one whose
                    # histogram bounds conflict with what's already
                    # merged) raises HERE and is skipped — it must not
                    # poison the document or wedge the caller
                    merged = self.metrics.merge([merged, ws])
                    worker_snaps[wid] = ws
            except Exception:           # noqa: BLE001 - partial/typed: skip
                continue
        return {"daemon": snap,
                "workers": {str(wid): s for wid, s in worker_snaps.items()},
                "merged": merged,
                "workers_reporting": len(worker_snaps),
                "workers_total": total}

    def _h_trace(self, params, ctx):
        return self.trace_doc(params.get("trace_id"),
                              limit=params.get("limit"))

    def trace_doc(self, trace_id: Optional[str] = None,
                  limit: Optional[int] = None) -> dict:
        """Without ``trace_id``: the daemon tracer's recent traces.
        With one: that request's spans stitched across the daemon and
        every live worker (each worker's ``trace`` RPC returns its ring
        buffer slice), sorted by anchored wall time."""
        if trace_id is None:
            return {"traces": obs.TRACER.traces(limit=int(limit or 50))}
        spans = obs.TRACER.spans(trace_id)
        with self._lock:
            handles = [(wid, h) for wid, h in sorted(self._pool.items())
                       if h is not None and h.alive]
        for wid, handle in handles:
            try:
                dump = handle.client.call("trace", {"trace_id": trace_id},
                                          deadline_s=2.0)
                spans.extend(dump.get("spans", []))
            except Exception:           # noqa: BLE001 - stub/dead: skip
                continue
        spans.sort(key=lambda s: s.get("t0_wall", 0.0))
        return {"trace_id": trace_id, "spans": spans}

    def reject_count(self) -> int:
        return self._c["rejected"].value

    def drain_and_stop(self, timeout: float = 60.0) -> None:
        """Graceful shutdown: reject new, serve admitted, stop every
        worker, close the front endpoint."""
        if self._draining:
            self._stopped.wait(timeout)
            return
        self._draining = True
        self._queue.close()
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if not self._pending_count():
                break
            time.sleep(self._poll_s)
        with self._lock:
            pool = {wid: h for wid, h in self._pool.items()
                    if h is not None}
            for wid in self._pool:
                self._pool[wid] = None
            leftovers = []
            for m in self._winflight.values():
                leftovers.extend(m.values())
                m.clear()
            for b in self._backlog.values():
                leftovers.extend(b)
                b[:] = []
        # drain timed out: nothing may hang — fail the stragglers typed,
        # including anything still sitting in the (closed) front queue
        leftovers.extend(self._queue.drain(max_n=1 << 30, wait_s=0.0))
        for req, fut in leftovers:
            self._settle_exc(fut, WorkerDied("daemon stopped mid-flight"))
        for wid, handle in sorted(pool.items()):
            try:
                handle.client.call("shutdown", {}, deadline_s=5.0)
                if handle.proc is not None:
                    handle.proc.wait(timeout=15.0)
            except Exception:           # noqa: BLE001
                pass
            handle.kill()
        self._stopped.set()
        if self._rpc is not None:
            self._rpc.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.serve.daemon",
        description="serving management daemon (foreground; use "
                    "'python -m repro.launch.served start' to detach)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--workers", type=int, default=1,
                    help="worker subprocesses in the pool (stream-affine "
                         "routing across them)")
    ap.add_argument("--max-pending", type=int, default=256)
    ap.add_argument("--retry-limit", type=int, default=1)
    ap.add_argument("--heartbeat-s", type=float, default=1.0)
    ap.add_argument("--worker-window", type=int, default=32,
                    help="max dispatched-but-unreplied requests per worker")
    ap.add_argument("--spill-depth", type=int, default=32,
                    help="affine-worker depth at which requests spill to "
                         "the least-loaded worker")
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--pidfile", default=None,
                    help="JSON pidfile ({pid, host, port}); removed on "
                         "clean exit")
    args = ap.parse_args(argv)

    obs.set_service("daemon")
    daemon = ServeDaemon(
        host=args.host, port=args.port, max_pending=args.max_pending,
        retry_limit=args.retry_limit, heartbeat_s=args.heartbeat_s,
        workers=args.workers, worker_window=args.worker_window,
        spill_depth=args.spill_depth,
        worker_args={"max_batch": args.max_batch,
                     "max_wait_ms": args.max_wait_ms})
    daemon.start()
    host, port = daemon.addr
    info = {"pid": os.getpid(), "host": host, "port": port,
            "workers": daemon.workers}
    if args.pidfile:
        with open(args.pidfile, "w") as fh:
            json.dump(info, fh)
    print(READY_PREFIX + json.dumps(info), flush=True)

    import signal
    signal.signal(signal.SIGTERM,
                  lambda *a: threading.Thread(target=daemon.drain_and_stop,
                                              daemon=True).start())
    signal.signal(signal.SIGINT,
                  lambda *a: threading.Thread(target=daemon.drain_and_stop,
                                              daemon=True).start())
    daemon._stopped.wait()
    if args.pidfile and os.path.exists(args.pidfile):
        os.unlink(args.pidfile)
    return 0


if __name__ == "__main__":
    sys.exit(main())
