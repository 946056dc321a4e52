"""Multi-host data plane: process-isolated workers behind a control-plane RPC.

The paper's deployment model (§3.2, Fig. 2) runs each worker as its own
cloud process; only metadata crosses the control plane, while dataframes move
worker-to-worker over the data plane. ``LocalCluster`` collapses both planes
into one Python process — fine for tests, but "worker failure" is simulated,
memory is shared by accident, and one GIL caps the fleet. This module splits
the planes for real (DataFlower's control-/data-flow decoupling):

  * **control plane** — a tiny length-prefixed RPC (the same framing as the
    flight channel) carrying ``plan``/``dispatch``/``describe``/``cancel``/
    ``heartbeat``/``evict``/``shutdown`` between the engine and each worker
    daemon. Dispatch responses are *streams*: every user ``print`` and system
    event hops back over the control channel as it happens, so a remote run
    still "feels local".
  * **data plane** — untouched. Run-scoped ``TableHandle``\\ s already name
    where buffers live (flight host:port, mmap path, objectstore key), so
    shard exchange, gather reads, and cross-worker fetches work unchanged
    across process boundaries.
  * **WorkerDaemon** — hosts a real ``runtime.Worker`` (DataTransport +
    FlightServer + scan/result caches + a per-process PackageStore) behind
    the control socket; ``repro.launch.worker_main`` is its entrypoint, so a
    worker is joinable by address from anywhere that shares the object store.
  * **RemoteWorker / RemoteCluster** — the engine-facing side. They implement
    ``contract.WorkerLike`` / ``contract.ClusterLike``, so late binding,
    bounded queues, per-shard retry, speculation, and transitive lost-input
    recovery drive a process fleet exactly as they drive threads.

Failure model (SIGKILL a worker process mid-run):

  a. in-flight dispatches on it surface as ``WorkerFailure`` (socket reset /
     EOF) -> the engine retries on another worker;
  b. its zerocopy/flight buffers vanish -> consumers hit ``ShardUnavailable``
     / ``HandleUnavailable`` -> per-shard producer re-execution;
  c. the heartbeat thread marks it dead and calls ``engine.worker_lost``,
     which proactively invalidates its memory-resident outputs so recovery
     starts before a consumer trips the hole (mmap/objectstore outputs are
     path/key-addressed and survive the process).
"""
from __future__ import annotations

import os
import pickle
import select
import socket
import subprocess
import sys
import threading
import time
import traceback
import uuid
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Set, Tuple

from repro.core.channels import (DataTransport, TableHandle, _recv_frame,
                                 _send_frame)
from repro.core.physical import PhysicalPlan, WorkerProfile
from repro.core.runtime import (Client, Event, HandleUnavailable, TaskError,
                                Worker, WorkerFailure)

PROTOCOL_VERSION = 1


# ---------------------------------------------------------------------------
# wire format: length-prefixed pickle frames (control plane is trusted,
# same-tenant infrastructure — mirrors the flight channel's framing)
# ---------------------------------------------------------------------------


def _send_msg(sock: socket.socket, obj) -> None:
    _send_frame(sock, pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL))


def _recv_msg(sock: socket.socket):
    return pickle.loads(_recv_frame(sock))


_ERROR_TYPES = {
    "HandleUnavailable": HandleUnavailable,
    "WorkerFailure": WorkerFailure,
    "TaskError": TaskError,
}


class _UnknownPlan(Exception):
    """Daemon-internal signal: the dispatch referenced a plan the daemon has
    evicted from its LRU; the proxy re-ships the plan and retries once."""


def _map_error(msg: Dict) -> Exception:
    """Rehydrate a daemon-side failure into the exception class the engine's
    recovery paths dispatch on (anything unknown degrades to TaskError)."""
    etype, message = msg.get("etype", ""), msg.get("message", "")
    exc = _ERROR_TYPES.get(etype)
    if exc is not None:
        return exc(message)
    return TaskError(f"{etype}: {message}" if etype else message)


# ---------------------------------------------------------------------------
# daemon side
# ---------------------------------------------------------------------------


class _StreamClient(Client):
    """Daemon-side Client: every event is forwarded over the dispatch
    connection as its own frame, then a final result/error frame ends the
    stream. A vanished caller doesn't abort the task — execution is
    idempotent and the engine will retry or read the cached output."""

    def __init__(self, conn: socket.socket):
        super().__init__()
        self._conn = conn
        self.send_lock = threading.Lock()
        self._broken = False

    def emit(self, event: Event) -> None:
        super().emit(event)
        if self._broken:
            return
        try:
            with self.send_lock:
                _send_msg(self._conn, {"kind": "event", "event": event})
        except OSError:
            self._broken = True


class WorkerDaemon:
    """Hosts one ``runtime.Worker`` behind the control-plane RPC.

    Thread-per-connection, like the flight server: heartbeats and describes
    stay responsive while long dispatches run. Plans are registered once per
    (client, plan_id) via the ``plan`` op and referenced by id afterwards, so
    a shard fan-out doesn't re-ship plan metadata per task; the registry is
    an LRU (``MAX_PLANS``) so a long-lived joinable daemon serving a warm
    cluster doesn't accumulate one plan per run forever — a dispatch against
    an evicted plan gets ``UnknownPlan`` and the proxy re-ships it."""

    MAX_PLANS = 64

    def __init__(self, worker: Worker, project=None,
                 host: str = "127.0.0.1", port: int = 0):
        self.worker = worker
        self.project = project
        self._plans: "OrderedDict[str, PhysicalPlan]" = OrderedDict()  # guard: _lock
        self._cancelled: Set[Tuple[str, str]] = set()    # guard: _lock
        self._inflight = 0                               # guard: _lock
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((host, port))
        self._srv.listen(64)
        self.host, self.port = self._srv.getsockname()
        self._thread = threading.Thread(target=self._serve, daemon=True,
                                        name=f"control-{self.port}")
        self._thread.start()

    # -- server loop --------------------------------------------------------
    def _serve(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return
            threading.Thread(target=self._handle, args=(conn,),
                             daemon=True).start()

    def _handle(self, conn: socket.socket) -> None:
        try:
            msg = _recv_msg(conn)
            op = msg.get("op")
            handler = getattr(self, f"_op_{op}", None)
            if handler is None:
                _send_msg(conn, {"kind": "error", "etype": "ValueError",
                                 "message": f"unknown op {op!r}"})
                return
            handler(conn, msg)
        except (ConnectionError, OSError, EOFError, pickle.UnpicklingError):
            pass            # caller vanished mid-request
        finally:
            try:
                conn.close()
            except OSError:
                pass

    # -- ops ----------------------------------------------------------------
    def _op_hello(self, conn, msg) -> None:
        t = self.worker.transport
        _send_msg(conn, {"kind": "result", "protocol": PROTOCOL_VERSION,
                         "worker_id": self.worker.worker_id,
                         "pid": os.getpid(),
                         "flight": f"{t.flight.host}:{t.flight.port}"})

    def _op_plan(self, conn, msg) -> None:
        plan: PhysicalPlan = msg["plan"]
        with self._lock:
            self._plans[plan.plan_id] = plan
            self._plans.move_to_end(plan.plan_id)
            while len(self._plans) > self.MAX_PLANS:
                self._plans.popitem(last=False)
        _send_msg(conn, {"kind": "result", "plan_id": plan.plan_id})

    def _op_dispatch(self, conn, msg) -> None:
        with self._lock:
            plan = self._plans.get(msg["plan_id"])
            if plan is not None:
                self._plans.move_to_end(msg["plan_id"])
        if plan is None:
            _send_msg(conn, {"kind": "error", "etype": "UnknownPlan",
                             "message": msg["plan_id"]})
            return
        tid = msg["task_id"]
        task = plan.tasks[tid]
        # a long-lived daemon may outlive the project source it was started
        # with; executing stale code under the plan's (new) cache key would
        # publish wrong results that every content-addressed layer then
        # trusts — refuse instead
        want_hash = getattr(task, "code_hash", None)
        if want_hash and self.project is not None:
            spec = self.project.functions.get(task.name)
            if spec is not None and spec.code_hash != want_hash:
                _send_msg(conn, {"kind": "error", "etype": "TaskError",
                                 "message":
                                 f"stale code for {task.name!r}: worker "
                                 f"{self.worker.worker_id} has "
                                 f"{spec.code_hash}, plan wants {want_hash}; "
                                 f"restart the worker with current project "
                                 f"source"})
                return
            # a contract-only edit (new CombineContract, same body) is
            # invisible to code_hash; running the old partial/combine would
            # publish old-aggregation results under the plan's new
            # contract-folded cache keys — refuse, same as stale code
            want_contract = getattr(task, "contract_id", "")
            if want_contract and spec is not None:
                have_ids = [c.contract_id for c in
                            (spec.combinable,
                             getattr(spec, "exchange", None))
                            if c is not None]
                have = ", ".join(have_ids) if have_ids else "<none>"
                if want_contract not in have_ids:
                    _send_msg(conn, {"kind": "error", "etype": "TaskError",
                                     "message":
                                     f"stale combine contract for "
                                     f"{task.name!r}: worker "
                                     f"{self.worker.worker_id} has {have}, "
                                     f"plan wants {want_contract}; restart "
                                     f"the worker with current project "
                                     f"source"})
                    return
        client = _StreamClient(conn)
        key = (plan.run_id, tid)
        with self._lock:
            self._inflight += 1
            cancelled = key in self._cancelled
            self._cancelled.discard(key)
        try:
            if cancelled:
                self._reply_error(conn, client, "TaskError",
                                  f"cancelled: {tid}")
                return
            handle = self.worker.execute(
                plan, task, msg["handles"], client, msg["put_channel"],
                self.project, edge_channels=msg.get("edge_channels") or {})
            with self._lock:
                cancelled = key in self._cancelled
                self._cancelled.discard(key)
            if cancelled:
                self.worker.transport.evict(handle)
                self._reply_error(conn, client, "TaskError",
                                  f"cancelled: {tid}")
                return
            with client.send_lock:
                _send_msg(conn, {"kind": "result", "handle": handle})
        except HandleUnavailable as e:
            self._reply_error(conn, client, "HandleUnavailable",
                              str(e.args[0]) if e.args else "")
        except WorkerFailure as e:
            self._reply_error(conn, client, "WorkerFailure", str(e))
        except TaskError as e:
            self._reply_error(conn, client, "TaskError", str(e))
        except Exception as e:  # noqa: BLE001 — cross the wire, don't die
            self._reply_error(conn, client, "TaskError",
                              f"{type(e).__name__}: {e}\n"
                              f"{traceback.format_exc()}")
        finally:
            with self._lock:
                self._inflight -= 1

    def _reply_error(self, conn, client: _StreamClient, etype: str,
                     message: str) -> None:
        try:
            with client.send_lock:
                _send_msg(conn, {"kind": "error", "etype": etype,
                                 "message": message})
        except OSError:
            pass            # caller already gone; engine sees WorkerFailure

    def _op_heartbeat(self, conn, msg) -> None:
        with self._lock:
            inflight = self._inflight
        _send_msg(conn, {"kind": "result", "ok": True, "ts": time.time(),
                         "inflight": inflight,
                         "alive": self.worker.alive})

    def _op_describe(self, conn, msg) -> None:
        t = self.worker.transport
        with self._lock:
            plans = sorted(self._plans)
            inflight = self._inflight
        _send_msg(conn, {"kind": "result",
                         "worker_id": self.worker.worker_id,
                         "pid": os.getpid(),
                         "alive": self.worker.alive,
                         "inflight": inflight,
                         "plans": plans,
                         "transport_stats": dict(t.stats),
                         "scan_cache": dict(self.worker.scan_cache.stats),
                         "result_cache": dict(self.worker.result_cache.stats),
                         "flight": f"{t.flight.host}:{t.flight.port}"})

    def _op_cancel(self, conn, msg) -> None:
        with self._lock:
            self._cancelled.add((msg["run_id"], msg["task_id"]))
        _send_msg(conn, {"kind": "result", "cancelled": True})

    def _op_evict(self, conn, msg) -> None:
        self.worker.transport.evict(msg["handle"])
        _send_msg(conn, {"kind": "result", "evicted": msg["handle"].key})

    def _op_shutdown(self, conn, msg) -> None:
        _send_msg(conn, {"kind": "result", "stopping": True})
        self._stop.set()

    # -- lifecycle ----------------------------------------------------------
    def serve_forever(self) -> None:
        self._stop.wait()

    def close(self) -> None:
        self._stop.set()
        try:
            self._srv.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._srv.close()
        except OSError:
            pass
        self.worker.transport.close()


# ---------------------------------------------------------------------------
# engine side: proxies
# ---------------------------------------------------------------------------


class _RemoteTransportView:
    """Client-side view of a remote worker's DataTransport (TransportLike).

    Reads resolve through a shared local resolver transport — handles are
    location-addressed (flight host:port / mmap path / objectstore key), so
    no RPC is needed to fetch. Evict IS an RPC: only the daemon owns the
    buffers; a dead daemon means they're already gone, so it's best-effort."""

    def __init__(self, proxy: "RemoteWorker", resolver: DataTransport):
        self._proxy = proxy
        self._resolver = resolver

    def get(self, handle, columns=None, via=None):
        return self._resolver.get(handle, columns=columns, via=via)

    def has_local(self, key: str) -> bool:
        return False

    def evict(self, handle) -> None:
        try:
            self._proxy.evict(handle)
        except (WorkerFailure, ConnectionError, OSError):
            pass

    def close(self) -> None:
        pass                # the resolver is cluster-owned


class RemoteWorker:
    """Engine-facing proxy for one worker daemon process (WorkerLike).

    ``execute`` opens a dispatch connection, forwards streamed events into
    the run's Client, and maps the final frame back onto the engine's
    exception taxonomy; a reset/EOF mid-task (the process was SIGKILLed)
    surfaces as WorkerFailure, which the engine retries elsewhere.

    Joining is *lazy*: the spawner hands over a ``port_waiter`` and the
    first RPC resolves it, so ``RemoteCluster.provision`` (called under the
    engine's dispatch lock) returns in milliseconds instead of stalling
    every run behind a process boot. ``mark_down`` aborts any dispatch recv
    blocked on a peer that died without a TCP reset (node loss, partition)
    by closing the registered in-flight sockets."""

    def __init__(self, profile: WorkerProfile, host: str,
                 port: Optional[int] = None,
                 proc: Optional[subprocess.Popen] = None,
                 resolver: Optional[DataTransport] = None,
                 rpc_timeout_s: float = 10.0,
                 port_waiter: Optional[Callable[[], int]] = None):
        self.profile = profile
        self.worker_id = profile.worker_id
        self.host = host
        self.addr: Optional[Tuple[str, int]] = (
            (host, port) if port is not None else None)
        self.proc = proc
        self.alive = True
        self.rpc_timeout_s = rpc_timeout_s
        self.transport = _RemoteTransportView(self, resolver)
        self._plan_lock = threading.Lock()
        self._plans_sent: Set[str] = set()          # guard: _plan_lock
        self._port_waiter = port_waiter
        self._join_lock = threading.Lock()
        self._socks: Set[socket.socket] = set()     # guard: _socks_lock
        self._socks_lock = threading.Lock()

    @property
    def joined(self) -> bool:
        return self.addr is not None

    def _ensure_joined(self) -> Tuple[str, int]:
        """Resolve the daemon's control address, waiting for the port
        announcement on first use (off the engine lock, in the pool thread
        that actually needs the worker)."""
        addr = self.addr
        if addr is not None:
            return addr
        with self._join_lock:
            if self.addr is not None:
                return self.addr
            if not self.alive:
                raise WorkerFailure(f"worker {self.worker_id} is down")
            if self._port_waiter is None:
                raise WorkerFailure(
                    f"worker {self.worker_id} has no control address")
            try:
                port = self._port_waiter()
            except WorkerFailure:
                self.alive = False
                raise
            self.addr = (self.host, port)
            return self.addr

    def mark_down(self) -> None:
        """Flip liveness and abort blocked dispatch recvs: a peer that dies
        without sending a reset (power loss, partition) would otherwise pin
        an engine pool thread forever."""
        self.alive = False
        with self._socks_lock:
            socks, self._socks = list(self._socks), set()
        for s in socks:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                s.close()
            except OSError:
                pass

    # -- one-shot RPCs ------------------------------------------------------
    def _rpc(self, msg: Dict, timeout: Optional[float] = None):
        if not self.alive:
            raise WorkerFailure(f"worker {self.worker_id} is down")
        addr = self._ensure_joined()
        timeout = self.rpc_timeout_s if timeout is None else timeout
        try:
            sock = socket.create_connection(addr, timeout=timeout)
        except OSError as e:
            raise WorkerFailure(
                f"worker {self.worker_id} unreachable: {e}") from e
        try:
            sock.settimeout(timeout)
            _send_msg(sock, msg)
            reply = _recv_msg(sock)
        except (OSError, EOFError, pickle.UnpicklingError) as e:
            raise WorkerFailure(
                f"worker {self.worker_id} RPC {msg.get('op')!r} failed: "
                f"{e}") from e
        finally:
            sock.close()
        if reply.get("kind") == "error":
            raise _map_error(reply)
        return reply

    def hello(self) -> Dict:
        return self._rpc({"op": "hello"})

    def heartbeat(self, timeout: float = 2.0) -> Dict:
        return self._rpc({"op": "heartbeat"}, timeout=timeout)

    def describe(self) -> Dict:
        return self._rpc({"op": "describe"})

    def cancel(self, run_id: str, task_id: str) -> Dict:
        return self._rpc({"op": "cancel", "run_id": run_id,
                          "task_id": task_id})

    def evict(self, handle: TableHandle) -> Dict:
        return self._rpc({"op": "evict", "handle": handle})

    # -- plan shipping ------------------------------------------------------
    def _ensure_plan(self, plan: PhysicalPlan) -> None:
        """Register the plan on the daemon exactly once per proxy; the lock
        makes registration synchronous, so a concurrent shard fan-out never
        dispatches against a plan id the daemon hasn't seen yet."""
        with self._plan_lock:
            if plan.plan_id in self._plans_sent:
                return
            self._rpc({"op": "plan", "plan": plan})
            self._plans_sent.add(plan.plan_id)

    # -- WorkerLike ---------------------------------------------------------
    def execute(self, plan: PhysicalPlan, task, handles, client: Client,
                put_channel: str, project=None,
                edge_channels: Optional[Dict[str, str]] = None) -> TableHandle:
        if not self.alive:
            raise WorkerFailure(f"worker {self.worker_id} is down")
        self._ensure_plan(plan)
        # ship only the parent handles this task consumes; a missing parent
        # stays missing so the daemon raises HandleUnavailable exactly like
        # an in-process worker would
        needed: Dict[str, TableHandle] = {}
        for edge in getattr(task, "inputs", ()):
            h = handles.get(edge.parent_task)
            if h is not None:
                needed[edge.parent_task] = h
        try:
            return self._dispatch(plan, task, needed, client, put_channel,
                                  edge_channels)
        except _UnknownPlan:
            # a long-lived daemon evicted the plan from its LRU between runs:
            # re-ship it and retry once
            with self._plan_lock:
                self._plans_sent.discard(plan.plan_id)
            self._ensure_plan(plan)
            return self._dispatch(plan, task, needed, client, put_channel,
                                  edge_channels)

    def _dispatch(self, plan: PhysicalPlan, task,
                  needed: Dict[str, TableHandle], client: Client,
                  put_channel: str,
                  edge_channels: Optional[Dict[str, str]]) -> TableHandle:
        addr = self._ensure_joined()
        timeout_s = getattr(task, "timeout_s", 0) or None
        try:
            sock = socket.create_connection(addr, timeout=self.rpc_timeout_s)
        except OSError as e:
            raise WorkerFailure(
                f"worker {self.worker_id} unreachable: {e}") from e
        with self._socks_lock:
            self._socks.add(sock)       # mark_down aborts a silent-death hang
        try:
            # a killed process resets the socket and a silently-dead one is
            # aborted by mark_down; the explicit deadline only bounds
            # genuinely wedged tasks
            sock.settimeout(timeout_s + 30.0 if timeout_s else 60.0)
            deadline = (time.monotonic() + timeout_s + 30.0
                        if timeout_s else None)
            _send_msg(sock, {"op": "dispatch", "plan_id": plan.plan_id,
                             "task_id": task.task_id, "handles": needed,
                             "put_channel": put_channel,
                             "edge_channels": dict(edge_channels or {})})
            while True:
                # wait for readability in short slices, re-checking
                # liveness each slice: mark_down's cross-thread
                # shutdown+close can lose the race with this thread
                # re-entering recv (the fd may even be reused by a new
                # dispatch), leaving a recv that blocks forever on a
                # worker everyone else knows is dead
                while True:
                    if not self.alive:
                        raise WorkerFailure(
                            f"worker {self.worker_id} marked down "
                            f"mid-task {task.task_id}")
                    if deadline is not None and time.monotonic() > deadline:
                        raise WorkerFailure(
                            f"worker {self.worker_id} timed out on task "
                            f"{task.task_id} ({timeout_s:.0f}s limit)")
                    try:
                        readable, _, _ = select.select([sock], [], [], 0.5)
                    except (OSError, ValueError) as e:
                        raise WorkerFailure(
                            f"worker {self.worker_id} lost mid-task "
                            f"{task.task_id}: {e}") from e
                    if readable:
                        break
                try:
                    msg = _recv_msg(sock)
                except (OSError, EOFError, pickle.UnpicklingError) as e:
                    raise WorkerFailure(
                        f"worker {self.worker_id} lost mid-task "
                        f"{task.task_id}: {e}") from e
                kind = msg.get("kind")
                if kind == "event":
                    client.emit(msg["event"])
                elif kind == "result":
                    return msg["handle"]
                elif msg.get("etype") == "UnknownPlan":
                    raise _UnknownPlan(plan.plan_id)
                else:
                    raise _map_error(msg)
        finally:
            with self._socks_lock:
                self._socks.discard(sock)
            sock.close()

    def kill(self) -> None:
        """Chaos hook (WorkerLike): SIGKILL the daemon — its in-memory
        buffers die with the process, exactly like real node loss."""
        self.mark_down()
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass

    def close(self) -> None:
        """Graceful shutdown: ask the daemon to stop, then reap it (a
        still-booting daemon that never joined gets SIGTERM directly)."""
        asked = False
        if self.alive and self.joined:
            try:
                self._rpc({"op": "shutdown"}, timeout=2.0)
                asked = True
            except (WorkerFailure, ConnectionError, OSError):
                pass
        self.mark_down()
        if self.proc is not None and self.proc.poll() is None:
            if not asked:
                self.proc.terminate()
            try:
                self.proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                try:
                    self.proc.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    pass


# ---------------------------------------------------------------------------
# project loading (daemon side)
# ---------------------------------------------------------------------------


def load_project_spec(spec: str):
    """Resolve ``'pkg.module:attr'`` or ``'/path/file.py:attr'`` to a
    Project; ``attr`` may be the Project itself or a zero-arg factory.
    The daemon loads the same project source the control plane planned
    against, so function specs (names, envs, code hashes) line up."""
    path, sep, attr = spec.rpartition(":")
    if not sep or not attr:
        raise ValueError(f"project spec {spec!r} must look like "
                         f"'pkg.module:attr' or '/path/file.py:attr'")
    if path.endswith(".py"):
        import importlib.util

        modname = f"repro_project_{uuid.uuid4().hex[:8]}"
        mspec = importlib.util.spec_from_file_location(modname, path)
        if mspec is None or mspec.loader is None:
            raise ImportError(f"cannot load project file {path!r}")
        mod = importlib.util.module_from_spec(mspec)
        sys.modules[modname] = mod
        mspec.loader.exec_module(mod)
    else:
        import importlib

        mod = importlib.import_module(path)
    obj = getattr(mod, attr)
    from repro.api import Project

    if not isinstance(obj, Project) and callable(obj):
        obj = obj()
    if not isinstance(obj, Project):
        raise TypeError(f"{spec!r} resolved to {type(obj).__name__}, "
                        f"not a Project")
    return obj


# ---------------------------------------------------------------------------
# RemoteCluster
# ---------------------------------------------------------------------------


class RemoteCluster:
    """A process-isolated data plane (ClusterLike): every worker is its own
    OS process, spawned on demand via ``subprocess`` and joined by control
    address. Implements the same surface the ExecutionEngine consumes from
    ``LocalCluster``, so ``bp.run(cluster=...)`` / ``submit_run`` and every
    fault-tolerance/sharding feature work unchanged — but against genuinely
    isolated memory, one GIL per worker, and real process death.

    ``project`` is a ``load_project_spec`` string handed to each daemon so
    workers can resolve FunctionSpecs by name (the control plane only ships
    plan metadata, never code). A heartbeat thread detects dead processes
    and feeds ``engine.worker_lost`` for proactive recovery.

    A TPU belongs to one process, so daemons do not share a chip: on a
    one-chip host at most one of them can open it, and a ``backend="jax"``
    operator in any other raises (``repro.kernels.ops``) instead of running
    on the CPU. Device operators run on a ``LocalCluster`` inside the
    process that holds the chip."""

    def __init__(self, catalog, object_store, scratch_root: str,
                 n_workers: int = 2, memory_gb: float = 4.0,
                 project: Optional[str] = None,
                 python_exe: Optional[str] = None,
                 heartbeat_interval_s: float = 0.5,
                 heartbeat_misses: int = 3,
                 spawn_timeout_s: float = 120.0):
        self.catalog = catalog
        self.object_store = object_store
        self.scratch_root = os.path.abspath(scratch_root)
        os.makedirs(self.scratch_root, exist_ok=True)
        self.project_spec = project
        self.python_exe = python_exe or sys.executable
        self.heartbeat_interval_s = heartbeat_interval_s
        self.heartbeat_misses = heartbeat_misses
        self.spawn_timeout_s = spawn_timeout_s
        self.workers: Dict[str, RemoteWorker] = {}    # guard: _lock
        self._lock = threading.Lock()
        self._engine = None                           # guard: _lock
        self._closed = False                          # guard: _lock
        self._hb_misses: Dict[str, int] = {}
        # location-addressed reads (RunResult.read, degraded fetches) resolve
        # through one client-side transport; its flight server sits idle —
        # the control plane only ever *fetches*
        self._resolver = DataTransport(
            os.path.join(self.scratch_root, "client", "spill"),
            object_store=object_store)
        try:
            for i in range(n_workers):
                self._add(WorkerProfile(f"worker-{i}", memory_gb=memory_gb))
        except Exception:
            self.close()
            raise
        self._hb_stop = threading.Event()
        self._hb_thread = threading.Thread(target=self._heartbeat_loop,
                                           daemon=True,
                                           name="remote-heartbeat")
        self._hb_thread.start()

    # -- spawning -----------------------------------------------------------
    def _spawn(self, profile: WorkerProfile) -> RemoteWorker:
        """Start the daemon process and return its proxy immediately: the
        Popen itself is milliseconds, and the port-file wait happens lazily
        in whichever pool thread first uses the worker — `provision` runs
        under the engine's dispatch lock and must never stall every run
        behind a process boot."""
        wid = profile.worker_id
        port_file = os.path.join(self.scratch_root, f"{wid}.port")
        if os.path.exists(port_file):
            os.remove(port_file)
        cmd = [self.python_exe, "-m", "repro.launch.worker_main",
               "--worker-id", wid,
               "--store-root", self.object_store.root,
               "--scratch", self.scratch_root,
               "--memory-gb", str(profile.memory_gb),
               "--cpus", str(profile.cpus),
               "--port-file", port_file]
        if self.project_spec:
            cmd += ["--project", self.project_spec]
        env = dict(os.environ)
        src_root = os.path.dirname(os.path.dirname(os.path.abspath(
            sys.modules["repro"].__file__)))
        extra = [src_root, os.getcwd()]
        if env.get("PYTHONPATH"):
            extra.append(env["PYTHONPATH"])
        env["PYTHONPATH"] = os.pathsep.join(extra)
        proc = subprocess.Popen(cmd, env=env)
        deadline = time.time() + self.spawn_timeout_s

        def wait_for_port() -> int:
            while time.time() < deadline:
                if proc.poll() is not None:
                    raise WorkerFailure(
                        f"worker {wid} exited with code {proc.returncode} "
                        f"during startup")
                try:
                    with open(port_file) as f:
                        txt = f.read().strip()
                    if txt:
                        return int(txt)
                except (FileNotFoundError, ValueError):
                    pass
                time.sleep(0.02)
            proc.kill()
            raise WorkerFailure(f"worker {wid} did not announce a control "
                                f"port within {self.spawn_timeout_s}s")

        return RemoteWorker(profile, "127.0.0.1", proc=proc,
                            resolver=self._resolver,
                            port_waiter=wait_for_port)

    def _add(self, profile: WorkerProfile) -> RemoteWorker:
        proxy = self._spawn(profile)
        with self._lock:
            self.workers[profile.worker_id] = proxy
            engine, n = self._engine, len(self.workers)
        if engine is not None:
            engine.fleet_resized(n)
        return proxy

    # -- ClusterLike --------------------------------------------------------
    def engine(self):
        from repro.core.engine import ExecutionEngine

        with self._lock:
            if self._engine is None:
                self._engine = ExecutionEngine(self)
            return self._engine

    def profiles(self) -> List[WorkerProfile]:
        with self._lock:
            return [w.profile for w in self.workers.values() if w.alive]

    def provision(self, profile: WorkerProfile) -> RemoteWorker:
        """On-demand VM (paper Fig. 2 step 3) — here, an on-demand process."""
        return self._add(profile)

    def get(self, worker_id: str) -> RemoteWorker:
        with self._lock:
            w = self.workers.get(worker_id)
            known = sorted(self.workers)
        if w is not None:
            return w
        if worker_id.startswith("ondemand-"):
            return self.provision(WorkerProfile(worker_id, memory_gb=8.0,
                                                on_demand=True))
        raise KeyError(f"unknown worker {worker_id!r}; have {known}")

    def healthy_workers(self) -> List[RemoteWorker]:
        with self._lock:
            return [w for w in self.workers.values() if w.alive]

    def kill_worker(self, worker_id: str) -> None:
        """Chaos hook: SIGKILL the worker process and tell the engine now
        (same immediacy as LocalCluster's simulated kill). The kill runs
        off-lock: it triggers engine callbacks that re-enter the cluster."""
        with self._lock:
            w = self.workers[worker_id]
        w.kill()
        self._notify_lost(worker_id)

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            engine, self._engine = self._engine, None
            fleet = list(self.workers.values())
        if getattr(self, "_hb_stop", None) is not None:
            self._hb_stop.set()
        if engine is not None:
            engine.close()
        for w in fleet:
            w.close()
        self._resolver.close()

    # -- failure detection --------------------------------------------------
    def _notify_lost(self, worker_id: str) -> None:
        with self._lock:
            engine = self._engine
        if engine is not None:
            engine.worker_lost(worker_id)

    def _heartbeat_loop(self) -> None:
        """Poll every live worker; a dead process (reaped) or
        ``heartbeat_misses`` consecutive RPC failures marks it down and
        triggers proactive engine-side invalidation of its resident
        outputs."""
        while not self._hb_stop.wait(self.heartbeat_interval_s):
            with self._lock:
                fleet = list(self.workers.items())
            for wid, proxy in fleet:
                if not proxy.alive:
                    continue
                dead = False
                if proxy.proc is not None and proxy.proc.poll() is not None:
                    dead = True
                elif not proxy.joined:
                    continue    # still booting: liveness is the proc poll
                else:
                    try:
                        proxy.heartbeat(
                            timeout=max(self.heartbeat_interval_s, 1.0))
                        self._hb_misses[wid] = 0
                    except (WorkerFailure, ConnectionError, OSError):
                        n = self._hb_misses.get(wid, 0) + 1
                        self._hb_misses[wid] = n
                        dead = n >= self.heartbeat_misses
                if dead and proxy.alive:
                    proxy.mark_down()
                    self._notify_lost(wid)
