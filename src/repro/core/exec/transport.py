"""SubprocessTransport: the worker-pool side of cross-process execution.

Layering contract (unchanged from ``repro.core.transport``): the
RemoteAgent dispatcher is the single master — it decides *when* an
attempt runs; this transport only executes.  Each worker is a long-lived
``repro.core.exec.worker`` daemon process with its own isolated JAX
runtime, connected back over a localhost socket speaking the
length-prefixed pickle protocol.  One task runs per worker at a time, so
``capacity == max_workers`` and the agent's in-flight window maps 1:1
onto processes.

Fault model — a Future returned by ``submit`` always resolves:

- worker returns → result / reconstructed ``RemoteTaskError`` /
  ``ServicePreempted`` (typed result frames; exception *objects* never
  cross the wire);
- worker process exits (crash, SIGKILL, OOM) → the monitor's
  ``proc.poll`` notices within one poll interval and fails the Future
  with ``WorkerCrashed`` — no heartbeat-timeout wait on the fast path;
- worker hangs without dying → missed heartbeats trip the
  ``heartbeat_timeout_s`` backstop, same ``WorkerCrashed``.

Crashed workers are respawned so the agent's checkpoint-aware retry
finds a live pool — under the transport's :class:`FailurePolicy`:
consecutive crashes of the same worker slot back off exponentially
(deterministic jitter), so a crash-looping worker no longer burns the
lifetime ``max_respawns`` cap in seconds, and every respawn (attempt,
streak, delay) is visible in ``stats()``.  The policy's
``attempt_timeout_s`` (or a per-submit override) is enforced by the
monitor: a busy worker whose attempt outlives its deadline is treated
as hung — which is also what rescues a dropped RPC reply.  ``shutdown``
reaps every worker process either way: ``wait=True`` drains in-flight
work first; ``wait=False`` terminates immediately and fails
outstanding Futures.

Chaos hooks: when a :mod:`repro.core.resilience.faults` injector is
armed, the dispatch path consults the ``transport.dispatch`` site after
handing a worker its task (actions ``crash_worker`` / ``stall_heartbeat``
become ``die`` / ``stall`` frames the worker honours), and each worker
channel consults ``protocol.recv`` per inbound frame (``drop`` /
``delay`` of result replies) — every fault mode above is reproducible
from a seed, with detection and recovery exercising the real paths.

Service tasks: ``submit(..., service_control=ctrl)`` bridges the
caller-held :class:`~repro.core.task.ServiceControl` to a replica in the
worker — queued requests and stop/drain/preempt flags flow down; token
streams and terminal request states flow back and are applied to the
client-held Request objects, so streaming semantics match the
in-process transport.
"""
from __future__ import annotations

import collections
import itertools
import os
import subprocess
import sys
import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, Deque, Dict, List, Optional

import jax

from repro.core.exec import pickling, protocol
from repro.core.resilience import faults as rfaults
from repro.core.resilience.policy import FailurePolicy
from repro.core.task import ServicePreempted
from repro.core.transport import Transport


class WorkerCrashed(RuntimeError):
    """The worker process executing a task died (or stopped heartbeating)
    before returning a result."""

    def __init__(self, worker_id: int, pid: Optional[int], label: str,
                 reason: str):
        self.worker_id = worker_id
        self.pid = pid
        super().__init__(
            f"worker {worker_id} (pid {pid}) died while running "
            f"{label or 'a task'}: {reason}")


class RemoteTaskError(RuntimeError):
    """A task fn raised inside a worker.  Carries the remote exception's
    type name and traceback text (the object itself never crosses the
    wire — custom exception signatures don't survive pickling)."""

    def __init__(self, etype: str, message: str, traceback_text: str = ""):
        self.remote_type = etype
        self.remote_traceback = traceback_text
        detail = f"\n--- remote traceback ---\n{traceback_text}" \
            if traceback_text else ""
        super().__init__(f"{etype}: {message}{detail}")


class _Job:
    __slots__ = ("jid", "payload", "future", "label", "service_control",
                 "on_done", "worker_id", "attempt_timeout_s", "deadline")

    def __init__(self, jid: int, payload: bytes, label: str,
                 service_control, on_done,
                 attempt_timeout_s: Optional[float] = None):
        self.jid = jid
        self.payload = payload
        self.label = label
        self.service_control = service_control
        self.on_done = on_done
        self.future: Future = Future()
        self.worker_id: Optional[int] = None
        self.attempt_timeout_s = attempt_timeout_s
        self.deadline: Optional[float] = None  # set at dispatch


class _WorkerHandle:
    __slots__ = ("wid", "proc", "chan", "state", "last_seen", "job",
                 "spawned_at", "devices")

    def __init__(self, wid: int, proc: subprocess.Popen):
        self.wid = wid
        self.proc = proc
        self.chan: Optional[protocol.Channel] = None
        self.state = "starting"  # starting | idle | busy | dead
        self.last_seen = time.time()
        self.job: Optional[_Job] = None
        self.spawned_at = time.time()
        self.devices: Optional[int] = None


class SubprocessTransport(Transport):
    """Pool of worker daemon processes executing pickled task calls."""

    name = "subprocess"
    #: marks transports whose submit crosses a process boundary — the
    #: agent switches to the picklable remote-dispatch path on this flag
    remote = True

    #: task kinds whose bodies need the accelerator.  Workers run JAX on
    #: their own CPU (a chip belongs to one process, and on a TPU host the
    #: parent holds it), so such a task would quietly run on the CPU there.
    DEVICE_KINDS = ("train", "inference")

    _pool_seq = itertools.count()

    def __init__(self, max_workers: int = 2, *,
                 worker_devices: int = 2,
                 heartbeat_s: float = 0.2,
                 heartbeat_timeout_s: float = 3.0,
                 poll_s: float = 0.05,
                 start_timeout_s: float = 120.0,
                 drain_timeout_s: float = 120.0,
                 max_respawns: int = 16,
                 policy: Optional[FailurePolicy] = None,
                 env: Optional[Dict[str, str]] = None):
        import socket as _socket
        self.capacity = max_workers
        self._worker_devices = worker_devices
        self._heartbeat_s = heartbeat_s
        self._heartbeat_timeout_s = max(heartbeat_timeout_s, 3 * heartbeat_s)
        self._poll_s = poll_s
        self._start_timeout_s = start_timeout_s
        self._drain_timeout_s = drain_timeout_s
        self._env = env
        # respawn backoff + attempt deadlines; the default keeps the first
        # respawn near-immediate but makes a crash-looping slot back off
        # exponentially instead of burning the lifetime cap in seconds
        self._policy = policy if policy is not None else FailurePolicy(
            backoff_base_s=0.1, backoff_factor=2.0, backoff_max_s=5.0,
            jitter=0.1)
        # multi-host hook (set by JaxDistributedTransport)
        self._jax_coordinator: Optional[str] = None
        self._jax_num_processes: Optional[int] = None
        self._jax_process_id: Optional[int] = None

        self._cond = threading.Condition()
        self._workers: Dict[int, _WorkerHandle] = {}  # guarded-by: _cond
        self._queue: Deque[_Job] = collections.deque()  # guarded-by: _cond
        self._inflight: Dict[int, _Job] = {}  # guarded-by: _cond (jid -> job)
        self._closed = False  # guarded-by: _cond
        self._respawns = 0  # guarded-by: _cond
        self._crash_streak: Dict[int, int] = {}  # guarded-by: _cond
        self._respawn_due: Dict[int, float] = {}  # guarded-by: _cond
        self._respawn_log: List[Dict[str, Any]] = []  # guarded-by: _cond
        self._jid = itertools.count()

        self._stream_lock = threading.Lock()
        #: rid -> client-held Request the worker streams into
        self._streams: Dict[str, Any] = {}  # guarded-by: _stream_lock

        self._listener = _socket.socket(_socket.AF_INET, _socket.SOCK_STREAM)
        self._listener.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
        self._listener.bind(("127.0.0.1", 0))
        self._listener.listen(max_workers + 4)
        self._listener.settimeout(0.2)
        self._port = self._listener.getsockname()[1]

        pool_id = next(self._pool_seq)
        with self._cond:
            for wid in range(max_workers):
                self._workers[wid] = self._spawn_locked(wid)
        self._threads = [
            threading.Thread(target=self._accept_loop,
                             name=f"rc-exec-accept-{pool_id}", daemon=True),
            threading.Thread(target=self._dispatch_loop,
                             name=f"rc-exec-dispatch-{pool_id}", daemon=True),
            threading.Thread(target=self._monitor_loop,
                             name=f"rc-exec-monitor-{pool_id}", daemon=True),
        ]
        for t in self._threads:
            t.start()

    # -- public --------------------------------------------------------------

    def submit(self, fn: Callable, *args,
               kind: Optional[str] = None,
               service_control=None,
               on_done: Optional[Callable[[Future], None]] = None,
               label: Optional[str] = None,
               attempt_timeout_s: Optional[float] = None,
               **kwargs) -> Future:
        """Ship ``fn(*args, **kwargs)`` to an idle worker.

        Raises ``TypeError`` (naming the offending closure/capture)
        synchronously if the call is unpicklable, and ``RuntimeError`` if
        the transport is shut down.  Execution errors travel through the
        returned Future.  ``on_done`` fires exactly once on a transport
        thread after the Future resolves — never on the submitter's
        thread, so callers may hold scheduling locks while submitting.
        ``attempt_timeout_s`` (default: the transport policy's) bounds
        how long this attempt may run once dispatched before the monitor
        declares the worker hung and fails the Future.  A ``kind`` in
        ``DEVICE_KINDS`` raises ``RuntimeError`` when this process runs on
        a TPU: the workers could only run it on their CPU.
        """
        if kind in self.DEVICE_KINDS and jax.default_backend() == "tpu":
            raise RuntimeError(
                f"{self.name} workers run JAX on their own CPU; refusing a "
                f"{kind!r} task on a TPU host (use the in-process transport)")
        pickling.ensure_picklable(fn, args, kwargs, transport=self.name)
        payload = pickling.format_payload(
            fn, args, kwargs, service=service_control is not None)
        if attempt_timeout_s is None:
            attempt_timeout_s = self._policy.attempt_timeout_s
        job = _Job(next(self._jid), payload,
                   label or getattr(fn, "__qualname__", repr(fn)),
                   service_control, on_done,
                   attempt_timeout_s=attempt_timeout_s)
        with self._cond:
            if self._closed:
                raise RuntimeError("SubprocessTransport is shut down")
            self._queue.append(job)
            self._cond.notify_all()
        return job.future

    def shutdown(self, wait: bool = True) -> None:
        """Stop the pool.  ``wait=True`` drains in-flight attempts (up to
        ``drain_timeout_s``) then asks workers to exit; ``wait=False``
        terminates worker processes immediately and fails their Futures.
        Either way every worker process is reaped — no orphans."""
        with self._cond:
            if self._closed:
                return
            self._closed = True
            queued = list(self._queue)
            self._queue.clear()
            inflight = list(self._inflight.values())
            workers = list(self._workers.values())
            self._cond.notify_all()
        for job in queued:
            self._resolve(job, exc=RuntimeError(
                "transport shut down before dispatch"))
        if wait:
            deadline = time.time() + self._drain_timeout_s
            for job in inflight:
                try:
                    job.future.result(timeout=max(0.0,
                                                  deadline - time.time()))
                except Exception:  # noqa: BLE001 — outcome lives in the Future
                    pass
            for w in workers:
                if w.chan is not None and w.state != "dead":
                    try:
                        w.chan.send({"type": "shutdown"})
                    except (protocol.ConnectionClosed, OSError):
                        pass
        self._reap_all(workers, grace_s=2.0 if wait else 0.2)
        # any Future still unresolved (wait=False, or a drain that timed
        # out on a hung worker) must resolve now — never a hang
        for job in inflight:
            if not job.future.done():
                self._resolve(job, exc=WorkerCrashed(
                    job.worker_id if job.worker_id is not None else -1,
                    None, job.label,
                    "transport shutdown" + ("" if wait else "(wait=False)")))
        try:
            self._listener.close()
        except OSError:
            pass
        for t in self._threads:
            t.join(timeout=1.0)

    def worker_pids(self) -> List[int]:
        """PIDs of live worker processes (test/diagnostic surface)."""
        with self._cond:
            return [w.proc.pid for w in self._workers.values()
                    if w.state != "dead" and w.proc.poll() is None]

    def stats(self) -> Dict[str, Any]:
        """One-lock snapshot of pool health and the respawn history."""
        with self._cond:
            states = collections.Counter(
                w.state for w in self._workers.values())
            now = time.time()
            return {
                "respawns": self._respawns,
                "respawn_log": [dict(r) for r in self._respawn_log],
                "respawn_pending": {
                    wid: max(0.0, due - now)
                    for wid, due in self._respawn_due.items()},
                "crash_streaks": {w: s for w, s in
                                  self._crash_streak.items() if s},
                "queued": len(self._queue),
                "inflight": len(self._inflight),
                "workers": dict(states),
            }

    # -- spawning / reaping ----------------------------------------------------

    def _spawn_locked(self, wid: int) -> _WorkerHandle:
        env = dict(os.environ if self._env is None else self._env)
        env["PYTHONPATH"] = os.pathsep.join(p for p in sys.path if p)
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={self._worker_devices}")
        env.setdefault("JAX_PLATFORMS", "cpu")
        cmd = [sys.executable, "-m", "repro.core.exec.worker",
               "--host", "127.0.0.1", "--port", str(self._port),
               "--worker-id", str(wid),
               "--heartbeat-s", str(self._heartbeat_s)]
        if self._jax_coordinator is not None:
            cmd += ["--jax-coordinator", self._jax_coordinator,
                    "--jax-num-processes", str(self._jax_num_processes),
                    "--jax-process-id", str(self._jax_process_id)]
        proc = subprocess.Popen(cmd, env=env)
        return _WorkerHandle(wid, proc)

    def _reap_all(self, workers: List[_WorkerHandle], grace_s: float) -> None:
        for w in workers:
            if w.proc.poll() is None:
                w.proc.terminate()
        deadline = time.time() + grace_s
        for w in workers:
            try:
                w.proc.wait(timeout=max(0.0, deadline - time.time()))
            except subprocess.TimeoutExpired:
                w.proc.kill()
                w.proc.wait()  # noqa: TMO001 — SIGKILL'd: reap cannot hang
            if w.chan is not None:
                w.chan.close()

    # -- accept / receive ------------------------------------------------------

    def _accept_loop(self) -> None:
        import socket as _socket
        while True:
            with self._cond:
                if self._closed:
                    return
            try:
                sock, _ = self._listener.accept()
            except _socket.timeout:
                continue
            except OSError:
                return  # listener closed under us: shutting down
            chan = protocol.Channel(sock)
            try:
                hello = chan.recv(timeout=10.0)
            except (protocol.ConnectionClosed, _socket.timeout):
                chan.close()
                continue
            wid = hello.get("worker_id")
            with self._cond:
                w = self._workers.get(wid)
                if (w is None or w.state == "dead"
                        or w.proc.pid != hello.get("pid")):
                    stale = True  # a replaced worker's late connection
                else:
                    stale = False
                    chan.fault_filter = self._fault_filter_for(wid)
                    w.chan = chan
                    w.state = "idle"
                    w.last_seen = time.time()
                    self._cond.notify_all()
            if stale:
                chan.close()
            else:
                threading.Thread(target=self._recv_loop, args=(w, chan),
                                 name=f"rc-exec-recv-{wid}",
                                 daemon=True).start()

    @staticmethod
    def _fault_filter_for(wid: int):
        """Per-frame chaos filter for a worker channel: consults the
        armed injector's ``protocol.recv`` site so a planned fault can
        drop or delay an RPC reply (recovery then rides the attempt
        deadline, like a real lost result would)."""
        def _filter(msg: Dict[str, Any]):
            inj = rfaults.active()
            if inj is None:
                return None
            return inj.fire("protocol.recv", worker=wid,
                            mtype=msg.get("type"), task=msg.get("task_id"))
        return _filter

    def _recv_loop(self, w: _WorkerHandle, chan: protocol.Channel) -> None:
        while True:
            try:
                msg = chan.recv()  # noqa: TMO001 — heartbeat monitor backstops a dead peer
            except protocol.ConnectionClosed:
                self._worker_lost(w, "channel closed")
                return
            mtype = msg.get("type")
            if mtype in ("heartbeat", "ready"):
                with self._cond:
                    w.last_seen = time.time()
                    if mtype == "ready":
                        w.devices = msg.get("devices")
            elif mtype == "result":
                self._on_result(w, msg)
            elif mtype == "stream":
                self._apply_stream(msg)
            elif mtype == "finish":
                self._apply_finish(msg)

    # -- dispatch --------------------------------------------------------------

    def _dispatch_loop(self) -> None:
        while True:
            to_send: Optional[tuple] = None
            with self._cond:
                while to_send is None:
                    if self._closed:
                        return
                    job, w = self._pick_locked()
                    if job is None:
                        self._cond.wait(0.5)
                        if self._closed:
                            return
                        continue
                    if not job.future.set_running_or_notify_cancel():
                        continue  # cancelled while queued (agent close)
                    w.state = "busy"
                    w.job = job
                    job.worker_id = w.wid
                    if job.attempt_timeout_s is not None:
                        job.deadline = time.time() + job.attempt_timeout_s
                    self._inflight[job.jid] = job
                    to_send = (job, w)
            job, w = to_send
            try:
                # chaos site first: an injected crash/stall frame lands
                # before the task, so the fault deterministically hits
                # the attempt being dispatched (no result/death race)
                self._maybe_inject_dispatch_fault(job, w)
                w.chan.send({"type": "task", "task_id": job.jid,
                             "payload": job.payload})
            except (protocol.ConnectionClosed, OSError):
                self._worker_lost(w, "send failed")
                continue
            if job.service_control is not None:
                threading.Thread(
                    target=self._bridge_loop, args=(job, w),
                    name=f"rc-exec-bridge-{job.jid}", daemon=True).start()

    def _pick_locked(self):
        if not self._queue:
            return None, None
        for w in self._workers.values():
            if w.state == "idle" and w.chan is not None:
                return self._queue.popleft(), w
        return None, None

    def _maybe_inject_dispatch_fault(self, job: _Job,
                                     w: _WorkerHandle) -> None:
        """``transport.dispatch`` chaos site: a planned fault frame is
        sent just ahead of the task frame, so the crash (worker exits
        with the attempt assigned but unfinished) or stall (worker goes
        heartbeat-silent while the attempt runs) hits exactly the
        dispatch the plan named."""
        inj = rfaults.active()
        if inj is None:
            return
        act = inj.fire("transport.dispatch", worker=w.wid, task=job.jid,
                       label=job.label)
        if act is None:
            return
        if act["action"] == "crash_worker":
            w.chan.send({"type": "die"})
        elif act["action"] == "stall_heartbeat":
            w.chan.send({"type": "stall",
                         "for_s": float(act.get("for_s", 1.0))})

    # -- results / faults ------------------------------------------------------

    def _on_result(self, w: _WorkerHandle, msg: Dict[str, Any]) -> None:
        with self._cond:
            w.last_seen = time.time()
            self._crash_streak[w.wid] = 0  # a result proves the slot healthy
            job = self._inflight.pop(msg["task_id"], None)
            if w.job is job:
                w.job = None
            if w.state == "busy":
                w.state = "idle"
            self._cond.notify_all()
        if job is None:
            return  # already failed by the monitor (late result)
        status = msg.get("status")
        if status == "ok":
            self._resolve(job, value=msg.get("value"))
        elif status == "preempted":
            self._resolve(job, exc=ServicePreempted(msg.get("state")))
        else:
            err = msg.get("error") or {}
            self._resolve(job, exc=RemoteTaskError(
                err.get("etype", "Exception"), err.get("message", ""),
                err.get("traceback", "")))

    def _worker_lost(self, w: _WorkerHandle, reason: str) -> None:
        with self._cond:
            if w.state == "dead":
                return
            if self._closed:
                w.state = "dead"
                return  # shutdown() owns reaping and future resolution
            w.state = "dead"
            job, w.job = w.job, None
            if job is not None:
                self._inflight.pop(job.jid, None)
            pid = w.proc.pid
            chan = w.chan
            if self._respawns < self._max_respawns():
                self._respawns += 1
                streak = self._crash_streak.get(w.wid, 0) + 1
                self._crash_streak[w.wid] = streak
                delay = self._policy.backoff_s(streak,
                                               key=f"respawn.{w.wid}")
                self._respawn_log.append({
                    "worker": w.wid, "attempt": self._respawns,
                    "streak": streak, "delay_s": delay})
                if delay <= 0:
                    self._workers[w.wid] = self._spawn_locked(w.wid)
                else:
                    # the monitor performs the spawn once the backoff
                    # elapses; until then the dead handle holds the slot
                    self._respawn_due[w.wid] = time.time() + delay
            self._cond.notify_all()
        if w.proc.poll() is None:
            w.proc.terminate()
        try:
            w.proc.wait(timeout=2.0)
        except subprocess.TimeoutExpired:
            w.proc.kill()
            w.proc.wait()  # noqa: TMO001 — SIGKILL'd: reap cannot hang
        if chan is not None:
            chan.close()
        if job is not None:
            self._resolve(job, exc=WorkerCrashed(w.wid, pid, job.label,
                                                 reason))

    def _max_respawns(self) -> int:
        return 16 if self.capacity is None else max(16, 4 * self.capacity)

    def _monitor_loop(self) -> None:
        while True:
            with self._cond:
                if self._closed:
                    return
                now = time.time()
                for wid, due in list(self._respawn_due.items()):
                    if due <= now:  # backoff elapsed: perform the respawn
                        del self._respawn_due[wid]
                        self._workers[wid] = self._spawn_locked(wid)
                        self._cond.notify_all()
                workers = [(w, w.job) for w in self._workers.values()]
            for w, job in workers:
                if w.state == "dead":
                    continue
                if w.proc.poll() is not None:
                    # fast path: process exit (crash/SIGKILL) — detected at
                    # poll cadence, without waiting out a heartbeat timeout
                    self._worker_lost(
                        w, f"process exited with code {w.proc.returncode}")
                elif (w.chan is not None
                      and now - w.last_seen > self._heartbeat_timeout_s):
                    self._worker_lost(
                        w, f"no heartbeat for "
                           f"{now - w.last_seen:.1f}s (hung?)")
                elif (w.state == "busy" and job is not None
                      and job.deadline is not None and now > job.deadline):
                    # per-attempt deadline (FailurePolicy.attempt_timeout_s):
                    # also the recovery path for a dropped result reply
                    self._worker_lost(
                        w, f"attempt exceeded its "
                           f"{job.attempt_timeout_s:.1f}s deadline")
                elif (w.chan is None
                      and now - w.spawned_at > self._start_timeout_s):
                    self._worker_lost(w, "never connected (start timeout)")
            time.sleep(self._poll_s)

    def _resolve(self, job: _Job, value: Any = None,
                 exc: Optional[BaseException] = None) -> None:
        try:
            if exc is not None:
                job.future.set_exception(exc)
            else:
                job.future.set_result(value)
        except Exception:  # noqa: BLE001 — future already cancelled/resolved
            pass
        if job.on_done is not None:
            try:
                job.on_done(job.future)
            except Exception:  # noqa: BLE001 — callbacks must not kill the pool
                import traceback
                traceback.print_exc()

    # -- service bridge --------------------------------------------------------

    def _bridge_loop(self, job: _Job, w: _WorkerHandle) -> None:
        """Pump the caller-held ServiceControl down to the worker replica
        for the lifetime of one service attempt."""
        control = job.service_control
        sent_stop = sent_drain = sent_preempt = False
        while not job.future.done():
            entries = control.take_requests()
            for entry in entries:
                req = getattr(entry, "request", entry)
                rid = getattr(req, "rid", None)
                if rid is not None:
                    with self._stream_lock:
                        self._streams[rid] = req
                try:
                    w.chan.send({"type": "control", "op": "submit_request",
                                 "data": protocol.dumps(entry)})
                except (protocol.ConnectionClosed, OSError):
                    return
            try:
                if control.stop_requested() and not sent_stop:
                    sent_stop = True
                    w.chan.send({"type": "control", "op": "stop"})
                if control.drain_requested() and not sent_drain:
                    sent_drain = True
                    w.chan.send({"type": "control", "op": "drain"})
                if control.preempt_requested() and not sent_preempt:
                    sent_preempt = True
                    w.chan.send({"type": "control", "op": "preempt"})
            except (protocol.ConnectionClosed, OSError):
                return
            time.sleep(0.005)

    # -- stream application ----------------------------------------------------

    def _apply_stream(self, msg: Dict[str, Any]) -> None:
        with self._stream_lock:
            req = self._streams.get(msg.get("rid"))
        if req is None:
            return
        try:
            from repro.serve.request import RequestState
        except ImportError:
            return
        if req.admitted_at is None and msg.get("admitted_at") is not None:
            req.admitted_at = msg["admitted_at"]
        if req.first_token_at is None and msg.get("first_token_at") is not None:
            req.first_token_at = msg["first_token_at"]
        if req.state == RequestState.QUEUED:
            req.state = RequestState.RUNNING
        req.tokens.extend(msg.get("tokens", ()))
        req.token_times.extend(msg.get("times", ()))

    def _apply_finish(self, msg: Dict[str, Any]) -> None:
        with self._stream_lock:
            req = self._streams.pop(msg.get("rid"), None)
        if req is None:
            return
        try:
            from repro.serve.request import RequestState
        except ImportError:
            return
        req._finish(RequestState[msg["state"]], msg.get("error"))
        if msg.get("finished_at") is not None:
            req.finished_at = msg["finished_at"]


class JaxDistributedTransport(SubprocessTransport):
    """Cross-node flavour of the subprocess pool.

    The single-host build carries the multi-host coordinates through to
    the workers' ``jax.distributed.initialize`` hook
    (``repro.core.exec.worker --jax-coordinator ...``), but there is no
    fabric behind them in this container — so requesting real multi-host
    init raises a specific error instead of hanging on a coordinator
    that will never answer.  Constructed with no coordinates it behaves
    exactly like :class:`SubprocessTransport` (process-isolated workers
    on this host).
    """

    name = "jax-distributed"

    def __init__(self, coordinator: Optional[str] = None,
                 num_processes: Optional[int] = None,
                 process_id: Optional[int] = None, **kwargs):
        multi_host = (coordinator is not None
                      or (num_processes or 1) > 1
                      or (process_id or 0) != 0)
        if multi_host:
            raise NotImplementedError(
                "cross-node multi-host init requested "
                f"(coordinator={coordinator!r}, num_processes={num_processes}"
                f", process_id={process_id}) but no multi-host fabric exists "
                "in this build. The worker daemon already accepts "
                "--jax-coordinator/--jax-num-processes/--jax-process-id "
                "(repro.core.exec.worker) and calls "
                "jax.distributed.initialize with them — point the pool at "
                "real hosts to enable it. For process-isolated workers on "
                "this host, construct without coordinates (or use "
                "SubprocessTransport).")
        super().__init__(**kwargs)
        self._jax_coordinator = coordinator
        self._jax_num_processes = num_processes
        self._jax_process_id = process_id
