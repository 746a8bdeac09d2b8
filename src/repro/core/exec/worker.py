"""Worker daemon: one long-lived process of a ``SubprocessTransport`` pool.

Run as ``python -m repro.core.exec.worker --host H --port P
--worker-id N``.  The parent sets the environment before spawn —
notably ``XLA_FLAGS=--xla_force_host_platform_device_count=<k>`` so the
worker owns an isolated emulated device pool, and ``PYTHONPATH`` so
task fns pickled by reference resolve here.  With
``--jax-coordinator/--jax-num-processes/--jax-process-id`` the worker
instead joins a real multi-host fabric via
``jax.distributed.initialize`` before touching devices (the hook pinned
for multi-host deployments; unused under emulation).

Threads:

- **main**: blocking RPC read loop (task / control / shutdown frames);
- **heartbeat**: periodic liveness frames — if a send ever fails the
  parent is gone and the worker exits rather than orphan itself;
- **runner**: executes the current task (one at a time per worker);
- **streamer**: while a service task runs, polls its worker-side
  Request replicas and forwards token deltas / terminal transitions to
  the parent, which applies them to the client-held originals.
"""
from __future__ import annotations

import argparse
import os
import sys
import threading
import time
import traceback
from socket import create_connection
from typing import Any, Dict, Optional

from repro.core.exec import protocol

_STREAM_POLL_S = 0.005


def _error_payload(e: BaseException) -> Dict[str, str]:
    """Exceptions cross the wire as typed dicts, never pickled objects —
    custom ``__init__`` signatures (e.g. DeviceFailure) reconstruct
    wrongly under default exception pickling."""
    return {"etype": type(e).__name__,
            "message": str(e),
            "traceback": traceback.format_exc()[-2000:]}


class _Streamer:
    """Tracks live Request replicas for the running service task and
    mirrors their progress to the parent."""

    def __init__(self, chan: protocol.Channel, task_id: int):
        self._chan = chan
        self._task_id = task_id
        self._lock = threading.Lock()
        #: rid -> [request, tokens_already_sent, finish_sent]
        self._reqs: Dict[str, list] = {}  # guarded-by: _lock
        self._done = threading.Event()
        self._thread = threading.Thread(target=self._loop,
                                        name="rc-exec-streamer", daemon=True)
        self._thread.start()

    def register(self, req, sent: int = 0) -> None:
        with self._lock:
            self._reqs.setdefault(req.rid, [req, sent, False])

    def register_tree(self, obj: Any, _depth: int = 0,
                      _seen: Optional[set] = None) -> None:
        """Find Request instances anywhere inside a resume-state pytree
        (engine checkpoints embed them in slots/queue/outbox) and track
        them as already-streamed up to their current token count."""
        try:
            from repro.serve.request import Request
        except ImportError:  # serve layer absent: nothing to stream
            return
        seen = _seen if _seen is not None else set()
        if _depth > 8 or id(obj) in seen:
            return
        seen.add(id(obj))
        if isinstance(obj, Request):
            self.register(obj, sent=len(obj.tokens))
            return
        if isinstance(obj, dict):
            children = obj.values()
        elif isinstance(obj, (list, tuple, set)):
            children = obj
        elif hasattr(obj, "__dict__") and type(obj).__module__.startswith("repro."):
            children = vars(obj).values()
        else:
            return
        for c in children:
            self.register_tree(c, _depth + 1, seen)

    def _loop(self) -> None:
        while not self._done.wait(_STREAM_POLL_S):
            self.sweep()

    def sweep(self) -> None:
        """Forward any unsent tokens / terminal transitions.  Called from
        the poll loop and synchronously by the runner right before a
        preempted/final result, so parent and worker agree on the token
        count at every checkpoint boundary."""
        with self._lock:
            entries = list(self._reqs.values())
        for entry in entries:
            req, sent, finished_sent = entry
            # read the terminal state BEFORE the token count: the engine
            # appends a request's last tokens and then finishes it, so a
            # finish seen first means n covers every token.  The other
            # order could ship "finish" ahead of the final tokens, and the
            # parent drops stream frames for a request it has finished.
            done = req.done()
            n = len(req.tokens)
            try:
                if n > sent:
                    self._chan.send({
                        "type": "stream", "task_id": self._task_id,
                        "rid": req.rid,
                        "tokens": [int(t) for t in req.tokens[sent:n]],
                        "times": [float(t) for t in req.token_times[sent:n]],
                        "admitted_at": req.admitted_at,
                        "first_token_at": req.first_token_at,
                    })
                    entry[1] = n
                if done and not finished_sent:
                    self._chan.send({
                        "type": "finish", "task_id": self._task_id,
                        "rid": req.rid, "state": req.state.name,
                        "error": req.error,
                        "finished_at": req.finished_at,
                    })
                    entry[2] = True
            except protocol.ConnectionClosed:
                self._done.set()
                return

    def close(self) -> None:
        self._done.set()
        self._thread.join(timeout=1.0)
        self.sweep()


class _TaskRun:
    """State for the (single) in-flight task on this worker."""

    def __init__(self, chan: protocol.Channel, msg: Dict[str, Any]):
        self.chan = chan
        self.task_id = msg["task_id"]
        self.payload = protocol.loads(msg["payload"])
        #: set just before the result frame goes out.  The busy check
        #: reads this, NOT thread.is_alive(): the parent marks the worker
        #: idle the instant the result frame lands, so a fast next
        #: dispatch can beat the runner thread's teardown.
        self.done = False
        self.control = None
        self.streamer: Optional[_Streamer] = None
        if self.payload.get("service"):
            from repro.core.task import ServiceControl
            self.control = ServiceControl()
            self.streamer = _Streamer(chan, self.task_id)
        self.thread = threading.Thread(target=self._run,
                                       name="rc-exec-runner", daemon=True)

    def handle_control(self, msg: Dict[str, Any]) -> None:
        op = msg["op"]
        if self.control is None:
            return  # stale control frame for a non-service task
        if op == "submit_request":
            entry = protocol.loads(msg["data"])
            req = getattr(entry, "request", entry)  # KVHandoff carries one
            if self.streamer is not None and hasattr(req, "rid"):
                self.streamer.register(req)
            try:
                self.control.submit_request(entry)
            except RuntimeError as e:
                # raced a stop/drain the parent had not seen yet: fail the
                # replica so the streamer reports a terminal state instead
                # of the client-held original hanging forever
                if hasattr(req, "_finish"):
                    from repro.serve.request import RequestState
                    req._finish(RequestState.FAILED, str(e))
        elif op == "stop":
            self.control.stop()
        elif op == "drain":
            self.control.drain()
        elif op == "preempt":
            self.control.request_preempt()

    def _run(self) -> None:
        from repro.core.task import ServicePreempted
        fn = self.payload["fn"]
        args = self.payload["args"]
        kwargs = dict(self.payload["kwargs"])
        if self.control is not None:
            kwargs["control"] = self.control
            if self.streamer is not None:
                self.streamer.register_tree(kwargs.get("resume_state"))
        t0 = time.time()
        try:
            value = fn(*args, **kwargs)
            result = {"type": "result", "task_id": self.task_id,
                      "status": "ok", "value": value,
                      "elapsed": time.time() - t0}
        except ServicePreempted as e:
            result = {"type": "result", "task_id": self.task_id,
                      "status": "preempted", "state": e.state,
                      "elapsed": time.time() - t0}
        except BaseException as e:  # noqa: BLE001 — worker isolation boundary
            result = {"type": "result", "task_id": self.task_id,
                      "status": "error", "error": _error_payload(e),
                      "elapsed": time.time() - t0}
        if self.streamer is not None:
            # final sweep BEFORE the result frame: the parent must hold
            # every token the checkpointed state accounts for by the time
            # the preemption/completion lands
            self.streamer.close()
        self.done = True
        try:
            self.chan.send(result)
        except protocol.ConnectionClosed:
            pass  # parent gone; heartbeat thread will exit the process
        except Exception as e:  # noqa: BLE001 — any pickle failure lands here
            # unpicklable task *result* — report instead of dying silently
            try:
                self.chan.send({"type": "result", "task_id": self.task_id,
                                "status": "error",
                                "error": {"etype": "TypeError",
                                          "message": f"task result failed to "
                                                     f"pickle: {e}",
                                          "traceback": ""},
                                "elapsed": time.time() - t0})
            except protocol.ConnectionClosed:
                pass


#: chaos: heartbeats are suppressed until this wall-clock time — set by
#: a ``stall`` frame so the parent's heartbeat-timeout backstop can be
#: exercised deterministically against a live, task-running worker.
_STALL_UNTIL = [0.0]


def _heartbeat_loop(chan: protocol.Channel, period_s: float) -> None:
    while True:
        time.sleep(period_s)
        if time.time() < _STALL_UNTIL[0]:
            continue  # stalled: alive but silent
        try:
            chan.send({"type": "heartbeat", "t": time.time()})
        except (protocol.ConnectionClosed, OSError):
            # the parent is gone: never linger as an orphan
            os._exit(0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--worker-id", type=int, required=True)
    ap.add_argument("--heartbeat-s", type=float, default=0.2)
    # multi-host hook: point at a real fabric and the worker joins it
    ap.add_argument("--jax-coordinator", default=None)
    ap.add_argument("--jax-num-processes", type=int, default=None)
    ap.add_argument("--jax-process-id", type=int, default=None)
    args = ap.parse_args(argv)

    chan = protocol.Channel(create_connection((args.host, args.port),
                                              timeout=10))
    chan.send({"type": "hello", "worker_id": args.worker_id,
               "pid": os.getpid()})
    threading.Thread(target=_heartbeat_loop, args=(chan, args.heartbeat_s),
                     name="rc-exec-heartbeat", daemon=True).start()

    if args.jax_coordinator is not None:
        import jax
        jax.distributed.initialize(
            coordinator_address=args.jax_coordinator,
            num_processes=args.jax_num_processes,
            process_id=args.jax_process_id)
    if os.environ.get("REPRO_FAULT_PLAN"):
        # worker-side chaos: the parent (a bench/test) shipped a fault
        # plan through the transport's env hook — sites that fire inside
        # the worker (e.g. checkpoint.save tears) arm here
        from repro.core.resilience import faults as _faults
        _faults.install_from_env()

    # warm the runtime off the task path and tell the parent the pool size
    import jax
    chan.send({"type": "ready", "worker_id": args.worker_id,
               "devices": len(jax.devices())})

    current: Optional[_TaskRun] = None
    while True:
        try:
            msg = chan.recv()  # noqa: TMO001 — main RPC loop; heartbeat thread exits on a dead parent
        except protocol.ConnectionClosed:
            return 0  # parent closed the channel: clean exit
        mtype = msg.get("type")
        if mtype == "task":
            if current is not None and not current.done:
                chan.send({"type": "result", "task_id": msg["task_id"],
                           "status": "error",
                           "error": {"etype": "RuntimeError",
                                     "message": "worker is busy (protocol "
                                                "violation: one task per "
                                                "worker)",
                                     "traceback": ""}})
                continue
            current = _TaskRun(chan, msg)
            current.thread.start()
        elif mtype == "control":
            if current is not None:
                current.handle_control(msg)
        elif mtype == "die":
            # injected crash (FaultPlan.crash_worker): exit hard,
            # mid-task — the parent sees a real process death
            os._exit(3)
        elif mtype == "stall":
            # injected heartbeat stall: stay alive, go silent for a while
            _STALL_UNTIL[0] = time.time() + float(msg.get("for_s", 1.0))
        elif mtype == "shutdown":
            if current is not None and current.control is not None:
                current.control.stop()
            if current is not None:
                current.thread.join(timeout=5.0)
            return 0


if __name__ == "__main__":
    sys.exit(main())
