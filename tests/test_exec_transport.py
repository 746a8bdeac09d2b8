"""Cross-process worker transport (PR 9): RPC round-trip, the
picklable-task contract, fault detection (SIGKILL / hang), respawn,
checkpoint-aware retry through the agent, Session pipelines and
ServeEngine service stages equal to in-process, and the fleet KV-page
handoff crossing a real process boundary bitwise.

Every task fn here is module-level: pytest puts ``tests/`` on
``sys.path`` and the workers inherit it through the transport's
PYTHONPATH propagation, so the fns resolve by qualified name in the
worker interpreter.
"""
import os
import signal
import time

import numpy as np
import pytest

from repro.checkpoint import store
from repro.core import Session, stage
from repro.core.agent import RemoteAgent
from repro.core.exec import (
    JaxDistributedTransport,
    RemoteTaskError,
    SubprocessTransport,
    WorkerCrashed,
    ensure_picklable,
)
from repro.core.exec.pickling import check_roundtrip
from repro.core.pilot import PilotDescription, PilotManager
from repro.core.task import TaskDescription, TaskState
from repro.serve import Request


# ---------------------------------------------------------------------------
# module-level task fns (the picklable contract)
# ---------------------------------------------------------------------------


def echo(x):
    return x


def double(comm, x):
    return x * 2


def boom():
    raise ValueError("worker-side failure")


def die(comm=None):
    os.kill(os.getpid(), signal.SIGKILL)


def sleep_for(seconds):
    time.sleep(seconds)
    return seconds


def train_then_die(comm, ckpt_dir, resume_step=None):
    """First attempt: checkpoint step 7 then kill own worker (simulated
    node death).  Retry: report the step the agent threaded back in."""
    if resume_step is None:
        store.save(ckpt_dir, 7, {"w": np.zeros(2, np.float32)})
        os.kill(os.getpid(), signal.SIGKILL)
    return ("resumed", resume_step)


@stage(kind="data_engineering", name="make")
def make_stage(ctx):
    return np.arange(8, dtype=np.float32)


@stage(kind="train", name="square")
def square_stage(ctx):
    x = ctx.upstream["make"]
    return float((x * x).sum())


@stage(kind="inference", service=True, name="engine")
def engine_stage(ctx, max_slots=2, max_len=24):
    from repro.configs import get_config
    from repro.configs.base import RunConfig
    from repro.serve import ServeEngine
    cfg = get_config("tinyllama-1.1b", smoke=True)
    engine = ServeEngine(cfg, RunConfig(), max_slots=max_slots,
                         max_len=max_len, seed=0)
    return engine.run_service(ctx.control, resume_state=ctx.resume_state)


# ---------------------------------------------------------------------------
# RPC round-trip + wire fidelity
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def pool():
    t = SubprocessTransport(max_workers=2, worker_devices=1)
    yield t
    t.shutdown(wait=False)


def test_submit_roundtrip(pool):
    futs = [pool.submit(echo, i) for i in range(8)]
    assert [f.result(timeout=120) for f in futs] == list(range(8))


def test_numpy_crosses_bitwise(pool):
    a = np.random.default_rng(0).normal(size=(16, 8)).astype(np.float32)
    out = pool.submit(echo, a).result(timeout=120)
    np.testing.assert_array_equal(out, a)
    assert check_roundtrip(a).tobytes() == a.tobytes()


def test_remote_exception_is_typed(pool):
    with pytest.raises(RemoteTaskError) as ei:
        pool.submit(boom).result(timeout=120)
    assert ei.value.remote_type == "ValueError"
    assert "worker-side failure" in str(ei.value)
    assert "boom" in ei.value.remote_traceback


def test_unpicklable_fn_rejected_at_submit(pool):
    with pytest.raises(TypeError, match="picklable-task contract"):
        pool.submit(lambda: 1)
    with pytest.raises(TypeError, match="picklable-task contract"):
        pool.submit(pool.shutdown)  # bound method of a live instance

    captured = 3

    def nested():
        return captured

    ensure_picklable(echo)  # module-level fn: fine
    with pytest.raises(TypeError, match="nested function"):
        ensure_picklable(nested)


def test_device_kinds_refused_on_tpu_host(pool, monkeypatch):
    """Workers run JAX on their CPU: on a TPU host a train/inference task
    must be refused at submit instead of quietly running there."""
    monkeypatch.setattr("jax.default_backend", lambda: "tpu")
    for kind in SubprocessTransport.DEVICE_KINDS:
        with pytest.raises(RuntimeError, match="in-process transport"):
            pool.submit(echo, 1, kind=kind)
    assert pool.submit(echo, 2, kind="data_engineering").result(
        timeout=60) == 2


def test_unpicklable_argument_names_the_leaf(pool):
    import threading
    with pytest.raises(TypeError, match=r"args\[0\]\['ev'\]"):
        pool.submit(echo, {"ev": threading.Event()})


# ---------------------------------------------------------------------------
# fault detection
# ---------------------------------------------------------------------------


def test_sigkill_surfaces_promptly_and_worker_respawns():
    t = SubprocessTransport(max_workers=1, worker_devices=1)
    try:
        assert t.submit(echo, 1).result(timeout=120) == 1
        t0 = time.time()
        with pytest.raises(WorkerCrashed, match="died while running"):
            t.submit(die).result(timeout=30)
        assert time.time() - t0 < 10.0, "crash detection too slow"
        # the pool respawned: the next task runs on a fresh worker
        assert t.submit(echo, 2).result(timeout=120) == 2
    finally:
        t.shutdown(wait=False)


def test_hung_worker_caught_by_heartbeat_backstop():
    """SIGSTOP freezes the worker without closing its socket or exiting
    the process — only the heartbeat-age path can catch it."""
    t = SubprocessTransport(max_workers=1, worker_devices=1,
                            heartbeat_s=0.1, heartbeat_timeout_s=1.0)
    try:
        # prove the worker is up first: freezing it mid-boot would land on
        # the (long) start-timeout path instead of the heartbeat backstop
        assert t.submit(echo, 0).result(timeout=120) == 0
        fut = t.submit(sleep_for, 60)
        time.sleep(0.3)  # let the task land on the worker
        (pid,) = t.worker_pids()
        os.kill(pid, signal.SIGSTOP)
        with pytest.raises(WorkerCrashed, match="heartbeat"):
            fut.result(timeout=30)
    finally:
        t.shutdown(wait=False)


def test_shutdown_no_wait_reaps_all_workers():
    t = SubprocessTransport(max_workers=2, worker_devices=1)
    t.submit(echo, 1).result(timeout=120)
    pids = t.worker_pids()
    assert len(pids) == 2
    t.shutdown(wait=False)
    deadline = time.time() + 10
    while time.time() < deadline:
        alive = [p for p in pids if _pid_alive(p)]
        if not alive:
            return
        time.sleep(0.05)
    raise AssertionError(f"orphaned workers after shutdown: {alive}")


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    # zombie counts as reaped-in-progress: ask the kernel for state
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().split()[2] != "Z"
    except OSError:
        return False


# ---------------------------------------------------------------------------
# agent integration: checkpoint-aware retry across a worker death
# ---------------------------------------------------------------------------


def test_agent_retries_dead_worker_task_from_checkpoint(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    transport = SubprocessTransport(max_workers=1, worker_devices=1)
    pm = PilotManager()
    pilot = pm.submit_pilot(PilotDescription(num_devices=1))
    agent = RemoteAgent(pilot, transport=transport)
    try:
        task, = agent.submit([TaskDescription(
            name="train", fn=train_then_die, args=(ckpt,),
            checkpoint_dir=ckpt, max_retries=2, group="g")])
        assert task.state == TaskState.DONE, task.error
        assert task.result == ("resumed", 7)
        assert task.attempts == 2
        assert agent.quota_violations() == {}
        assert pilot.free_count() == 1, "lease leaked across worker death"
    finally:
        agent.close()
        transport.shutdown(wait=False)


# ---------------------------------------------------------------------------
# retired stub: JaxDistributedTransport is now the subprocess pool
# ---------------------------------------------------------------------------


def test_jax_distributed_single_host_executes():
    t = JaxDistributedTransport(num_processes=1, process_id=0)
    try:
        assert t.name == "jax-distributed"
        assert t.submit(echo, 41).result(timeout=120) == 41
    finally:
        t.shutdown(wait=False)


# ---------------------------------------------------------------------------
# Session: pipeline + service stage end-to-end over subprocess workers
# ---------------------------------------------------------------------------


def _run_pipeline(transport_spec):
    with Session(pods=[PilotDescription(num_devices=1)],
                 max_workers_per_pilot=1, transport=transport_spec,
                 transport_options={"worker_devices": 1}) as s:
        return s.run(make_stage >> square_stage, name="p")


def test_session_pipeline_matches_in_process():
    got_sub = _run_pipeline("subprocess")
    got_in = _run_pipeline("in-process")
    assert got_sub["square"] == got_in["square"] == 140.0
    np.testing.assert_array_equal(got_sub["make"], got_in["make"])


def _run_service(transport_spec):
    with Session(pods=[PilotDescription(num_devices=1)],
                 max_workers_per_pilot=1, transport=transport_spec,
                 transport_options={"worker_devices": 1}) as s:
        handle = s.serve(engine_stage, name="svc")
        rng = np.random.default_rng(5)
        reqs = [handle.submit_request(
            Request(rng.integers(1, 64, 8), max_new_tokens=6))
            for _ in range(3)]
        deadline = time.time() + 300
        for r in reqs:
            while not r.wait(1.0):
                task = handle.task
                if task is not None and task.finalized and task.error:
                    raise AssertionError(f"service failed: {task.error}")
                assert time.time() < deadline, f"{r.rid} stalled: {r.tokens}"
        assert handle.stop(drain=True, timeout=60)
        return [list(r.tokens) for r in reqs]


def test_service_stage_streams_match_in_process():
    toks_sub = _run_service("subprocess")
    toks_in = _run_service("in-process")
    assert toks_sub == toks_in
    assert all(len(t) == 6 for t in toks_sub)


class _RacingChannel:
    """Records frames; while the first stream frame is being sent, the
    'engine' emits the request's last tokens and finishes it."""

    def __init__(self, req):
        self.req = req
        self.frames = []

    def send(self, msg):
        self.frames.append(msg)
        if msg["type"] == "stream" and not self.req.done():
            from repro.serve.request import RequestState
            self.req.tokens.extend([5, 6])
            self.req.token_times.extend([0.0, 0.0])
            self.req._finish(RequestState.DONE)


def test_streamer_sends_every_token_before_finish():
    """The parent drops stream frames for a finished request, so the
    worker must never send "finish" ahead of tokens it has not shipped,
    even when the engine finishes the request mid-sweep."""
    from repro.core.exec.worker import _Streamer
    req = Request(np.arange(1, 9), max_new_tokens=6)
    req.tokens.extend([1, 2, 3, 4])
    req.token_times.extend([0.0] * 4)
    chan = _RacingChannel(req)
    streamer = _Streamer(chan, task_id=0)
    streamer.close()  # stop the poll thread: sweeps below are explicit
    streamer.register(req)
    streamer.sweep()
    streamer.sweep()
    shipped = []
    for f in chan.frames:
        if f["type"] == "finish":
            break
        shipped.extend(f["tokens"])
    assert shipped == [1, 2, 3, 4, 5, 6]
    assert [f["type"] for f in chan.frames] == ["stream", "stream", "finish"]


# ---------------------------------------------------------------------------
# fleet: KV-page handoff round-trips bitwise across the process boundary
# ---------------------------------------------------------------------------


def _run_fleet(transport):
    from repro.configs import get_config
    from repro.configs.base import RunConfig
    from repro.serve import build_fleet

    cfg = get_config("tinyllama-1.1b", smoke=True)
    kw = {"transport": transport} if transport is not None else {}
    router = build_fleet(cfg, RunConfig(), num_engines=2, disaggregate=True,
                         seed=0, max_slots=2, max_len=24,
                         router_kwargs=kw, name_prefix="t")
    router.start()
    try:
        rng = np.random.default_rng(3)
        reqs = [router.submit(Request(rng.integers(1, 64, 8),
                                      max_new_tokens=6))
                for _ in range(3)]
        deadline = time.time() + 300
        for r in reqs:
            while not r.wait(1.0):
                assert time.time() < deadline, f"{r.rid} stalled: {r.tokens}"
        return [list(r.tokens) for r in reqs], router.stats()
    finally:
        router.close()


def test_fleet_handoff_roundtrips_bitwise_across_processes():
    transport = SubprocessTransport(max_workers=1, worker_devices=1)
    try:
        toks_sub, stats_sub = _run_fleet(transport)
    finally:
        transport.shutdown(wait=False)
    toks_in, stats_in = _run_fleet(None)
    # every prefill->decode migration crossed a real process boundary on
    # the subprocess run, and the decoded streams are identical token for
    # token — the page bytes round-tripped bitwise
    assert stats_sub["handoffs_routed"] >= 1
    assert stats_sub["handoff_wire_roundtrips"] == stats_sub["handoffs_routed"]
    assert stats_in.get("handoff_wire_roundtrips", 0) == 0
    assert toks_sub == toks_in
