"""Analyzer test coverage (PR 6 satellite): each static pass is proven
against a fixture module carrying exactly the violations it must report,
and the runtime lock-order recorder is proven against a seeded inversion
plus a live two-thread agent interleaving."""
import threading
from pathlib import Path
from types import SimpleNamespace

import pytest

from repro.analysis import excepts, jit_boundary, locks, pickles
from repro.analysis.findings import (
    Finding, diff_against_baseline, load_baseline, write_baseline,
)
from repro.analysis.lockorder import LockOrderRecorder, instrument_runtime
from repro.core.agent import RemoteAgent
from repro.core.pilot import Pilot
from repro.core.task import TaskDescription, TaskState

ROOT = Path(__file__).resolve().parents[1]
FIXTURES = ROOT / "tests" / "fixtures_analysis"


# ---------------------------------------------------------------------------
# lock-discipline pass: guarded-attr escapes
# ---------------------------------------------------------------------------


def test_lock_pass_reports_exactly_the_seeded_escapes():
    findings = locks.run([FIXTURES / "lock_fixture.py"], ROOT)
    got = sorted((f.rule, f.symbol) for f in findings)
    # exactly the two seeded violations: the unlocked read in peek() and
    # the closure that outlives its with-block in escape().  The clean
    # patterns (locked access, *_locked helper, # caller-locked method,
    # __init__) must produce nothing.
    assert got == [("guarded-attr", "Counter.history"),
                   ("guarded-attr", "Counter.value")]
    by_symbol = {f.symbol: f for f in findings}
    assert "peek" not in by_symbol  # symbols are class.attr, not methods
    assert "_lock" in by_symbol["Counter.value"].message


# ---------------------------------------------------------------------------
# jit-boundary pass: host syncs / traced branches / unhashable statics
# ---------------------------------------------------------------------------


def test_jit_pass_reports_exactly_the_seeded_violations():
    findings = jit_boundary.run(
        {"tests.fixtures_analysis.jit_fixture": FIXTURES / "jit_fixture.py"},
        ROOT)
    got = sorted((f.rule, f.line) for f in findings)
    assert got == [
        ("host-sync", 26),          # time.time() under jit
        ("host-sync", 30),          # float() on a traced value
        ("static-unhashable", 46),  # list display bound to static arg
        ("traced-branch", 28),      # if on a traced value
        ("traced-branch", 31),      # if on jax.sharding.reshard's result
    ]
    # every finding names the offending jit root; clean_step (shape
    # attrs, the mesh query, `is None`, static closure config) contributes
    # nothing
    assert all("leaky_step" in f.symbol for f in findings)


# ---------------------------------------------------------------------------
# broad-except pass
# ---------------------------------------------------------------------------


def test_excepts_pass_respects_noqa_boundary():
    findings = excepts.run([FIXTURES / "except_fixture.py"], ROOT)
    assert len(findings) == 1
    assert findings[0].rule == "broad-except"
    assert findings[0].line == 11  # risky() flagged, isolated() exempt


# ---------------------------------------------------------------------------
# picklable-task-contract pass
# ---------------------------------------------------------------------------


def test_pickles_pass_flags_nested_stage_and_lambda_task():
    findings = pickles.run([FIXTURES / "pickle_fixture.py"], ROOT)
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f)
    # nested @stage flagged; module-level and PKL001-marked ones exempt
    assert [f.symbol for f in by_rule.get("stage-nested", [])] == \
        ["inner_stage"]
    # fn=lambda flagged once; the PKL001-marked call site is exempt
    assert len(by_rule.get("lambda-task", [])) == 1
    assert by_rule["lambda-task"][0].symbol == "TaskDescription"
    assert len(findings) == 2


# ---------------------------------------------------------------------------
# baseline protocol
# ---------------------------------------------------------------------------


def test_baseline_diff_keys_exclude_line_numbers(tmp_path):
    f1 = Finding("locks", "guarded-attr", "a.py", 10, "C.x", "m")
    moved = Finding("locks", "guarded-attr", "a.py", 99, "C.x", "m")
    other = Finding("locks", "guarded-attr", "a.py", 5, "C.y", "m")
    path = tmp_path / "baseline.json"
    write_baseline(path, [f1])
    baseline = load_baseline(path)
    # the same finding on a different line is NOT new (edits above it
    # must not churn the baseline); a different symbol IS new
    new, stale = diff_against_baseline([moved], baseline)
    assert new == [] and stale == set()
    new, stale = diff_against_baseline([other], baseline)
    assert [f.symbol for f in new] == ["C.y"]
    new, stale = diff_against_baseline([], baseline)
    assert new == [] and stale == {f1.key()}


# ---------------------------------------------------------------------------
# lock-order recorder: seeded inversion, detected WITHOUT deadlocking
# ---------------------------------------------------------------------------


def test_lock_order_cycle_detected_from_sequential_threads():
    rec = LockOrderRecorder()
    a = rec.wrap(threading.Lock(), "A")
    b = rec.wrap(threading.Lock(), "B")

    def forward():
        with a:
            with b:
                pass

    def backward():
        with b:
            with a:
                pass

    # run the two orders SEQUENTIALLY: no deadlock ever happens, yet the
    # recorder still sees both edges and reports the inversion
    for body in (forward, backward):
        t = threading.Thread(target=body)
        t.start()
        t.join()
    cycles = rec.cycles()
    assert cycles == [["A", "B", "A"]]
    with pytest.raises(AssertionError, match="A -> B -> A"):
        rec.assert_no_cycles()


def test_lock_order_clean_nesting_has_no_cycle():
    rec = LockOrderRecorder()
    a = rec.wrap(threading.Lock(), "A")
    b = rec.wrap(threading.Lock(), "B")
    for _ in range(3):
        with a:
            with b:
                pass
    assert rec.cycles() == []
    rec.assert_no_cycles()  # must not raise


# ---------------------------------------------------------------------------
# live interleaving: agent submit_async / service preemption under the
# recorder — the agent <-> pilot lock orders must stay acyclic
# ---------------------------------------------------------------------------


class _FakeDevice:
    def __init__(self, i):
        self.id = i
        self.platform = "cpu"


class _FakePilot(Pilot):
    def carve(self, devices, mesh_shape=None, mesh_axes=("data",)):
        return SimpleNamespace(devices=tuple(devices), size=len(devices),
                               backend="fake", build_time_s=0.0)


def test_agent_submit_and_preempt_interleaving_is_cycle_free():
    pilot = _FakePilot("fake.2", [_FakeDevice(i) for i in range(2)])
    agent = RemoteAgent(pilot, max_workers=2, straggler_check_s=0.01)
    rec = LockOrderRecorder()
    instrument_runtime(rec, agent=agent)
    rec.instrument(pilot, "_lock", "pilot._lock")

    def service(comm, control=None, resume_state=None):
        while True:
            control.wait_for_work(0.05)
            if control.preempt_requested():
                from repro.core.task import ServicePreempted
                raise ServicePreempted(state="ckpt")
            if control.stop_requested():
                return "stopped"
            control.take_requests()

    def unit(comm):
        return "ok"

    try:
        [svc] = agent.submit_async([TaskDescription(
            name="svc", fn=service, num_devices=2, priority=0, service=True)])
        started = threading.Event()
        svc.description.control.submit_request("warm")

        # thread 1: floods the agent with higher-priority unit work (this
        # starves on devices and triggers a preemption request); thread 2:
        # drives the service control from the submitting side
        def submitter():
            started.wait(5.0)
            tasks = agent.submit_async(
                [TaskDescription(name=f"hi{i}", fn=unit, num_devices=2,
                                 priority=5) for i in range(4)])
            agent.wait(tasks, timeout=10.0)

        def driver():
            started.set()
            for i in range(20):
                try:
                    svc.description.control.submit_request(i)
                except RuntimeError:
                    break

        threads = [threading.Thread(target=submitter),
                   threading.Thread(target=driver)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=15.0)
        svc.description.control.stop()
        svc.wait(10.0)
    finally:
        agent.close(timeout=10.0)

    assert agent.preemption_requests >= 1  # the interleaving really happened
    assert rec.edges(), "recorder saw no lock activity"
    rec.assert_no_cycles()
