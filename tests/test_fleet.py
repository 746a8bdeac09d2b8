"""Fleet serving tests: EngineRouter load-aware routing, non-terminal
drain, rolling engine restart mid-stream (checkpoint/resume; greedy
streams bitwise-equal to an undisturbed run), pilot-mode preemption
re-route under one PilotManager with zero quota violations, and
disaggregated prefill/decode KV handoff (page blocks shipped through the
transport and re-addressed by block-table rewrite — bitwise-equal to
colocated serving, bytes bounded by the migrating request's own pages).

Like tests/test_serving.py, token-stream equivalence runs in f32 compute
(in bf16 two near-tied logits can argmax-flip between numerically
different but equally valid paths); params are shared — the compute
dtype is applied at runtime.  Pilot-mode tests run on FakePilots over
plain-object devices, so an 8-device fleet is modelled on the
container's single real device.
"""
import dataclasses
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.params import init_params
from repro.configs import get_config
from repro.core.pilot import Pilot, PilotDescription, PilotManager
from repro.core.task import TaskDescription, TaskState
from repro.serve import (EngineRouter, Request, RequestState, ServeEngine,
                         build_fleet)
from repro.train.state import model_specs

CFG = get_config("tinyllama-1.1b", smoke=True)
CFG32 = dataclasses.replace(CFG, compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), model_specs(CFG))


def _prompts(rng, lens):
    return [rng.integers(1, CFG.vocab_size, int(n)).astype(np.int32)
            for n in lens]


def _ref_streams(params, prompts, gen, *, max_len=96):
    """The undisturbed single-engine run every fleet test must match."""
    eng = ServeEngine(CFG32, params=params, max_slots=2, max_len=max_len,
                      page_size=16)
    reqs = [eng.submit(Request(p, max_new_tokens=gen)) for p in prompts]
    eng.run_until_drained()
    return [r.tokens for r in reqs]


# ---------------------------------------------------------------------------
# routing: load-aware spread, bitwise streams, non-terminal drain
# ---------------------------------------------------------------------------


def test_router_spreads_load_and_matches_reference(params):
    rng = np.random.default_rng(0)
    prompts = _prompts(rng, rng.integers(4, 30, 10))
    ref = _ref_streams(params, prompts, 16)

    router = build_fleet(CFG32, num_engines=2, params=params, max_slots=2,
                         max_len=96, page_size=16, name_prefix="t")
    with router:
        reqs = [router.submit(Request(p, max_new_tokens=16))
                for p in prompts]
        assert router.drain(timeout=180)
        # drain is a flush, not a shutdown: the router keeps accepting
        extra = [router.submit(Request(p, max_new_tokens=4))
                 for p in prompts[:2]]
        assert router.drain(timeout=60)
        stats = router.stats()
    assert [r.tokens for r in reqs] == ref, "fleet changed token streams"
    assert all(r.state is RequestState.DONE for r in extra)
    spread = {k: v for k, v in stats.items() if k.startswith("routed_to.")}
    assert len(spread) == 2, f"both engines must serve: {spread}"
    assert stats["fleet_completed"] == len(reqs) + len(extra)


def test_router_admission_signals_one_lock_snapshot(params):
    eng = ServeEngine(CFG32, params=params, max_slots=2, max_len=64,
                      page_size=16, name="sig")
    sig = eng.admission_signals()
    assert sig["engine"] == "sig" and not sig["prefill_only"]
    assert sig["occupied"] == 0 and sig["queue_depth"] == 0
    assert sig["free_pages"] == sig["num_pages"] == eng.num_pages
    eng.submit(Request(np.arange(1, 6, dtype=np.int32), max_new_tokens=4))
    sig = eng.admission_signals()
    assert sig["queue_depth"] == 1
    assert sig["oldest_queued_age_s"] >= 0.0


# ---------------------------------------------------------------------------
# drain + rolling restart: checkpoint/resume mid-stream, bitwise streams
# ---------------------------------------------------------------------------


def test_rolling_restart_mid_stream_bitwise(params):
    rng = np.random.default_rng(1)
    prompts = _prompts(rng, rng.integers(4, 30, 12))
    ref = _ref_streams(params, prompts, 24)

    router = build_fleet(CFG32, num_engines=2, params=params, max_slots=2,
                         max_len=96, page_size=16, name_prefix="rr")
    with router:
        reqs = [router.submit(Request(p, max_new_tokens=24))
                for p in prompts]
        # wait until engine 0 actually holds bound in-flight work, then
        # bounce it: queued entries re-route, bound slots checkpoint and
        # resume exactly where they stopped
        t0 = time.time()
        while (router.members[0].engine.occupancy() == 0
               and time.time() - t0 < 60):
            time.sleep(0.002)
        assert router.members[0].engine.occupancy() > 0
        router.rolling_restart(0)
        assert router.drain(timeout=180)
        stats = router.stats()
    assert [r.tokens for r in reqs] == ref, "restart changed token streams"
    assert stats["restarts"] == 1
    assert sum(e.get("resumes", 0) for e in stats["engines"]) >= 1


# ---------------------------------------------------------------------------
# pilot mode: placement, priority preemption, re-route, quotas
# ---------------------------------------------------------------------------


class FakeDevice:
    def __init__(self, i):
        self.id = i
        self.platform = "fake"


class FakePilot(Pilot):
    """Pilot over dummy devices; carve returns a mesh-free communicator
    whose devices are the real one, where the leased engine is placed."""

    def carve(self, devices, mesh_shape=None, mesh_axes=("data",)):
        real = jax.devices()[0]
        return SimpleNamespace(devices=(real,) * len(devices),
                               size=len(devices),
                               backend="fake", build_time_s=0.0,
                               pilot_uid=self.uid)


def test_pilot_mode_preemption_reroutes_without_quota_violations(params):
    rng = np.random.default_rng(1)
    prompts = _prompts(rng, rng.integers(4, 30, 12))
    ref = _ref_streams(params, prompts, 24)

    mgr = PilotManager(devices=[FakeDevice(i) for i in range(8)],
                       pilot_factory=FakePilot)
    mgr.submit_pilot(PilotDescription(num_devices=4, name="pod0"))
    mgr.submit_pilot(PilotDescription(num_devices=4, name="pod1"))
    engines = [ServeEngine(CFG32, params=params, max_slots=2, max_len=96,
                           page_size=16, name=f"pm{i}") for i in range(2)]
    router = EngineRouter(engines, manager=mgr, group="fleet", priority=0)
    with router:
        assert len({m.pilot.uid for m in router.members}) == 2, \
            "engines must land on distinct pilots"
        reqs = [router.submit(Request(p, max_new_tokens=24))
                for p in prompts]
        assert router.drain(timeout=180)

        # a higher-priority task wanting the whole pod forces the service
        # lease to yield: the agent preempts engine 0, the router steals
        # its inbox and re-routes, and the quota ledger stays clean
        m0 = router.members[0]
        m0.agent.set_quota("fleet", 4)

        def hog(comm):
            time.sleep(0.3)
            return "done"

        tasks = m0.agent.submit_async([TaskDescription(
            name="hog", fn=hog, num_devices=4, priority=10)])
        extra = [router.submit(Request(p, max_new_tokens=8))
                 for p in prompts[:6]]
        m0.agent.wait(tasks, timeout=120)
        assert tasks[0].state is TaskState.DONE, tasks[0].error
        assert router.drain(timeout=180)
        violations = m0.agent.quota_violations()
        assert m0.agent.preemption_requests >= 1
    assert [r.tokens for r in reqs] == ref, "pilot-mode changed streams"
    assert all(r.state is RequestState.DONE for r in extra)
    assert not violations, f"quota violations during preemption: {violations}"


# ---------------------------------------------------------------------------
# disaggregation: prefill -> decode KV handoff
# ---------------------------------------------------------------------------


def test_disaggregated_handoff_bitwise_and_byte_bounded(params):
    # 17 and 23 straddle a page boundary at page_size=16: the handoff
    # must preserve intra-page offsets across the block-table rewrite
    prompts = [np.arange(1, 6, dtype=np.int32),
               np.arange(1, 18, dtype=np.int32),
               np.arange(1, 24, dtype=np.int32)]
    ref = _ref_streams(params, prompts, 12, max_len=64)

    router = build_fleet(CFG32, num_engines=2, disaggregate=True,
                         params=params, max_slots=4, max_len=64,
                         page_size=16, name_prefix="dg")
    with router:
        reqs = [router.submit(Request(p, max_new_tokens=12))
                for p in prompts]
        assert router.drain(timeout=180)
        stats = router.stats()
        eng = router.members[0].engine
        pool_bytes = eng._page_bytes * eng.num_pages
    assert [r.tokens for r in reqs] == ref, "handoff changed token streams"
    assert stats["handoffs_routed"] == len(prompts), \
        "every prompt must migrate exactly once"
    owned_pages = sum(-(-len(p) // 16) for p in prompts)
    assert stats["handoff_pages"] == owned_pages
    assert stats["handoff_bytes"] == owned_pages * eng._page_bytes, \
        "handoff must ship exactly the owned pages, never the pool"
    assert stats["handoff_bytes"] < pool_bytes
    assert stats["fleet_handoffs_exported"] == len(prompts)
    assert stats["fleet_handoffs_imported"] == len(prompts)


def test_handoff_export_import_block_table_rewrite(params):
    prompt = np.arange(1, 18, dtype=np.int32)  # 2 pages, straddles one
    ref = _ref_streams(params, [prompt], 8, max_len=64)

    pre = ServeEngine(CFG32, params=params, max_slots=2, max_len=64,
                      page_size=16, prefill_only=True, name="pre")
    req = pre.submit(Request(prompt, max_new_tokens=8))
    pre.run_until_drained()  # prefill engine drains by exporting the slot
    [hand] = pre.take_handoffs()
    assert req.state is RequestState.RUNNING, \
        "migrating request must stay RUNNING across the handoff"
    assert hand.n_pages == 2 and hand.page_size == 16
    assert hand.kv_bytes == 2 * pre._page_bytes
    assert len(req.tokens) == 1, "prefill engine samples the first token"
    # the exporter's pages are back in the pool, its table row sentineled
    assert len(pre.free_pages) == pre.num_pages
    assert (pre.block_table == pre.num_pages).all()

    dec = ServeEngine(CFG32, params=params, max_slots=2, max_len=64,
                      page_size=16, name="dec")
    assert dec.submit(hand) is req
    dec.step()  # admit (import) + one decode step
    row = dec.block_table[0]
    assert (row[:2] < dec.num_pages).all(), "imported pages must be bound"
    assert (row[2:] == dec.num_pages).all(), \
        "beyond the owned pages the table row stays sentinel-padded"
    dec.run_until_drained()
    assert req.state is RequestState.DONE
    assert [req.tokens] == ref, "migrated stream must match colocated"
    assert dec.stats()["handoffs_imported"] == 1
