"""Serving subsystem tests: ServeEngine slot mechanics, batched-prefill
correctness against the old token-replay path (kept here as the reference
check), and service stages on the runtime (barrier exclusion, priority
preemption with checkpoint/resume, coexistence with a training pipeline
under one PilotManager).

Model-level tests run the tinyllama smoke config on the container's
single CPU device; runtime tests use tiny sleep-stage pipelines.
"""
import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.params import init_params, is_param
from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.core.agent import RemoteAgent
from repro.core.pilot import PilotDescription, PilotManager
from repro.core.pipeline import Pipeline, PipelineScheduler, Stage
from repro.core.task import ServiceControl, TaskDescription, TaskState
from repro.models.lm import lm_cache_specs
from repro.serve import Request, RequestState, ServeEngine
from repro.train.state import model_specs
from repro.train.step import make_decode_step, make_prefill_step

CFG = get_config("tinyllama-1.1b", smoke=True)
# token-stream equivalence runs in f32 compute: in bf16 two near-tied
# logits can argmax-flip between the (numerically different but equally
# valid) batched-prefill and token-replay paths.  Params are shared — the
# compute dtype is applied at runtime.
CFG32 = dataclasses.replace(CFG, compute_dtype=jnp.float32)
RUN = RunConfig()


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), model_specs(CFG))


def _prompts(rng, lens):
    return [rng.integers(1, CFG.vocab_size, int(l)).astype(np.int32)
            for l in lens]


def _replay_generate(params, prompt, n_new, max_len, cfg=CFG):
    """The seed driver's token-by-token path: replay the prompt through
    the jitted decode step, then greedy-decode — the reference the
    batched prefill must match."""
    decode = jax.jit(make_decode_step(cfg, RUN))
    cache = jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype),
                         lm_cache_specs(cfg, 1, max_len), is_leaf=is_param)
    tok = None
    logits = None
    for t in range(len(prompt)):
        tok, logits, cache = decode(params, jnp.asarray(prompt[None, t:t + 1]),
                                    cache, jnp.asarray(t, jnp.int32))
    out = [int(tok[0])]
    pos = len(prompt)
    while len(out) < n_new:
        tok, logits, cache = decode(params, tok[:, None], cache,
                                    jnp.asarray(pos, jnp.int32))
        out.append(int(tok[0]))
        pos += 1
    return out, np.asarray(logits[0, -1], np.float32)


# ---------------------------------------------------------------------------
# engine mechanics: admission, eviction, slot reuse
# ---------------------------------------------------------------------------


def test_engine_admission_eviction_slot_reuse(params):
    eng = ServeEngine(CFG, RUN, max_slots=2, max_len=32, params=params)
    rng = np.random.default_rng(0)
    reqs = [eng.submit(p, max_new_tokens=g)
            for p, g in zip(_prompts(rng, [5, 9, 3, 7, 4]), [4, 2, 7, 1, 3])]
    eng.run_until_drained()
    for r in reqs:
        assert r.state is RequestState.DONE
        assert len(r.tokens) == r.max_new_tokens
        assert r.latency_s is not None and r.ttft_s is not None
    stats = eng.stats()
    # 5 requests through 2 slots: slots were freed and reused
    assert stats["admitted"] == 5 and stats["completed"] == 5
    assert stats["prefill_batches"] >= 3
    # eviction left the engine empty
    assert eng.occupancy() == 0 and not eng.has_work()
    assert (eng.lengths == 0).all()


def test_chunked_prefill_matches_unchunked_streams(params):
    """Prompt processing in bounded chunks must not change any token
    stream: same greedy tokens whether a prompt prefills whole
    (prefill_chunk_tokens=None) or 8 tokens per step."""
    rng = np.random.default_rng(3)
    prompts = _prompts(rng, [21, 3, 14, 9])
    streams = {}
    for chunk in (None, 8):
        eng = ServeEngine(CFG32, RUN, max_slots=2, max_len=64,
                          params=params, prefill_chunk_tokens=chunk)
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run_until_drained()
        assert all(r.state is RequestState.DONE for r in reqs)
        streams[chunk] = [r.tokens for r in reqs]
        if chunk is not None:
            # a 21-token prompt at 8 tokens/step needs >= 3 chunks
            assert eng.stats()["prefill_chunks"] > len(prompts)
    assert streams[None] == streams[8]


def test_prefill_fn_cache_bounded_and_reported(params):
    eng = ServeEngine(CFG, RUN, max_slots=2, max_len=32, params=params,
                      prefill_chunk_tokens=8)
    rng = np.random.default_rng(1)
    for p in _prompts(rng, [3, 9, 15, 2, 11]):
        eng.submit(p, max_new_tokens=2)
    eng.run_until_drained()
    stats = eng.stats()
    assert 1 <= stats["prefill_fns_cached"] <= ServeEngine._PREFILL_FN_CAP
    assert stats["prefill_chunk_tokens"] == 8
    # force cache churn well past the cap: eviction, not growth
    for t in range(ServeEngine._PREFILL_FN_CAP + 3):
        eng._get_prefill(1000 + t)
    assert len(eng._prefill_fns) == ServeEngine._PREFILL_FN_CAP
    assert eng.stats()["prefill_fns_evicted"] >= 3


def test_token_times_track_tokens(params):
    eng = ServeEngine(CFG, RUN, max_slots=2, max_len=64, params=params,
                      prefill_chunk_tokens=4)
    rng = np.random.default_rng(2)
    reqs = [eng.submit(p, max_new_tokens=5) for p in _prompts(rng, [10, 4])]
    eng.run_until_drained()
    for r in reqs:
        assert len(r.token_times) == len(r.tokens)
        assert r.token_times == sorted(r.token_times)
        assert len(r.inter_token_s) == len(r.tokens) - 1
        assert r.token_times[0] == r.first_token_at


def test_engine_rejects_oversized_prompt(params):
    eng = ServeEngine(CFG, RUN, max_slots=1, max_len=16, params=params)
    bad = eng.submit(np.ones(16, np.int32), max_new_tokens=2)
    ok = eng.submit(np.ones(4, np.int32), max_new_tokens=2)
    eng.run_until_drained()
    assert bad.state is RequestState.FAILED and "fit" in bad.error
    assert ok.state is RequestState.DONE and len(ok.tokens) == 2


def test_engine_respects_stop_token(params):
    eng = ServeEngine(CFG, RUN, max_slots=1, max_len=64, params=params)
    free = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=40)
    eng.run_until_drained()
    assert len(free.tokens) == 40
    # stop on a token from the free-running stream: identical greedy
    # stream, cut at that token's FIRST occurrence
    stop_tok = free.tokens[2]
    first = free.tokens.index(stop_tok)
    stop = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=40,
                      stop_token=stop_tok)
    eng.run_until_drained()
    assert stop.tokens == free.tokens[:first + 1]


# ---------------------------------------------------------------------------
# batched prefill vs token replay (the old serve path as reference)
# ---------------------------------------------------------------------------


def test_batched_prefill_matches_token_replay(params):
    max_len = 32
    rng = np.random.default_rng(1)
    prompts = _prompts(rng, [5, 9, 7])
    P = max(len(p) for p in prompts)
    tokens = np.zeros((len(prompts), P), np.int32)
    lens = np.zeros(len(prompts), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
        lens[i] = len(p)
    prefill = jax.jit(make_prefill_step(CFG, RUN, with_cache=True,
                                        max_len=max_len))
    next_tok, last_logits, cache = prefill(params, jnp.asarray(tokens),
                                           jnp.asarray(lens))
    for i, p in enumerate(prompts):
        replay_toks, replay_logits = _replay_generate(params, p, 1, max_len)
        got = np.asarray(last_logits[i], np.float32)
        # same last-position logits (bf16 compute: loose allclose + argmax)
        scale = np.max(np.abs(replay_logits)) + 1e-9
        assert np.max(np.abs(got - replay_logits)) / scale < 0.05
        assert int(next_tok[i]) == replay_toks[0]


def test_prefill_cache_matches_replay_cache(params):
    """The K/V written by the one-shot prefill equals what token replay
    deposits, for every row's valid prefix."""
    max_len = 32
    rng = np.random.default_rng(2)
    prompt = _prompts(rng, [9])[0]
    prefill = jax.jit(make_prefill_step(CFG, RUN, with_cache=True,
                                        max_len=max_len))
    _, _, cache = prefill(params, jnp.asarray(prompt[None]),
                          jnp.asarray([len(prompt)], np.int32))
    decode = jax.jit(make_decode_step(CFG, RUN))
    ref = jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype),
                       lm_cache_specs(CFG, 1, max_len), is_leaf=is_param)
    for t in range(len(prompt)):
        _, _, ref = decode(params, jnp.asarray(prompt[None, t:t + 1]), ref,
                           jnp.asarray(t, jnp.int32))
    L = len(prompt)
    for kind in ("k", "v"):
        got = np.asarray(cache["unit"]["b0"][kind][:, 0, :L], np.float32)
        want = np.asarray(ref["unit"]["b0"][kind][:, 0, :L], np.float32)
        assert np.max(np.abs(got - want)) < 0.05, kind


def test_engine_generation_matches_token_replay(params):
    """Mixed-length continuous batching produces the same greedy streams
    as isolated token replay — per-slot lengths never cross-talk."""
    max_len = 48
    rng = np.random.default_rng(3)
    prompts = _prompts(rng, [4, 11, 7])
    gens = [6, 3, 9]
    eng = ServeEngine(CFG32, RUN, max_slots=2, max_len=max_len, params=params)
    reqs = [eng.submit(p, max_new_tokens=g) for p, g in zip(prompts, gens)]
    eng.run_until_drained()
    for r, p, g in zip(reqs, prompts, gens):
        want, _ = _replay_generate(params, p, g, max_len, cfg=CFG32)
        assert r.tokens == want, (r.rid, r.tokens, want)


def test_continuous_beats_static_admission(params):
    """With mixed generation lengths, continuous batching refills freed
    slots mid-flight and needs fewer fused decode steps than the
    static-batch baseline for the same work."""
    rng = np.random.default_rng(4)
    prompts = _prompts(rng, [4, 4, 4, 4])
    gens = [10, 2, 10, 2]

    def drive(continuous):
        eng = ServeEngine(CFG, RUN, max_slots=2, max_len=32, params=params,
                          continuous=continuous)
        reqs = [eng.submit(p, max_new_tokens=g)
                for p, g in zip(prompts, gens)]
        eng.run_until_drained()
        assert all(r.state is RequestState.DONE for r in reqs)
        return eng.stats()["decode_steps"]

    assert drive(True) < drive(False)


# ---------------------------------------------------------------------------
# paged KV cache: layout equivalence, page reuse, backpressure, checkpoint
# ---------------------------------------------------------------------------


def test_paged_matches_contiguous_streams(params):
    """The paged engine (page pool + block tables) must emit exactly the
    contiguous engine's greedy streams — the layout is invisible to the
    math."""
    rng = np.random.default_rng(10)
    prompts = _prompts(rng, [4, 11, 7])
    gens = [6, 3, 9]
    outs = {}
    for layout in ("paged", "contiguous"):
        eng = ServeEngine(CFG32, RUN, max_slots=2, max_len=48, params=params,
                          kv_layout=layout, page_size=8)
        reqs = [eng.submit(p, max_new_tokens=g)
                for p, g in zip(prompts, gens)]
        eng.run_until_drained()
        assert all(r.state is RequestState.DONE for r in reqs)
        outs[layout] = [r.tokens for r in reqs]
    assert outs["paged"] == outs["contiguous"]


def test_paged_page_reuse_after_eviction(params):
    """A pool far smaller than max_slots x max_len serves a stream of
    requests because _finish_slot recycles pages: with 2 pages total only
    one request fits at a time, yet all five complete (FIFO backpressure
    holds the queue, never fails it)."""
    eng = ServeEngine(CFG, RUN, max_slots=2, max_len=32, params=params,
                      kv_layout="paged", page_size=8, num_pages=2)
    rng = np.random.default_rng(11)
    # prompt + generation stay within the 2 reserved pages (<= 16 slots)
    reqs = [eng.submit(p, max_new_tokens=6)
            for p in _prompts(rng, [5, 7, 4, 6, 5])]
    eng.run_until_drained()
    for r in reqs:
        assert r.state is RequestState.DONE and len(r.tokens) == 6
    stats = eng.stats()
    assert stats["peak_pages"] <= 2
    assert eng.pages_in_use() == 0
    assert sorted(eng.free_pages) == [0, 1]
    assert (eng.block_table == eng.num_pages).all()


def test_paged_pool_exhaustion_fails_slot_then_recovers(params):
    """Overcommit gone wrong: a sequence that outgrows the pool fails
    with a page-pool error (never hangs), its pages return to the free
    list, and later requests succeed."""
    eng = ServeEngine(CFG, RUN, max_slots=1, max_len=64, params=params,
                      kv_layout="paged", page_size=8, num_pages=2)
    hog = eng.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=40)
    eng.run_until_drained()
    assert hog.state is RequestState.FAILED
    assert "page pool exhausted" in hog.error
    ok = eng.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=8)
    eng.run_until_drained()
    assert ok.state is RequestState.DONE and len(ok.tokens) == 8
    assert eng.pages_in_use() == 0


def test_paged_unservable_prompt_fails_fast(params):
    """A prompt whose page requirement exceeds the whole pool can never
    be admitted — it must fail immediately instead of livelocking the
    FIFO queue (and everything behind it) forever."""
    eng = ServeEngine(CFG, RUN, max_slots=1, max_len=64, params=params,
                      kv_layout="paged", page_size=8, num_pages=2)
    hog = eng.submit(np.arange(1, 22, dtype=np.int32), max_new_tokens=2)
    ok = eng.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=4)
    eng.run_until_drained()
    assert hog.state is RequestState.FAILED and "pool" in hog.error
    assert ok.state is RequestState.DONE and len(ok.tokens) == 4


def test_paged_checkpoint_restore_roundtrip(params):
    """checkpoint/restore round-trips the page pool, block tables, and
    free list mid-generation: the resumed engine finishes with exactly
    the uninterrupted streams."""
    rng = np.random.default_rng(12)
    prompts = _prompts(rng, [5, 9])
    want = [_replay_generate(params, p, 10, 64, cfg=CFG32)[0]
            for p in prompts]
    eng = ServeEngine(CFG32, RUN, max_slots=2, max_len=64, params=params,
                      kv_layout="paged", page_size=8)
    reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    for _ in range(4):
        eng.step()
    state = eng.checkpoint()
    eng._release_state()
    assert eng.pages_in_use() == 0  # released engine holds nothing
    eng.restore(state)
    assert np.array_equal(eng.block_table, state["block_table"])
    assert eng.free_pages == state["free_pages"]
    eng.run_until_drained()
    for r, w in zip(reqs, want):
        assert r.state is RequestState.DONE
        assert r.tokens == w, (r.tokens, w)


def test_bucket_floor_and_retrace_stats(params):
    """The prefill prompt bucket floor is 2 (an 8-floor padded every
    small admission to shape 8), and the engine counts each fresh jit
    shape in stats() so the bucketing/retrace tradeoff is observable."""
    from repro.serve.engine import _bucket
    assert _bucket(1) == 2 and _bucket(3) == 4 and _bucket(8) == 8
    eng = ServeEngine(CFG, RUN, max_slots=2, max_len=64, params=params)
    eng.submit(np.arange(1, 4, dtype=np.int32), max_new_tokens=2)
    eng.run_until_drained()
    first = eng.stats()["retraces"]
    assert first >= 2  # one prefill shape + one decode bucket
    eng.submit(np.arange(1, 4, dtype=np.int32), max_new_tokens=2)
    eng.run_until_drained()
    assert eng.stats()["retraces"] == first  # warm shapes: no retrace
    eng.submit(np.arange(1, 25, dtype=np.int32), max_new_tokens=2)
    eng.run_until_drained()
    assert eng.stats()["retraces_prefill"] > 1  # new P bucket counted


# ---------------------------------------------------------------------------
# sampling: temperature / top-k / seeded per-slot streams
# ---------------------------------------------------------------------------


def test_sampling_seeded_reproducible(params):
    """Same seed -> same sampled stream; different seed -> different (at
    temperature 2 over a 256-vocab the 12-token collision odds are nil).
    Streams depend only on the request's own seed, not batch placement."""
    eng = ServeEngine(CFG32, RUN, max_slots=2, max_len=64, params=params)
    prompt = np.arange(1, 7, dtype=np.int32)
    a = eng.submit(prompt, max_new_tokens=12, temperature=2.0, seed=123)
    b = eng.submit(prompt, max_new_tokens=12, temperature=2.0, seed=123)
    c = eng.submit(prompt, max_new_tokens=12, temperature=2.0, seed=124)
    eng.run_until_drained()
    assert a.tokens == b.tokens
    assert a.tokens != c.tokens


def test_sampling_does_not_perturb_greedy_neighbors(params):
    """A sampling request sharing the fused batch must not change a
    greedy neighbour's stream — greedy stays bit-identical to isolated
    token replay."""
    rng = np.random.default_rng(13)
    prompt = _prompts(rng, [6])[0]
    want, _ = _replay_generate(params, prompt, 8, 64, cfg=CFG32)
    eng = ServeEngine(CFG32, RUN, max_slots=2, max_len=64, params=params)
    greedy = eng.submit(prompt, max_new_tokens=8)
    eng.submit(_prompts(rng, [5])[0], max_new_tokens=8, temperature=1.5,
               seed=7)
    eng.run_until_drained()
    assert greedy.tokens == want


def test_sampling_top_k_one_is_argmax(params):
    """top_k=1 collapses sampling to argmax whatever the temperature."""
    prompt = np.arange(1, 8, dtype=np.int32)
    eng = ServeEngine(CFG32, RUN, max_slots=1, max_len=64, params=params)
    greedy = eng.submit(prompt, max_new_tokens=10)
    eng.run_until_drained()
    topk1 = eng.submit(prompt, max_new_tokens=10, temperature=3.0, top_k=1,
                       seed=99)
    eng.run_until_drained()
    assert topk1.tokens == greedy.tokens


def test_sampling_stream_survives_preemption(params):
    """The per-slot PRNG keys ride the checkpoint: a preempted-and-resumed
    sampled stream equals the uninterrupted one."""
    prompt = np.arange(1, 8, dtype=np.int32)
    ref_eng = ServeEngine(CFG32, RUN, max_slots=2, max_len=64, params=params)
    ref_req = ref_eng.submit(prompt, max_new_tokens=12, temperature=1.0,
                             seed=42)
    ref_eng.run_until_drained()

    eng = ServeEngine(CFG32, RUN, max_slots=2, max_len=64, params=params)
    req = eng.submit(prompt, max_new_tokens=12, temperature=1.0, seed=42)
    for _ in range(5):
        eng.step()
    state = eng.checkpoint()
    eng._release_state()
    eng.restore(state)
    eng.run_until_drained()
    assert req.state is RequestState.DONE
    assert req.tokens == ref_req.tokens


# ---------------------------------------------------------------------------
# service stages on the runtime
# ---------------------------------------------------------------------------


def _service_pipeline(engine, priority=0, quota=None):
    return Pipeline("serve", [Stage(
        "engine",
        lambda comm, upstream, control=None, resume_state=None:
            engine.run_service(control, resume_state=resume_state),
        kind="inference", service=True, priority=priority)], quota=quota)


def test_service_stage_excluded_from_barrier(params):
    eng = ServeEngine(CFG, RUN, max_slots=1, max_len=32, params=params,
                      idle_wait_s=0.002)
    pm = PilotManager()
    agent = RemoteAgent(pm.submit_pilot(PilotDescription()), max_workers=2)
    try:
        pipe = Pipeline("mixed", [
            Stage("plain", lambda comm, upstream: 41),
            Stage("engine",
                  lambda comm, upstream, control=None, resume_state=None:
                      eng.run_service(control, resume_state=resume_state),
                  kind="inference", service=True),
        ])
        out = pipe.run(agent)  # returns when `plain` is done
        assert out["plain"] == 41
        svc = pipe.tasks["engine"]
        assert not svc.finalized, "service must outlive the barrier"
        req = pipe.control("engine").submit_request(
            Request(np.arange(1, 5, dtype=np.int32), max_new_tokens=3))
        assert req.wait(30) and req.state is RequestState.DONE
        assert pipe.stop_services(drain=True, timeout=30)
        assert pipe.results["engine"]["completed"] == 1
    finally:
        agent.close()


def test_failed_pipeline_stops_its_service(params):
    """A pipeline whose ordinary stage fails must stop its service stages
    on the way out — a leaked service would pin its device lease forever."""
    eng = ServeEngine(CFG, RUN, max_slots=1, max_len=32, params=params,
                      idle_wait_s=0.002)
    pm = PilotManager()
    agent = RemoteAgent(pm.submit_pilot(PilotDescription()), max_workers=2)
    try:
        pipe = Pipeline("doomed", [
            Stage("boom", lambda comm, upstream: 1 / 0, max_retries=0),
            Stage("engine",
                  lambda comm, upstream, control=None, resume_state=None:
                      eng.run_service(control, resume_state=resume_state),
                  kind="inference", service=True),
        ])
        with pytest.raises(RuntimeError, match="boom"):
            pipe.run(agent)
        svc = pipe.tasks["engine"]
        assert svc.wait(30), "service must stop when the pipeline fails"
        assert svc.state is TaskState.DONE
    finally:
        agent.close()


def test_service_stage_cannot_be_dependency():
    pipe = Pipeline("bad", [
        Stage("svc", lambda c, u: None, service=True),
        Stage("after", lambda c, u: None, deps=("svc",)),
    ])
    with pytest.raises(RuntimeError, match="depends on service"):
        pipe._validate_dag()


def test_training_preempts_service_and_it_resumes(params):
    """Acceptance scenario: a service stage and a training pipeline share
    one PilotManager; higher-priority training preempts the engine (it
    checkpoints + yields its device), then the engine resumes from the
    checkpoint and drains every accepted request.  Zero quota violations."""
    eng = ServeEngine(CFG, RUN, max_slots=2, max_len=128, params=params,
                      idle_wait_s=0.002)
    pm = PilotManager()
    # a single-device pilot forces genuine contention: the service holds
    # the only device, so training can ONLY run by preempting it (the
    # host may emulate any device count — pin the pool size)
    agent = RemoteAgent(pm.submit_pilot(PilotDescription(num_devices=1)),
                        max_workers=2)
    try:
        serve_pipe = _service_pipeline(eng, priority=0)
        trained = threading.Event()

        def train_fn(comm, upstream):
            trained.set()
            time.sleep(0.25)
            return "trained"

        train_pipe = Pipeline("train", [
            Stage("step", train_fn, kind="train", priority=10)])

        serve_pipe.start(agent)
        ctl = serve_pipe.control("engine")
        rng = np.random.default_rng(5)
        reqs = [ctl.submit_request(Request(p, max_new_tokens=80))
                for p in _prompts(rng, [6, 6, 6])]
        deadline = time.time() + 60
        while reqs[0].first_token_at is None:
            assert time.time() < deadline, "service never started generating"
            time.sleep(0.01)

        out = PipelineScheduler(agent).run([train_pipe], timeout=60)
        assert out["train"]["step"] == "trained" and trained.is_set()

        svc_task = serve_pipe.tasks["engine"]
        assert svc_task.preemptions >= 1, "training never preempted the engine"
        assert agent.preemption_requests >= 1
        for r in reqs:
            assert r.wait(120), f"{r.rid} not drained after resume"
            assert len(r.tokens) == 80
        assert serve_pipe.stop_services(drain=True, timeout=60)
        stats = serve_pipe.results["engine"]
        assert stats["completed"] == len(reqs)
        assert stats["preemptions"] >= 1 and stats["resumes"] >= 1
        assert agent.quota_violations() == {}
    finally:
        agent.close()


def test_preemption_preserves_greedy_streams(params):
    """A preempted-and-resumed engine must emit exactly the tokens an
    uninterrupted engine would — the checkpoint carries the slot cache."""
    rng = np.random.default_rng(6)
    prompts = _prompts(rng, [5, 8])
    want = [_replay_generate(params, p, 12, 64, cfg=CFG32)[0]
            for p in prompts]

    eng = ServeEngine(CFG32, RUN, max_slots=2, max_len=64, params=params)
    ctl = ServiceControl()
    reqs = [ctl.submit_request(Request(p, max_new_tokens=12))
            for p in prompts]
    # run a few steps, force a preemption mid-generation, then resume
    from repro.core.task import ServicePreempted
    for r in ctl.take_requests():
        eng.submit(r)
    for _ in range(4):
        eng.step()
    ctl.request_preempt()
    with pytest.raises(ServicePreempted) as ei:
        eng.run_service(ctl)
    ctl._clear_preempt()
    ctl.drain()
    eng.run_service(ctl, resume_state=ei.value.state)
    for r, w in zip(reqs, want):
        assert r.state is RequestState.DONE
        assert r.tokens == w, (r.tokens, w)


def test_stop_releases_waiting_requests(params):
    """A hard stop() must FAIL outstanding requests (in-slot and queued),
    not abandon them — clients block on Request.wait() with no timeout."""
    eng = ServeEngine(CFG, RUN, max_slots=1, max_len=64, params=params)
    ctl = ServiceControl()
    r1 = ctl.submit_request(Request(np.arange(1, 6, dtype=np.int32),
                                    max_new_tokens=50))
    r2 = ctl.submit_request(Request(np.arange(1, 4, dtype=np.int32),
                                    max_new_tokens=50))
    for r in ctl.take_requests():
        eng.submit(r)
    eng.step()  # r1 occupies the only slot; r2 still queued
    ctl.stop()
    eng.run_service(ctl)
    for r in (r1, r2):
        assert r.wait(5), f"{r.rid} waiter never released"
        assert r.state is RequestState.FAILED and "stopped" in r.error
    assert not eng.has_work()


def test_agent_close_stops_running_service(params):
    """close() must signal running services to stop instead of hanging on
    the transport drain."""
    eng = ServeEngine(CFG, RUN, max_slots=1, max_len=32, params=params,
                      idle_wait_s=0.002)
    pm = PilotManager()
    agent = RemoteAgent(pm.submit_pilot(PilotDescription()), max_workers=2)
    task, = agent.submit_async([TaskDescription(
        name="svc",
        fn=lambda comm, control=None, resume_state=None:
            eng.run_service(control, resume_state=resume_state),
        kind="inference", service=True)])
    deadline = time.time() + 30
    while task.state is not TaskState.RUNNING:
        assert time.time() < deadline
        time.sleep(0.01)
    t0 = time.time()
    agent.close(timeout=30)
    assert time.time() - t0 < 30
    assert task.wait(10) and task.state is TaskState.DONE


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "minicpm3-4b",
                                  "moonshot-v1-16b-a3b"])
def test_step_programs_carry_the_model_scopes(arch):
    """The compiled decode and prefill-chunk programs name each HLO
    operation's model scope in its ``op_name``: the step, the layer loop,
    and the blocks' parts (GQA, latent attention, MoE)."""
    import re

    eng = ServeEngine(get_config(arch, smoke=True), RUN, max_slots=2,
                      max_len=32)
    texts = {k: low.compile().as_text()
             for k, low in eng.lowered_steps().items()}
    block = "mlp" if arch != "moonshot-v1-16b-a3b" else "moe"
    paths = {}
    for kind, step in (("decode", "decode_step"),
                       ("prefill_chunk", "prefill_chunk")):
        paths[kind] = [n.split("/") for n in
                       re.findall(r'op_name="([^"]*)"', texts[kind])]
        scopes = {p for path in paths[kind] for p in path}
        assert {step, "embed", "layers", "final", "attn.qkv", "attn.core",
                "attn.out", block} <= scopes
        # the layer loop's own operations: in the scan, in no block
        assert any(path[-2:-1] == ["body"] and "layers" in path
                   for path in paths[kind])
    # the decode step appends to the cache in its own scope, inside the
    # layer loop
    assert any("layers" in path and "attn.kv_append" in path
               for path in paths["decode"])
