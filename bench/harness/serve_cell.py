"""A serving cell: a ``ServeEngine`` as a ``Session`` service stage, fed
through ``ServiceHandle.submit_request`` as ``launch/serve.py`` runs it.

Set-up makes the weights from the seed on the device, builds the engine
at the configuration's sizes (page size and prefill chunk at the engine's
defaults), runs every decode and prefill-chunk shape the mix can reach
once through the engine's own jitted steps, starts the service and lets
the engine fill its slots.  The window then measures for ``--seconds``.
In a traced run the compiled HLO of every warmed step program is taken
after the window, so the trace's operations can be mapped to their model
scopes.  Afterwards the engine's state is freed and the plain reference of
the configuration's family checks, token by token, a seeded sample of the
requests the window finished (the longest among them) and the unfinished
request whose context had grown longest: the longest contexts the window
decoded.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import gc
import numbers
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np
from repro.core import stage

from bench.harness import result
from bench.harness import traffic as tr
from bench.harness.result import log, percentile
from bench.harness.trace import WINDOW_SPAN, profiler_options

SAMPLE_REQUESTS = 4   # finished requests the reference replays
# engine counters every window reports, 0 where the engine never bumped one
COUNTERS = ("decode_steps", "decode_slot_steps", "prefill_chunks",
            "prefill_tokens", "retraces", "failed", "completed",
            "tokens_generated")


def _bucket(n: int, lo: int) -> int:
    p = lo
    while p < n:
        p *= 2
    return p


def _buckets(lo_n: int, hi_n: int, floor: int, cap: int) -> List[int]:
    """Power-of-two buckets from the one holding ``lo_n`` to the one
    holding ``hi_n`` (floored at ``floor``), capped at ``cap``."""
    out, b = [], _bucket(max(lo_n, 1), floor)
    while True:
        out.append(min(b, cap))
        if b >= hi_n or b >= cap:
            break
        b *= 2
    return sorted(set(out))


def engine_slots(config: Dict[str, Any], family, max_len: int) -> int:
    """The largest multiple of ``slot_multiple`` whose fully backed KV
    cache fits the configuration's cache budget at this ``max_len``."""
    serve = config["serve"]
    per_slot = family.kv_bytes_per_token(config) * max_len
    mult = int(serve["slot_multiple"])
    slots = int(serve["kv_cache_budget_bytes"] // per_slot) // mult * mult
    if slots < mult:
        raise ValueError(f"max_len {max_len} leaves no room for "
                         f"{mult} slots in the cache budget")
    return slots


@dataclasses.dataclass
class Served:
    spec: tr.RequestSpec
    request: Any                  # repro.serve.Request


@stage(kind="inference", service=True, name="engine")
def engine_stage(ctx, engine):  # noqa: PKL001 in-process service body
    """The service stage's body: the engine the set-up built, placed on
    the stage's leased device, serving until the harness stops it."""
    engine.place(ctx.comm.devices[0])
    return engine.run_service(ctx.control, resume_state=ctx.resume_state)


class ServeCell:
    """Set-up, window and check of one serving cell in one process."""

    def __init__(self, cell, seed: int):
        self.cell = cell
        self.seed = seed
        self.config = cell.config
        self.family = cell.family()
        self.traffic = cell.traffic
        self.max_len = int(self.traffic["max_len"])
        self.slots = engine_slots(self.config, self.family, self.max_len)
        self.engine = None
        self.params = None

    # -- set-up --------------------------------------------------------------

    def build(self) -> None:
        from repro.configs.base import RunConfig
        from repro.serve import ServeEngine
        from repro.train.state import model_specs
        from bench.harness.weights import make_weights

        self.cfg = self.family.program_config(self.config)
        self.params = make_weights(model_specs(self.cfg), self.seed)
        self.engine = ServeEngine(self.cfg, RunConfig(),
                                  max_slots=self.slots, max_len=self.max_len,
                                  params=self.params, name="bench")
        # the service stage places the engine on its lease's device; doing
        # it here first gives the warm-up the arguments the window passes
        import jax

        self.engine.place(jax.devices()[0])
        log(f"engine: {self.slots} slots x {self.max_len} tokens, "
            f"{self.engine.num_pages} pages of {self.engine.page_size}")

    def warm_shapes(self):
        """Every (kind, width, table pages) the mix can make the engine
        dispatch, all greedy: decode over its page-table buckets, prefill
        chunks over chunk and page buckets.  Both tables hold at least the
        pages of the shortest prompt: a decode table spans every slot's
        pages, and the first row of a prefill chunk always ends at or past
        the shortest prompt's length."""
        e = self.engine
        ps = e.page_size
        lo_pages = -(-int(self.traffic["prompt"]["min"]) // ps)
        hi_prompt = int(self.traffic["prompt"]["max"])
        chunk = e.prefill_chunk_tokens or self.max_len
        shapes = [("decode", 1, mb) for mb in
                  _buckets(lo_pages, e.max_pages, 1, e.max_pages)]
        for T in _buckets(1, min(chunk, hi_prompt), 2, 1 << 30):
            for mb in _buckets(lo_pages, -(-hi_prompt // ps), 1,
                               e.max_pages):
                shapes.append(("prefill", T, mb))
        return shapes

    def _args(self, kind: str, T: int, mb: int):
        import jax.numpy as jnp

        e = self.engine
        S = e.max_slots
        bt = jnp.asarray(np.full((S, mb), e.num_pages, np.int32))
        zeros = np.zeros(S, np.int32)
        if kind == "decode":
            return e._decode, (
                e.params, jnp.asarray(zeros), e.cache, jnp.asarray(zeros),
                jnp.asarray(np.zeros(S, bool)),
                jnp.asarray(np.zeros((S, 2), np.uint32)),
                jnp.asarray(np.zeros(S, np.float32)),
                jnp.asarray(zeros), bt), {"sampling": False}
        return e._get_prefill(T), (
            e.params, jnp.asarray(np.zeros((S, T), np.int32)),
            jnp.asarray(zeros), jnp.asarray(zeros), e.cache, bt), {}

    def warm(self, precompile: bool = False, workers: int = 8) -> int:
        """Run every shape once on inert rows through the engine's own
        jitted steps, so they hold it: each loads from the persistent
        cache, or compiles into it.  With ``precompile`` (a cold cache)
        the shapes are first compiled in parallel into that cache.
        Returns the number of shapes."""
        import jax

        shapes = self.warm_shapes()
        if precompile:
            def compile_one(shape):
                fn, args, kw = self._args(*shape)
                fn.lower(*args, **kw).compile()

            with concurrent.futures.ThreadPoolExecutor(workers) as pool:
                for f in [pool.submit(compile_one, s) for s in shapes]:
                    f.result()
        for shape in shapes:
            fn, args, kw = self._args(*shape)
            self.engine.cache = fn(*args, **kw)[-1]
        jax.block_until_ready(self.engine.cache)
        return len(shapes)

    def hlo_texts(self) -> List[str]:
        """The compiled HLO text of every step program ``warm`` runs,
        from the programs the engine holds (nothing compiles anew)."""
        texts = []
        for shape in self.warm_shapes():
            fn, args, kw = self._args(*shape)
            texts.append(fn.lower(*args, **kw).compile().as_text())
        return texts

    # -- window --------------------------------------------------------------

    def run(self, seconds: float, trace_dir: Optional[str],
            compiles=None) -> Dict:
        """Serve the mix with the queue kept topped up; measure
        ``seconds`` after the ramp.  Returns the window's bounds, the
        requests and the engine counters' deltas."""
        import jax
        from repro.core import Session
        from repro.core.pilot import PilotDescription
        from repro.serve import Request

        vocab = int(self.config["vocab_size"])
        specs = tr.serve_requests(self.traffic, self.seed, vocab)
        ahead = int(self.traffic["queue_ahead"])
        served: List[Served] = []
        nxt = 0

        with Session(pods=[PilotDescription(name="bench-serve")],
                     max_workers_per_pilot=2) as session:
            handle = session.serve(
                engine_stage.bind(engine=self.engine), name="serve")
            t0 = time.time() + float(self.traffic["ramp_s"])
            t1 = t0 + seconds
            stats0 = stats1 = None
            span = None
            trace_on = False
            while True:
                now = time.time()
                task = handle.task
                if task is not None and task.finalized:
                    raise RuntimeError(f"serve task ended: {task.error}")
                if trace_dir and not trace_on and now >= t0 - 1.5:
                    jax.profiler.start_trace(
                        trace_dir, profiler_options=profiler_options())
                    trace_on = True
                if stats0 is None and now >= t0:
                    stats0 = self.engine.stats()
                    t0 = time.time()
                    t1 = t0 + seconds
                    if compiles is not None:
                        compiles.counting = True
                    if trace_dir:
                        span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
                        span.__enter__()
                if now >= t1 and stats0 is not None:
                    if span is not None:
                        span.__exit__(None, None, None)
                    if compiles is not None:
                        compiles.counting = False
                    stats1 = self.engine.stats()
                    break
                live = sum(1 for s in served[-(self.slots + ahead + 64):]
                           if not s.request.done())
                while live < self.slots + ahead and nxt < len(specs):
                    r = Request(specs[nxt].prompt,
                                max_new_tokens=specs[nxt].max_new_tokens)
                    served.append(Served(specs[nxt], r))
                    handle.submit_request(r)
                    nxt += 1
                    live += 1
                time.sleep(0.02)
            if trace_on:
                jax.profiler.stop_trace()
            # what the window served is fixed now: hard-stop the rest
            handle.stop(drain=False, timeout=120)
        if nxt >= len(specs):
            raise RuntimeError("the traffic file ran out of requests")
        return {"t0": t0, "t1": t1, "served": served,
                "counters": counter_deltas(stats0, stats1),
                "max_slots": self.slots}

    # -- after the window ----------------------------------------------------

    def free_engine(self) -> None:
        """Drop the engine's cache and compiled steps; the benchmark's
        weights stay for the reference."""
        self.engine.cache = None
        self.engine = None
        gc.collect()


def measure(cell, seed: int, seconds: float, trace_dir: Optional[str],
            compiles, devices, process_start: float,
            precompile: bool = False) -> Dict:
    """Set-up, window and check of one serving cell: the result's parts."""
    run = ServeCell(cell, seed)
    run.build()
    n = run.warm(precompile)
    log(f"warmed {n} shapes; set-up so far "
        f"{time.time() - process_start:.1f}s")
    win = run.run(seconds, trace_dir, compiles)
    device = result.device_facts(devices)
    done, bad = finished(win), failed(win)
    short = [s for s in done
             if len(s.request.tokens) != s.spec.max_new_tokens]
    chosen = sample(done, seed) + longest_open(win)
    hlo = run.hlo_texts() if trace_dir else None
    run.free_engine()
    t_ref = time.time()
    gaps = logit_gaps(cell, run.params, chosen)
    log(f"reference over {gaps['tokens_checked']} tokens of {len(chosen)} "
        f"requests (longest context {gaps['longest_context']}) in "
        f"{time.time() - t_ref:.1f}s")
    limits = cell.config["correct"]
    checks = {
        "logit_gap": {"value": gaps["logit_gap"],
                      "limit": float(limits["logit_gap"])},
        "tokens_checked": {"value": gaps["tokens_checked"],
                           "limit": int(limits["min_tokens_checked"])},
        "short_answers": {"value": len(short), "limit": 0},
    }
    correct = (gaps["logit_gap"] <= checks["logit_gap"]["limit"]
               and gaps["tokens_checked"] >= checks["tokens_checked"]["limit"]
               and not short)
    return {"correct": correct, "attempted": len(done) + len(bad),
            "failed": len(bad), "device": device, "checks": checks,
            "setup_s": win["t0"] - process_start,
            "e2e": e2e_metrics(win),
            "work": window_work(win, cell.config, run.family),
            "counters": win["counters"], "max_slots": win["max_slots"],
            "hlo": hlo}


def counter_deltas(before: Dict[str, Any], after: Dict[str, Any]
                   ) -> Dict[str, Any]:
    """The window's change in every numeric engine counter both
    snapshots hold (``ServeEngine.stats()``), and in ``COUNTERS``."""
    def number(x) -> bool:
        return isinstance(x, numbers.Real) and not isinstance(x, bool)

    delta = {k: after.get(k, 0) - before.get(k, 0) for k in COUNTERS}
    delta.update({k: after[k] - before[k] for k in after
                  if k in before and number(after[k]) and number(before[k])})
    return delta


def e2e_metrics(win: Dict) -> Dict[str, float]:
    """The end-to-end numbers of a serving window, every one over all the
    tokens of the window."""
    t0, t1 = win["t0"], win["t1"]
    toks = 0
    gaps: List[float] = []
    for s in win["served"]:
        times = s.request.token_times
        toks += sum(1 for t in times if t0 <= t <= t1)
        gaps += [b - a for a, b in zip(times, times[1:]) if t0 <= b <= t1]
    out = {"serve_tok_s": toks / (t1 - t0)}
    if gaps:
        out["itl_p95_ms"] = percentile(gaps, 95) * 1e3
    return out


def window_work(win: Dict, config: Dict[str, Any], family) -> Dict[str, Any]:
    """Work the window's tokens needed, counted from the request stream:
    each output token attends its prompt and the tokens before it.
    ``model_flops`` from the family's ``prompt_flops`` and
    ``token_flops``; beside it ``(operations, bytes)`` under each name
    the family's ``prompt_work(config, prompt)`` gives for a prompt whose
    first token falls in the window and its ``token_work(config, keys)``
    for every later output token in it (without ``token_work``, its
    ``decode_attention_work`` as ``decode_attention``)."""
    t0, t1 = win["t0"], win["t1"]
    token_work = getattr(family, "token_work", None) or (
        lambda c, keys: {
            "decode_attention": family.decode_attention_work(c, keys)})
    prompt_work = getattr(family, "prompt_work", None)
    flops = 0.0
    work: Dict[str, Any] = {}

    def add(items: Dict[str, Any]) -> None:
        for name, (f, b) in items.items():
            f0, b0 = work.get(name, (0.0, 0.0))
            work[name] = (f0 + f, b0 + b)

    for s in win["served"]:
        r = s.request
        P = r.prompt_len
        if r.first_token_at is not None and t0 <= r.first_token_at <= t1:
            flops += family.prompt_flops(config, P)
            if prompt_work is not None:
                add(prompt_work(config, P))
        for j, t in enumerate(r.token_times):
            if j == 0 or not (t0 <= t <= t1):
                continue
            keys = P + j
            flops += family.token_flops(config, keys, logits=True)
            add(token_work(config, keys))
    return {"model_flops": flops, **work}


def finished(win: Dict) -> List[Served]:
    return [s for s in win["served"]
            if s.request.error is None and s.request.finished_at is not None
            and s.request.finished_at <= win["t1"]]


def failed(win: Dict) -> List[Served]:
    return [s for s in win["served"]
            if s.request.error is not None and s.request.finished_at is not None
            and s.request.finished_at <= win["t1"]]


def sample(done: List[Served], seed: int, k: int = SAMPLE_REQUESTS
           ) -> List[Served]:
    """The finished request with the most tokens, and ``k - 1`` more
    drawn from the seed."""
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: done[i].request.prompt_len
                  + len(done[i].request.tokens))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng([int(seed), 7])
    pick = list(rng.choice(rest, size=min(k - 1, len(rest)), replace=False))
    return [done[i] for i in [longest] + sorted(pick)]


def longest_open(win: Dict) -> List[Served]:
    """The request still decoding when the window closed whose prompt and
    served tokens were longest: every token it was served counts, and it
    reaches contexts that no request finished in the window does."""
    live = [s for s in win["served"] if s.request.tokens
            and (s.request.finished_at is None
                 or s.request.finished_at > win["t1"])]
    if not live:
        return []
    return [max(live, key=lambda s: s.request.prompt_len
                + len(s.request.tokens))]


def served_gaps(ref_logits: np.ndarray, prompt_len: int,
                served: np.ndarray) -> np.ndarray:
    """How far each served token's reference logit lies below the
    reference's best at the position that produced it."""
    rows = ref_logits[prompt_len - 1: prompt_len - 1 + len(served)]
    best = rows.max(axis=-1)
    got = rows[np.arange(len(served)), served]
    return best - got


def sequence(prompt: np.ndarray, served: np.ndarray, pad_to: int
             ) -> np.ndarray:
    """The tokens the reference reads: the prompt and every served token
    but the last, padded at the end to one length for every request."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    if len(seq) > pad_to:
        raise ValueError(f"sequence of {len(seq)} exceeds {pad_to}")
    return np.pad(seq, (0, pad_to - len(seq)))


def logit_gaps(cell, params, chosen: List[Served],
               control: Optional[Callable] = None) -> Dict[str, float]:
    """Widest gap of a served token's reference logit below the
    reference's best; with ``control``, also the widest gap of the token
    that arithmetic puts first at each of the same positions."""
    import jax

    ref = cell.reference()
    pad_to = int(cell.traffic["max_len"])
    worst = worst_ctl = 0.0
    tokens = longest = 0
    for s in chosen:
        served = np.asarray(s.request.tokens, np.int32)
        seq = jax.numpy.asarray(sequence(s.request.prompt, served, pad_to))
        exact = np.asarray(jax.device_get(
            ref.forward(params, seq, cell.config)))
        P = s.request.prompt_len
        worst = max(worst, float(served_gaps(exact, P, served).max()))
        tokens += len(served)
        longest = max(longest, P + len(served))
        if control is not None:
            low = np.asarray(jax.device_get(
                ref.forward(params, seq, cell.config, quant=control)))
            picked = low[P - 1: P - 1 + len(served)].argmax(-1)
            worst_ctl = max(worst_ctl, float(
                served_gaps(exact, P, picked).max()))
    out = {"logit_gap": worst, "tokens_checked": tokens,
           "longest_context": longest}
    if control is not None:
        out["control_gap"] = worst_ctl
    return out
