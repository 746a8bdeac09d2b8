"""Serving driver: a continuous-batching ServeEngine running as a
long-running *service stage* on the pilot runtime (the paper's inference
task kind living beside data engineering and training on one scheduler).

The engine prefills admitted prompts in ONE batched full-sequence forward
(no token-by-token replay), packs their KV rows into free slots of a
fixed ``[max_slots, max_len]`` cache, and fuses every occupied slot into
a single decode step.  The service stage holds its lease, is excluded
from the pipeline completion barrier, and yields to higher-priority
training work via checkpoint/resume preemption (see ``repro.serve``).

``--fleet N`` switches to the multi-engine gateway: an ``EngineRouter``
load-balances the same request stream over N engines (optionally
prefill/decode-disaggregated with ``--disaggregate``) and this driver
becomes a streaming front-end — it polls each request's live token list
and emits deltas as they land, the way a gateway would flush SSE chunks.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b --smoke \
      --batch 4 --prompt-len 32 --gen 16 [--slots 4]
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b --smoke \
      --batch 12 --fleet 3 --disaggregate
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.core import Session, stage
from repro.core.pilot import PilotDescription
from repro.serve import Request, ServeEngine


def _stream(requests, *, poll_s: float = 0.02, timeout: float = 600.0,
            quiet: bool = False) -> None:
    """Gateway-style streaming loop: ``Request.tokens`` is the live
    stream (the engine appends in place; ``_finish`` only stamps
    terminal state), so polling its length and flushing the delta is
    exactly what an SSE front-end would do per chunk."""
    seen = [0] * len(requests)
    deadline = time.time() + timeout
    while True:
        live = False
        for i, r in enumerate(requests):
            n = len(r.tokens)
            if n > seen[i] and not quiet:
                done = " done" if r.done() else ""
                print(f"[stream] {r.rid}: +{n - seen[i]} tok "
                      f"({n} total){done}", flush=True)
            seen[i] = n
            if not r.done():
                live = True
            elif r.error is not None:
                raise RuntimeError(f"{r.rid} failed: {r.error}")
        if not live:
            return
        if time.time() > deadline:
            raise RuntimeError("streaming front-end timed out")
        time.sleep(poll_s)


def run_fleet(args, cfg) -> dict:
    """Multi-engine gateway: EngineRouter over ``--fleet`` engines with
    load-aware admission; ``--disaggregate`` splits prefill/decode roles
    and migrates finished prompts by KV-page handoff."""
    from repro.serve import build_fleet

    slots = args.slots or min(args.batch, 4)
    max_len = args.prompt_len + args.gen + 1
    # one engine per device, round-robin when engines outnumber devices
    router = build_fleet(cfg, RunConfig(), num_engines=args.fleet,
                         disaggregate=args.disaggregate, seed=0,
                         max_slots=slots, max_len=max_len,
                         name_prefix="gateway", devices=jax.devices())
    router.start()
    try:
        rng = np.random.default_rng(1)
        t0 = time.time()
        requests = [
            router.submit(Request(
                rng.integers(1, cfg.vocab_size, args.prompt_len),
                max_new_tokens=args.gen))
            for _ in range(args.batch)]
        _stream(requests, quiet=args.quiet)
        wall = time.time() - t0
        stats = router.stats()
    finally:
        router.close()
    n_tok = sum(len(r.tokens) for r in requests)
    ttft = sorted(r.ttft_s for r in requests)
    res = {
        "requests": len(requests),
        "engines": args.fleet,
        "disaggregate": args.disaggregate,
        "generated_tokens": n_tok,
        "tokens_per_s": n_tok / max(wall, 1e-9),
        "ttft_p50_s": ttft[len(ttft) // 2],
        "routed": stats.get("routed", 0),
        "handoffs": stats.get("handoffs_routed", 0),
        "router": stats,
        "streams": [list(r.tokens) for r in requests],
        "engine_devices": [m.engine.device for m in router.members],
    }
    spread = {k.split("routed_to.")[1]: v for k, v in stats.items()
              if k.startswith("routed_to.")}
    print(f"[serve] {cfg.name} fleet={args.fleet}"
          f"{' disaggregated' if args.disaggregate else ''}: "
          f"{res['tokens_per_s']:.1f} tok/s over {len(requests)} reqs; "
          f"p50 ttft {res['ttft_p50_s']*1e3:.0f}ms; routed {spread}"
          + (f"; handoffs {res['handoffs']}" if args.disaggregate else ""))
    return res


@stage(kind="inference", service=True, name="engine")
def engine_service(ctx, arch: str = "tinyllama-1.1b", smoke: bool = True,
                   max_slots: int = 4, max_len: int = 49, seed: int = 0):
    """Module-level service body: builds the ServeEngine INSIDE the
    executing process (the picklable-task contract for
    ``transport="subprocess"`` — a closure over a parent-side engine
    would capture unpicklable device buffers; see README
    "Cross-process execution")."""
    engine = build_engine(arch, smoke, max_slots, max_len, seed,
                          device=ctx.comm.devices[0])
    return engine.run_service(ctx.control, resume_state=ctx.resume_state)


def engine_args(args) -> dict:
    """``engine_service``'s arguments for a single-engine serve run."""
    return dict(arch=args.arch, smoke=args.smoke,
                max_slots=args.slots or min(args.batch, 4),
                max_len=args.prompt_len + args.gen + 1, seed=0)


def build_engine(arch: str, smoke: bool, max_slots: int, max_len: int,
                 seed: int, device=None) -> ServeEngine:
    """The engine ``engine_service`` serves with."""
    return ServeEngine(get_config(arch, smoke=smoke), RunConfig(),
                       max_slots=max_slots, max_len=max_len, seed=seed,
                       device=device)


def run(args) -> dict:
    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.is_encoder_decoder or cfg.input_kind == "embeds":
        raise SystemExit("serve driver targets token-LM archs")
    if args.fleet > 1 or args.disaggregate:
        if args.disaggregate and args.fleet < 2:
            raise SystemExit("--disaggregate needs --fleet >= 2")
        return run_fleet(args, cfg)
    eargs = engine_args(args)
    slots = eargs["max_slots"]
    serve_stage = engine_service.bind(**eargs)

    # the Session's agents OWN their transports: close() drains the worker
    # pool, so the service lease is back before the pilot is recycled —
    # and close() runs on EVERY exit path (context manager), so a failed
    # serve task can no longer leak the pilot's devices
    with Session(pods=[PilotDescription(name="serve-pod")],
                 max_workers_per_pilot=2, transport=args.transport) as session:
        handle = session.serve(serve_stage, name="serve")

        rng = np.random.default_rng(1)
        t0 = time.time()
        requests = [
            handle.submit_request(Request(
                rng.integers(1, cfg.vocab_size, args.prompt_len),
                max_new_tokens=args.gen))
            for _ in range(args.batch)]
        task = handle.task
        deadline = time.time() + 600
        for r in requests:
            while not r.wait(timeout=1.0):
                # surface an engine failure immediately instead of letting
                # orphaned requests run the clock out
                if task.finalized and task.error:
                    raise RuntimeError(f"serve task failed: {task.error}")
                if time.time() > deadline:
                    raise RuntimeError(f"request {r.rid} did not finish")
        wall = time.time() - t0
        if not handle.stop(drain=True, timeout=60):
            raise RuntimeError("service stage did not drain")
        if task.error:
            raise RuntimeError(task.error)

        stats = task.result
        lat = sorted(r.latency_s for r in requests)
        ttft = sorted(r.ttft_s for r in requests)
        itl = sorted(g for r in requests for g in r.inter_token_s) or [0.0]
        n_tok = sum(len(r.tokens) for r in requests)
        res = {
            "requests": len(requests),
            "generated_tokens": n_tok,
            "tokens_per_s": n_tok / max(wall, 1e-9),
            "latency_p50_s": lat[len(lat) // 2],
            "latency_max_s": lat[-1],
            "ttft_p50_s": ttft[len(ttft) // 2],
            "inter_token_p50_s": itl[len(itl) // 2],
            "slot_occupancy": stats["slot_occupancy"],
            "engine": stats,
            "runtime_overheads": task.overhead_s,
            "preemptions": task.preemptions,
            "streams": [list(r.tokens) for r in requests],
        }
        print(f"[serve] {cfg.name}: {res['tokens_per_s']:.1f} tok/s over "
              f"{len(requests)} reqs ({slots} slots, occupancy "
              f"{res['slot_occupancy']:.2f}); p50 latency "
              f"{res['latency_p50_s']*1e3:.0f}ms, p50 ttft "
              f"{res['ttft_p50_s']*1e3:.0f}ms; overheads {task.overhead_s}")
        return res


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="number of requests to submit")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--slots", type=int, default=0,
                    help="KV-cache slots (0 = min(batch, 4))")
    ap.add_argument("--fleet", type=int, default=1,
                    help="number of engines behind the router gateway")
    ap.add_argument("--disaggregate", action="store_true",
                    help="split the fleet into prefill/decode engines "
                         "joined by KV-page handoff")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress per-request streaming deltas")
    ap.add_argument("--transport", default="in-process",
                    choices=["in-process", "subprocess"],
                    help="where the service stage executes: this process, "
                         "or a worker daemon process with its own JAX "
                         "runtime on the CPU (repro.core.exec; refused on "
                         "a TPU host)")
    return ap


if __name__ == "__main__":
    enable_compile_cache()
    run(build_parser().parse_args())
