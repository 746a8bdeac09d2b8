"""End-to-end Deep RC training driver (Session API).

The full paper pipeline as ONE stage graph under a Session:

  synthetic corpus -> Cylon-analogue Table (dedup/shuffle on a worker mesh)
  -> zero-copy Data Bridge -> LM train loop (pjit, microbatched, AdamW)
  -> async checkpointing (+restart) -> postprocess (eval perplexity)

Under ``--kind-pods`` the same graph runs with its data-engineering stage
placed on a data pod and its DL stages on a DL pod — per-stage placement,
the dependency edge crossing pilots.

CLI:
  PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --smoke \
      --steps 50 --batch 8 --seq 128
  ... --arch tinyllama-1.1b --steps 300        # ~100M-class full run
  ... --resume                                  # restart from checkpoint
  ... --kind-pods                               # data vs DL kind-split pilots
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import store
from repro.common.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.core import Session, stage
from repro.core.pilot import PilotDescription, PilotManager
from repro.dataframe.table import Table
from repro.train.state import init_train_state
from repro.train.step import make_train_step


def make_corpus(vocab: int, n_tokens: int, seed: int = 0) -> np.ndarray:
    """Synthetic Zipf-ish corpus with local structure (learnable bigrams)."""
    rng = np.random.default_rng(seed)
    base = rng.zipf(1.3, size=n_tokens).clip(max=vocab - 1)
    # inject deterministic bigram structure so loss can actually drop
    base[1::2] = (base[::2][: len(base[1::2])] * 7 + 3) % vocab
    return base.astype(np.int32)


def run(args) -> dict:
    cfg = get_config(args.arch, smoke=args.smoke)
    run_cfg = RunConfig(num_microbatches=args.microbatches,
                        learning_rate=args.lr)
    ckpt_dir = args.ckpt_dir or os.path.join("results", "ckpt", cfg.name)

    # kind-aware pods: split the machine into a data-engineering pod and a
    # DL pod (PilotDescription(task_kinds=...)); the Session's placement
    # policy routes each STAGE to the pod admitting its kind, so the DAG
    # below stays ONE pipeline whose dependency edges cross pilots.
    # Falls back to one shared pod when the machine cannot back two pools.
    pm = PilotManager()  # inventory; the Session materializes pods lazily
    kind_pods = args.kind_pods and pm.free_devices() >= 2
    if kind_pods:
        n_data = max(1, pm.free_devices() // 4)
        pods = [
            PilotDescription(num_devices=n_data, name="pod-data",
                             task_kinds=("data_engineering",)),
            PilotDescription(name="pod-dl",
                             task_kinds=("train", "inference")),
        ]
    else:
        pods = None
    session = Session(manager=pm, pods=pods, max_workers_per_pilot=2)

    # the three stage bodies below close over the driver's args/cfg by
    # design and run on the Session's default in-process transport; they
    # are not subprocess-portable (PKL001 records that decision)
    @stage(kind="data_engineering")
    def preprocess(ctx):  # noqa: PKL001 — in-process driver stage
        corpus = make_corpus(cfg.vocab_size, args.batch * args.seq * (args.steps + 8))
        n_rows = len(corpus) // args.seq
        rows = corpus[: n_rows * args.seq].reshape(n_rows, args.seq)
        table = Table.from_columns(
            {"tokens": rows, "row_id": np.arange(n_rows, dtype=np.int32)}
        )
        return table

    @stage(kind="train", checkpoint=ckpt_dir)
    def train(ctx):  # noqa: PKL001 — in-process driver stage
        table = ctx.upstream["preprocess"]
        # compute on the devices this stage leased: state replicated over
        # the stage's mesh, batch split over its data axis where it divides
        mesh = ctx.comm.mesh
        replicated = NamedSharding(mesh, P())
        batch_sharding = NamedSharding(
            mesh, P("data") if args.batch % mesh.size == 0 else P())
        with jax.default_device(ctx.comm.devices[0]):
            state = init_train_state(jax.random.PRNGKey(args.seed), cfg,
                                     run_cfg)
        state = jax.device_put(state, replicated)
        start_step = 0
        # ctx.resume_step is threaded in by the agent on checkpoint-aware
        # retry (the stage declares checkpoint=); --resume covers the
        # cold-start case where the user restarts the whole driver
        resume_from = ctx.resume_step
        if resume_from is None and args.resume:
            resume_from = store.latest_step(ckpt_dir)
        if resume_from is not None:
            state = jax.device_put(
                store.restore(ckpt_dir, state, step=resume_from), replicated)
            start_step = int(state["step"])
            print(f"[train] resumed from step {start_step}")
        step_fn = jax.jit(make_train_step(cfg, run_cfg), donate_argnums=(0,))
        ckpt = store.AsyncCheckpointer(ckpt_dir, keep=2)
        tokens = table.col("tokens")
        n_rows = tokens.shape[0]
        losses, step_s = [], []
        t0 = time.time()
        for i in range(start_step, args.steps):
            t_step = time.time()
            lo = (i * args.batch) % max(n_rows - args.batch, 1)
            chunk = jax.lax.dynamic_slice_in_dim(tokens, lo, args.batch, 0)
            batch = jax.device_put(
                {"tokens": chunk, "labels": jnp.roll(chunk, -1, axis=1)},
                batch_sharding)
            state, metrics = step_fn(state, batch)
            losses.append(float(metrics["loss"]))  # waits for the step
            step_s.append(time.time() - t_step)
            if args.ckpt_every and (i + 1) % args.ckpt_every == 0:
                ckpt.save(i + 1, state)
            if (i + 1) % max(args.steps // 10, 1) == 0:
                dt = (time.time() - t0) / (i + 1 - start_step)
                print(f"[train] step {i+1}/{args.steps} loss={losses[-1]:.4f} "
                      f"({dt:.2f}s/step)", flush=True)
        ckpt.save(args.steps, state)
        ckpt.close()
        return {"losses": losses, "state_step": int(state["step"]),
                "train_s": time.time() - t0, "step_s": step_s,
                "state_devices": sorted({d.id for leaf in jax.tree.leaves(state)
                                         for d in leaf.devices()})}

    @stage(kind="inference")
    def postprocess(ctx):  # noqa: PKL001 — in-process driver stage
        r = ctx.upstream["train"]
        first = np.mean(r["losses"][:5]) if len(r["losses"]) >= 5 else r["losses"][0]
        last = np.mean(r["losses"][-5:])
        return {"first_loss": float(first), "last_loss": float(last),
                "improved": bool(last < first), "train_s": r["train_s"],
                "steps": len(r["losses"]), "losses": r["losses"],
                "step_s": r["step_s"], "state_devices": r["state_devices"]}

    # ONE pipeline regardless of pod layout: under --kind-pods the
    # preprocess stage resolves to pod-data and train/postprocess to
    # pod-dl, with the dependency edge crossing agents — no manual split,
    # no blocking handoff.  Session.close() (the context manager) recycles
    # agents AND pilots on every exit path, including failures.
    with session:
        pipe = session.start(preprocess >> train >> postprocess,
                             name=f"train-{cfg.name}")
        pipe.wait()
        if pipe.error is not None:
            raise RuntimeError(f"pipeline {pipe.name} {pipe.error}")
        out = pipe.results
    res = out["postprocess"]
    res["overheads"] = {k: v for k, v in pipe.tasks["train"].overhead_s.items()}
    res["placement"] = pipe.stage_placements()
    res["kind_pods"] = {p.uid: sorted(p.task_kinds) for p in session.pilots} \
        if kind_pods else None
    res["pod_devices"] = {p.uid: [d.id for d in p.alive_devices()]
                          for p in session.pilots}
    print(f"[deep-rc] {cfg.name}: loss {res['first_loss']:.4f} -> "
          f"{res['last_loss']:.4f} in {res['steps']} steps "
          f"({res['train_s']:.1f}s); runtime overheads: {res['overheads']}; "
          f"placement: {res['placement']}")
    return res


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--kind-pods", action="store_true",
                    help="split data-engineering vs DL stages onto "
                         "kind-specialised pilots (needs >= 2 devices)")
    return ap


if __name__ == "__main__":
    enable_compile_cache()
    run(build_parser().parse_args())
