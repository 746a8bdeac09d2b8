"""Chip smoke test: drive the serving and training main paths once on a TPU.

    python chip_smoke.py              # one chip: kernels, serve, train
    python chip_smoke.py --chips 4    # only the paths that span 4 chips

Everything runs in this one process: a chip belongs to one process, so no
phase starts a child that needs it.  Readings go to stdout on lines that
start with ``[chip]``; the last line is one JSON object naming the device,
``{"ok": true, "device": {...}}``.  Any failed check raises, and the script
exits non-zero without that line.  On a host whose JAX backend is not a
TPU it stops before any phase and names the platform it found.

Phases (one chip):

* kernels -- each serving attention kernel against its ``kernels/ref.py``
  oracle at tinyllama-1.1b widths, in bf16, and the contiguous ones again
  on a cache length their KV spans do not divide;
* serve -- ``repro.launch.serve.run``: a ``Session`` running the
  ``engine_service`` stage, a full-width tinyllama-1.1b ``ServeEngine`` on
  paged KV answering 8 requests of 256 prompt and 32 new tokens; then
  the decode and prefill-chunk steps that engine jits must lower to
  Pallas kernels;
* train -- ``repro.launch.train.run``: the preprocess -> train ->
  postprocess stage graph on full-width xlstm-125m, batch 8 x 1024 tokens.

Phases (``--chips 4``):

* fleet -- ``--fleet 4`` tinyllama-1.1b engines, one per chip, whose greedy
  streams must equal one engine's;
* dataframe -- ``ops_dist`` shuffle / join / groupby_sum over a 4-chip mesh
  against ``ops_local`` on one chip;
* kind-pods -- the train pipeline with its data and DL stages on separate
  pods: the train state stays on the DL pod and its losses match the run
  on one chip.

The phase functions take a ``smoke`` flag (reduced configs, few tokens) so
a CPU test can rehearse them; the script itself always runs full size.
"""
from __future__ import annotations

import argparse
import collections
import json
import math
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.common.compile_cache import enable_compile_cache  # noqa: E402

SERVE_ARCH = "tinyllama-1.1b"
TRAIN_ARCH = "xlstm-125m"
# bf16 keeps 8 significant bits (relative rounding 2**-8 ~ 0.4%): a few
# roundings of O(1) attention outputs stay well inside this bound
BF16_TOL = 2e-2


def reading(phase: str, **values) -> None:
    """One line of chip readings for a phase."""
    print(f"[chip] {phase}: {json.dumps(values, default=str)}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip smoke check failed: {what}")


# -- kernels -----------------------------------------------------------------

def kernel_dims(smoke: bool) -> dict:
    """tinyllama-1.1b attention widths (GQA 32 q over 4 kv heads, head dim
    64), a 2048-row cache, the engine's 16-token pages and 64-token chunks."""
    if smoke:
        return dict(B=2, H=8, KV=2, D=16, S=64, page=16, T=8)
    return dict(B=8, H=32, KV=4, D=64, S=2048, page=16, T=64)


def kernel_phase(smoke: bool = False, interpret: bool = False) -> dict:
    """Max |kernel - oracle| of each serving kernel on seeded bf16 inputs;
    the oracle runs at the highest matmul precision.  Cache writes of the
    prefill kernels must equal the oracle's exactly."""
    from repro.kernels import decode_attention as da
    from repro.kernels import prefill_attention as pa
    from repro.kernels import ref

    d = kernel_dims(smoke)
    B, H, KV, D, S, page, T = (d[k] for k in
                               ("B", "H", "KV", "D", "S", "page", "T"))
    rng = np.random.default_rng(0)
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 8))

    def normal(*shape):
        return jax.random.normal(next(keys), shape, jnp.bfloat16)

    mp = S // page
    num_pages = B * mp
    q1, qT = normal(B, H, D), normal(B, T, H, D)
    k, v = normal(B, S, KV, D), normal(B, S, KV, D)
    kn, vn = normal(B, T, KV, D), normal(B, T, KV, D)
    # two layers' pools stacked; the kernels address layer 1 in place
    kpool = k.reshape(num_pages, page, KV, D)
    vpool = v.reshape(num_pages, page, KV, D)
    kp, vp = jnp.stack([vpool, kpool]), jnp.stack([kpool, vpool])
    lens = jnp.asarray(rng.integers(1, S + 1, B), jnp.int32)
    # scrambled pages; row 0 keeps only its live pages, the rest sentinel
    table = rng.permutation(num_pages).reshape(B, mp).astype(np.int32)
    table[0, -(-int(lens[0]) // page):] = num_pages
    table = jnp.asarray(table)
    base = jnp.asarray(rng.integers(0, S - T, B), jnp.int32)
    clens = jnp.asarray(rng.integers(1, T + 1, B), jnp.int32)
    # a cache length no span divides: the last of four spans reads 7 rows
    # past the cache, which the kernels must mask
    Sr, span = S - 7, S // 4
    kr, vr = k[:, :Sr], v[:, :Sr]
    lens_r, base_r = jnp.minimum(lens, Sr), jnp.minimum(base, Sr - T)
    kw = dict(interpret=interpret)
    cases = {
        "decode_attention": (
            lambda: da.decode_attention(q1, k, v, lens, **kw),
            lambda: ref.decode_attention_ref(q1, k, v, lens)),
        "decode_attention_paged": (
            lambda: da.decode_attention_paged(q1, kp, vp, table, lens, 1,
                                              **kw),
            lambda: ref.decode_attention_paged_ref(q1, kp, vp, table, lens,
                                                   1)),
        "prefill_attention": (
            lambda: pa.prefill_attention(qT, kn, vn, k, v, base, clens, **kw),
            lambda: ref.prefill_attention_ref(qT, kn, vn, k, v, base, clens)),
        "prefill_attention_paged": (
            lambda: pa.prefill_attention_paged(qT, kn, vn, kp, vp, table,
                                               base, clens, 1, **kw),
            lambda: ref.prefill_attention_paged_ref(qT, kn, vn, kp, vp, table,
                                                    base, clens, 1)),
        "decode_attention_span_past_cache": (
            lambda: da.decode_attention(q1, kr, vr, lens_r, block_k=span,
                                        **kw),
            lambda: ref.decode_attention_ref(q1, kr, vr, lens_r)),
        "prefill_attention_span_past_cache": (
            lambda: pa.prefill_attention(qT, kn, vn, kr, vr, base_r, clens,
                                         block_k=span, **kw),
            lambda: ref.prefill_attention_ref(qT, kn, vn, kr, vr, base_r,
                                              clens)),
    }
    errors = {}
    for name, (kernel, oracle) in cases.items():
        got = jax.block_until_ready(kernel())
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(oracle())
        if isinstance(got, tuple):  # prefill: (out, k cache', v cache')
            for g, w in zip(got[1:], want[1:]):
                check(bool(jnp.array_equal(g, w)),
                      f"{name} cache write differs from its oracle")
            got, want = got[0], want[0]
        g = np.asarray(got, np.float32)
        w = np.asarray(want, np.float32)
        check(bool(np.isfinite(g).all()), f"{name} output not finite")
        errors[name] = float(np.max(np.abs(g - w)))
        check(bool(np.all(np.abs(g - w) <= BF16_TOL * (1 + np.abs(w)))),
              f"{name} max abs error {errors[name]} over bf16 tolerance")
    return errors


# -- serve -------------------------------------------------------------------

def serve_argv(smoke: bool, fleet: int = 1) -> list:
    argv = ["--arch", SERVE_ARCH, "--batch", "8", "--quiet",
            "--fleet", str(fleet)]
    # 256-token prompts prefill in four 64-token chunks; smoke keeps two
    argv += (["--smoke", "--prompt-len", "80", "--gen", "4"] if smoke
             else ["--prompt-len", "256", "--gen", "32"])
    return argv


def serve_phase(smoke: bool = False, fleet: int = 1) -> dict:
    """Serve 8 requests through ``launch.serve.run``; every request must
    finish with exactly its requested number of tokens."""
    from repro.launch import serve

    args = serve.build_parser().parse_args(serve_argv(smoke, fleet))
    t0 = time.perf_counter()
    res = serve.run(args)
    res["wall_s"] = time.perf_counter() - t0
    check(len(res["streams"]) == args.batch,
          f"{len(res['streams'])} of {args.batch} requests came back")
    for i, s in enumerate(res["streams"]):
        check(len(s) == args.gen, f"request {i}: {len(s)} tokens, "
                                  f"expected {args.gen}")
    return res


def serve_step_kernels(smoke: bool = False) -> dict:
    """Lower the decode step and the prefill-chunk step of the engine
    ``serve_phase`` serves with, from that engine's own jitted functions;
    report whether each holds a Pallas kernel (``tpu_custom_call``) on
    this backend, and its lowering seconds."""
    from repro.launch import serve

    args = serve.build_parser().parse_args(serve_argv(smoke))
    engine = serve.build_engine(**serve.engine_args(args))
    out = {}
    t0 = time.perf_counter()
    for name, lowered in engine.lowered_steps().items():
        out[name] = {"tpu_custom_call": "tpu_custom_call" in lowered.as_text(),
                     "lower_s": time.perf_counter() - t0}
        t0 = time.perf_counter()
    return out


# -- train -------------------------------------------------------------------

def train_phase(ckpt_dir: str, smoke: bool = False,
                kind_pods: bool = False) -> dict:
    """The preprocess -> train -> postprocess graph through
    ``launch.train.run``; at least 5 steps, every loss finite."""
    from repro.launch import train

    argv = ["--arch", TRAIN_ARCH, "--steps", "6", "--batch", "8",
            "--ckpt-dir", ckpt_dir]
    argv += ["--smoke", "--seq", "32"] if smoke else ["--seq", "1024"]
    if kind_pods:
        argv.append("--kind-pods")
    res = train.run(train.build_parser().parse_args(argv))
    check(res["steps"] >= 5, f"only {res['steps']} train steps ran")
    check(all(math.isfinite(x) for x in res["losses"]),
          f"non-finite train loss in {res['losses']}")
    return res


# -- four chips --------------------------------------------------------------

def fleet_phase(smoke: bool = False) -> dict:
    """``--fleet 4`` against one engine: identical greedy streams, and the
    four engines' params and caches on four distinct devices."""
    one = serve_phase(smoke)
    four = serve_phase(smoke, fleet=4)
    devices = four["engine_devices"]
    check(len({d.id for d in devices}) == 4,
          f"fleet engines share devices: {devices}")
    check(four["streams"] == one["streams"],
          "fleet greedy streams differ from one engine's")
    return {"one_engine": one, "fleet": four}


def _rows(cols: dict, valid) -> collections.Counter:
    """Multiset of the valid rows of a column dict."""
    mask = np.asarray(valid)
    names = sorted(cols)
    data = [np.asarray(cols[n])[mask] for n in names]
    return collections.Counter(zip(*(c.tolist() for c in data)))


def dataframe_phase(smoke: bool = False) -> dict:
    """``ops_dist`` shuffle / join / groupby_sum over all devices as one
    mesh give the same row multisets as ``ops_local`` on one device."""
    from repro.dataframe import ops_dist, ops_local
    from repro.dataframe.table import Table
    from repro.launch.mesh import make_mesh

    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev,), ("data",))
    n, n_keys = (4096, 256) if smoke else (1 << 20, 1 << 12)
    rng = np.random.default_rng(0)
    keys = rng.integers(0, n_keys, n).astype(np.int32)
    vals = rng.integers(-1000, 1000, n).astype(np.int32)  # exact sums
    rkeys = np.arange(n_keys, dtype=np.int32)
    rvals = (rkeys * 7 + 1).astype(np.int32)
    left = Table.from_columns({"k": keys, "v": vals}, mesh)
    right = Table.from_columns({"k": rkeys, "w": rvals}, mesh)
    one = jax.devices()[0]
    lcols = jax.device_put({"k": keys, "v": vals}, one)
    rcols = jax.device_put({"k": rkeys, "w": rvals}, one)
    lvalid = jax.device_put(np.ones(n, bool), one)
    rvalid = jax.device_put(np.ones(n_keys, bool), one)
    out = {"rows": n, "devices": n_dev}

    t0 = time.perf_counter()
    shuffled, dropped = ops_dist.shuffle(left, "k")
    out["shuffle_s"] = time.perf_counter() - t0
    check(dropped == 0, f"shuffle dropped {dropped} rows")
    check(_rows(shuffled.columns, shuffled.valid) == _rows(lcols, lvalid),
          "shuffle changed the row multiset")

    t0 = time.perf_counter()
    joined, dropped = ops_dist.join(left, right, "k")
    out["join_s"] = time.perf_counter() - t0
    check(dropped == 0, f"join dropped {dropped} rows")
    jcols, jvalid = jax.jit(ops_local.local_hash_join, static_argnums=4)(
        lcols, lvalid, rcols, rvalid, "k")
    check(_rows(joined.columns, joined.valid) == _rows(jcols, jvalid),
          "join rows differ from ops_local")

    t0 = time.perf_counter()
    grouped, dropped = ops_dist.groupby_sum(left, "k", ["v"])
    out["groupby_s"] = time.perf_counter() - t0
    check(dropped == 0, f"groupby shuffle dropped {dropped} rows")
    gk, gs, gc = jax.jit(ops_local.local_groupby_sum, static_argnums=(2, 3, 4))(
        lcols, lvalid, "k", ("v",), n_keys)
    check(_rows(grouped.columns, grouped.valid)
          == _rows({"k": gk, "v": gs["v"], "_count": gc}, gc > 0),
          "groupby_sum rows differ from ops_local")
    return out


def kind_pods_phase(ckpt_root: str, smoke: bool = False) -> dict:
    """The train pipeline on kind-split pods against the same pipeline on
    one chip: the train state only on the DL pod, losses within 1e-4."""
    one = train_phase(os.path.join(ckpt_root, "one"), smoke)
    pods = train_phase(os.path.join(ckpt_root, "pods"), smoke,
                       kind_pods=True)
    dl = [uid for uid, kinds in pods["kind_pods"].items() if "train" in kinds]
    check(len(dl) == 1, f"expected one DL pod, got {pods['kind_pods']}")
    dl_devices = set(pods["pod_devices"][dl[0]])
    check(set(pods["state_devices"]) <= dl_devices,
          f"train state on {pods['state_devices']}, DL pod holds "
          f"{sorted(dl_devices)}")
    check(np.allclose(pods["losses"], one["losses"], rtol=1e-4, atol=1e-4),
          f"kind-pods losses {pods['losses']} vs one chip {one['losses']}")
    return {"one_chip": one, "kind_pods": pods,
            "dl_pod_devices": sorted(dl_devices)}


# -- main --------------------------------------------------------------------

def _train_summary(res: dict) -> dict:
    return {k: res[k] for k in ("losses", "step_s", "steps", "train_s",
                                "placement", "state_devices")}


def _serve_summary(res: dict) -> dict:
    keep = ("requests", "generated_tokens", "wall_s", "tokens_per_s",
            "ttft_p50_s", "inter_token_p50_s", "latency_p50_s",
            "latency_max_s", "slot_occupancy")
    out = {k: res[k] for k in keep if k in res}
    if "engine" in res:
        out["retraces"] = {k: v for k, v in res["engine"].items()
                           if k.startswith("retraces")}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: kernels, serve and train on one chip; 4: only "
                         "the fleet, dataframe and kind-pods paths")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke needs a TPU; JAX found platform {dev.platform!r} "
              f"({len(devices)} device(s))", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} chips; JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    reading("device", platform=dev.platform, kind=dev.device_kind,
            count=len(devices), compile_cache=cache_dir)

    with tempfile.TemporaryDirectory(prefix="chip-smoke-ckpt-") as ckpt:
        if args.chips == 1:
            from repro.kernels import ops

            t0 = time.perf_counter()
            errors = kernel_phase()
            reading("kernels", dims=kernel_dims(False), max_abs_error=errors,
                    tolerance=BF16_TOL, seconds=time.perf_counter() - t0)
            check(ops._resolve_decode("auto") == "pallas",
                  "auto dispatch does not pick the Pallas kernels")
            res = serve_phase()
            reading("serve", arch=SERVE_ARCH, **_serve_summary(res))
            steps = serve_step_kernels()
            reading("serve-steps", **steps)
            check(all(s["tpu_custom_call"] for s in steps.values()),
                  f"serving steps without Pallas kernels: {steps}")
            res = train_phase(os.path.join(ckpt, "train"))
            reading("train", arch=TRAIN_ARCH, batch=8, seq=1024,
                    **_train_summary(res))
        else:
            res = fleet_phase()
            reading("fleet", engine_devices=[str(d) for d in
                                             res["fleet"]["engine_devices"]],
                    one_engine=_serve_summary(res["one_engine"]),
                    fleet=_serve_summary(res["fleet"]),
                    streams_equal=True)
            reading("dataframe", **dataframe_phase())
            res = kind_pods_phase(ckpt)
            reading("kind-pods", dl_pod_devices=res["dl_pod_devices"],
                    kind_pods=_train_summary(res["kind_pods"]),
                    one_chip=_train_summary(res["one_chip"]))

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
