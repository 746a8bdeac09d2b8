"""Decode-attention kernel microbenchmark: jnp oracle vs vector-length
flash-decode (split-K Pallas) vs paged flash-decode (block-table gather),
at serving-shaped decode batches with ragged per-row cache lengths.

On TPU the Pallas kernels compile to Mosaic; elsewhere they run in
interpret mode (plain XLA), which is a *correctness* vehicle — it pays
per-grid-program overhead, so on the CPU container the oracle usually
wins and ``kernels/ops`` resolves ``impl="auto"`` to it.  The point of
recording both is exactly that dispatch decision: the numbers in
``results/bench/decode_kernel.json`` document where each path pays off
(and every row re-asserts kernel/oracle parity before timing).

Run standalone:

  PYTHONPATH=src python benchmarks/decode_kernel.py [--quick]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from benchmarks.results_io import bench_json, merge_record

RESULTS_JSON = bench_json("decode_kernel")

# (label, B, H, KV, S, D, block_k, page_size)
SHAPES = [
    ("gqa_4x512", 4, 8, 2, 512, 64, 128, 64),
    ("gqa_8x1024", 8, 8, 2, 1024, 64, 256, 64),
    ("mha_4x512", 4, 8, 8, 512, 64, 128, 64),
]
QUICK_SHAPES = [("gqa_4x256", 4, 8, 2, 256, 32, 128, 64)]


def _time_us(fn, *args, iters=30):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def _bench_shape(label, B, H, KV, S, D, block_k, page_size, iters):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.ops import _resolve_decode

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, KV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, KV, D)), jnp.float32)
    lens = jnp.asarray(rng.integers(1, S + 1, B), jnp.int32)

    num_pages = B * (S // page_size)
    max_pages = S // page_size
    bt = jnp.asarray(
        rng.permutation(num_pages)[:B * max_pages].reshape(B, max_pages),
        jnp.int32)
    # one layer's pools: the kernels take every layer's stacked, and a
    # layer index
    kp = jnp.asarray(
        rng.standard_normal((1, num_pages, page_size, KV, D)), jnp.float32)
    vp = jnp.asarray(
        rng.standard_normal((1, num_pages, page_size, KV, D)), jnp.float32)

    # kernel mode: real Pallas on TPU, interpret-mode Pallas elsewhere
    kmode = _resolve_decode("auto")
    if kmode == "ref":
        kmode = "interpret"

    f_ref = jax.jit(lambda q, k, v, l: ops.decode_attention(
        q, k, v, l, impl="ref"))
    f_vec = jax.jit(lambda q, k, v, l: ops.decode_attention(
        q, k, v, l, impl=kmode, block_k=block_k))
    f_pref = jax.jit(lambda q, kp, vp, bt, l: ops.decode_attention_paged(
        q, kp, vp, bt, l, 0, impl="ref"))
    f_pag = jax.jit(lambda q, kp, vp, bt, l: ops.decode_attention_paged(
        q, kp, vp, bt, l, 0, impl=kmode))

    # parity gate before timing: the kernels must match the oracles
    np.testing.assert_allclose(
        np.asarray(f_vec(q, k, v, lens)), np.asarray(f_ref(q, k, v, lens)),
        atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(f_pag(q, kp, vp, bt, lens)),
        np.asarray(f_pref(q, kp, vp, bt, lens)), atol=2e-5, rtol=2e-5)

    return {
        "shape": dict(B=B, H=H, KV=KV, S=S, D=D, block_k=block_k,
                      page_size=page_size),
        "kernel_mode": kmode,
        "ref_us": round(_time_us(f_ref, q, k, v, lens, iters=iters), 1),
        "veclen_us": round(_time_us(f_vec, q, k, v, lens, iters=iters), 1),
        "paged_ref_us": round(
            _time_us(f_pref, q, kp, vp, bt, lens, iters=iters), 1),
        "paged_us": round(
            _time_us(f_pag, q, kp, vp, bt, lens, iters=iters), 1),
    }


def bench_decode_kernel(quick: bool = False, full: bool = False):
    shapes = QUICK_SHAPES if quick else SHAPES
    iters = 5 if quick else 30
    rows = []
    results = {}
    for spec in shapes:
        r = _bench_shape(*spec, iters=iters)
        label = spec[0]
        results[label] = r
        rows.append((f"decode_kernel/{label}_ref", r["ref_us"],
                     f"us={r['ref_us']}"))
        rows.append((f"decode_kernel/{label}_veclen", r["veclen_us"],
                     f"us={r['veclen_us']};mode={r['kernel_mode']};"
                     f"vs_ref={r['ref_us'] / max(r['veclen_us'], 1e-9):.2f}x"))
        rows.append((f"decode_kernel/{label}_paged", r["paged_us"],
                     f"us={r['paged_us']};mode={r['kernel_mode']};"
                     f"vs_ref={r['paged_ref_us'] / max(r['paged_us'], 1e-9):.2f}x"))
    if not quick:
        merge_record(RESULTS_JSON, results)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    for name, val, derived in bench_decode_kernel(quick=args.quick):
        print(f"{name},{val:.2f},{derived}")
    print("decode kernel microbench OK (kernel/oracle parity asserted "
          + ("; --quick prints only)" if args.quick
             else "; recorded to results/bench/decode_kernel.json)"))
