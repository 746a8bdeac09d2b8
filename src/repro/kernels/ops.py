"""jit'd dispatch wrappers: ``impl="auto"`` -> Pallas on TPU, the jnp
reference elsewhere (``impl="interpret"`` selects interpret-mode Pallas).
The model code calls these; the dry-run lowers the ref path (XLA:CPU
cannot codegen Mosaic), real TPU runs take the kernel path."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import hash_partition as _hp
from repro.kernels import prefill_attention as _pf
from repro.kernels import ref as _ref
from repro.kernels import rmsnorm as _rms


def _on_tpu() -> bool:
    # no fallback: a backend that fails to initialise raises here rather
    # than quietly sending a TPU host down the reference path
    return jax.default_backend() == "tpu"


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if _on_tpu() else "ref"
    return impl


def flash_attention(q, k, v, *, causal=True, impl: str = "auto", **kw):
    mode = _resolve(impl)
    if mode == "pallas":
        return _fa.flash_attention(q, k, v, causal=causal, **kw)
    if mode == "interpret":
        return _fa.flash_attention(q, k, v, causal=causal, interpret=True, **kw)
    return _ref.flash_attention_ref(q, k, v, causal=causal)


def _resolve_decode(impl: str) -> str:
    """``auto`` = the Pallas flash-decode kernel on TPU, the jnp oracle
    elsewhere: XLA:CPU vectorizes the oracle's einsum, while emulated
    Pallas pays per-grid-program interpreter overhead that grows with
    ``slots x kv_heads x blocks`` — a measured 2-5x decode-step
    regression at 16 slots on the CPU container.  ``impl="interpret"``
    stays explicitly selectable (the kernel lowers to plain XLA under
    ``interpret=True``) and the CI parity suite + decode microbench run
    it on every PR, so the kernel path is exercised without TPUs."""
    if impl == "auto":
        return "pallas" if _on_tpu() else "ref"
    if impl not in ("pallas", "interpret", "ref"):
        raise ValueError(f"unknown decode impl {impl!r}: "
                         f"expected auto|pallas|interpret|ref")
    return impl


def decode_attention(q, k, v, cache_len, *, window: int = 0,
                     impl: str = "auto", **kw):
    """q [B,H,D]; k,v [B,S,KV,D]; cache_len [] or [B] int32 -> [B,H,D]."""
    mode = _resolve_decode(impl)
    if mode == "pallas":
        return _dec.decode_attention(q, k, v, cache_len, window=window, **kw)
    if mode == "interpret":
        return _dec.decode_attention(q, k, v, cache_len, window=window,
                                     interpret=True, **kw)
    return _ref.decode_attention_ref(q, k, v, cache_len, window=window)


def decode_attention_paged(q, k_pages, v_pages, block_table, cache_len,
                           layer, *, window: int = 0, impl: str = "auto",
                           **kw):
    """q [B,H,D]; pools [layers,num_pages,page_size,KV,Dp >= D] (lane
    padding past D), read at layer ``layer`` ([] int32); block_table
    [B,max_pages] int32 (sentinel >= num_pages = unallocated); cache_len
    [B] -> [B,H,D]."""
    mode = _resolve_decode(impl)
    if mode == "pallas":
        return _dec.decode_attention_paged(
            q, k_pages, v_pages, block_table, cache_len, layer,
            window=window, **kw)
    if mode == "interpret":
        return _dec.decode_attention_paged(
            q, k_pages, v_pages, block_table, cache_len, layer,
            window=window, interpret=True, **kw)
    return _ref.decode_attention_paged_ref(
        q, k_pages, v_pages, block_table, cache_len, layer, window=window)


def prefill_attention(q, k_new, v_new, k_cache, v_cache, base, chunk_lens,
                      *, impl: str = "auto", **kw):
    """Ragged cache-writing prefill, contiguous layout.  q [B,T,H,D];
    k_new, v_new [B,T,KV,D]; caches [B,S,KV,D]; base, chunk_lens [] or
    [B] int32 -> (out [B,T,H,D], k_cache', v_cache')."""
    mode = _resolve_decode(impl)
    if mode == "pallas":
        return _pf.prefill_attention(
            q, k_new, v_new, k_cache, v_cache, base, chunk_lens, **kw)
    if mode == "interpret":
        return _pf.prefill_attention(
            q, k_new, v_new, k_cache, v_cache, base, chunk_lens,
            interpret=True, **kw)
    return _ref.prefill_attention_ref(
        q, k_new, v_new, k_cache, v_cache, base, chunk_lens)


def prefill_attention_paged(q, k_new, v_new, k_pages, v_pages, block_table,
                            base, chunk_lens, layer, *, impl: str = "auto",
                            **kw):
    """Ragged cache-writing prefill through per-row block tables into
    layer ``layer`` ([] int32) of the stacked pools.  q [B,T,H,D]; pools
    [layers,num_pages,page_size,KV,Dp >= D]; block_table [B,max_pages] int32
    (sentinel >= num_pages = unallocated); base, chunk_lens [] or [B]
    int32 -> (out, k_pages', v_pages')."""
    mode = _resolve_decode(impl)
    if mode == "pallas":
        return _pf.prefill_attention_paged(
            q, k_new, v_new, k_pages, v_pages, block_table, base,
            chunk_lens, layer, **kw)
    if mode == "interpret":
        return _pf.prefill_attention_paged(
            q, k_new, v_new, k_pages, v_pages, block_table, base,
            chunk_lens, layer, interpret=True, **kw)
    return _ref.prefill_attention_paged_ref(
        q, k_new, v_new, k_pages, v_pages, block_table, base, chunk_lens,
        layer)


def rmsnorm(x, w, *, eps: float = 1e-5, impl: str = "auto", **kw):
    mode = _resolve(impl)
    if mode == "pallas":
        return _rms.rmsnorm(x, w, eps=eps, **kw)
    if mode == "interpret":
        return _rms.rmsnorm(x, w, eps=eps, interpret=True, **kw)
    return _ref.rmsnorm_ref(x, w, eps=eps)


def hash_partition_histogram(keys, *, num_buckets: int, impl: str = "auto", **kw):
    mode = _resolve(impl)
    if mode == "pallas":
        return _hp.hash_partition_histogram(keys, num_buckets=num_buckets, **kw)
    if mode == "interpret":
        return _hp.hash_partition_histogram(
            keys, num_buckets=num_buckets, interpret=True, **kw
        )
    # ref returns the global histogram; shape it like one block
    return _ref.hash_partition_histogram_ref(keys, num_buckets=num_buckets)[None]
