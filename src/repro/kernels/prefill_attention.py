"""Ragged cache-writing prefill attention — Pallas TPU kernels.

The prefill counterpart of ``decode_attention``: a ``[B, T]`` slab of
fresh prompt tokens (per-row ragged — row ``b`` carries ``chunk_lens[b]``
valid tokens, the rest right-padding) is appended into each row's KV
cache at its own ``base[b]`` offset and attended causally against the
full cached prefix ``[0, base[b] + chunk_lens[b])`` in one fused op.
``base`` is a *traced* per-row vector, so rows at different prefill
offsets batch into a single call — the property the serving engine's
chunked (Sarathi-style) prefill scheduler relies on: a long prompt is
prefilled in bounded chunks interleaved with decode steps, each chunk a
plain ``base += chunk`` continuation.

Two layouts, mirroring the decode kernels:

* ``prefill_attention`` — contiguous cache rows ``[B, S, KV, D]``.  The
  fresh K/V is scattered into the cache (writes past a row's
  ``chunk_lens`` drop, so padding never clobbers neighbouring state),
  then the kernel streams KV blocks with the per-row lengths riding in
  as scalar-prefetch operands: blocks past a row's causal frontier or
  past its query chunk are skipped (``pl.when``), the ragged tail block
  is masked at element granularity.
* ``prefill_attention_paged`` — the stacked page pools ``[layers,
  num_pages, page_size, KV, D]`` addressed by a layer index and per-row
  block tables: fresh K/V scatters into the layer's pages through the
  table (sentinel entries drop), and the kernel's K/V BlockSpec index
  maps gather the layer's physical page per (row, logical-page) grid
  step — the decode kernel's paged read, on the prefill side.  Pools
  wider than the heads (lane-aligned) take zero-padded rows.

Each grid program is one (batch row, query tile, KV span) and reads the
span's block over ALL KV heads, ``(1, block_k, KV, D)`` — the layout
Mosaic tiles without a transpose (see ``decode_attention``) — then loops
over the query heads inside the kernel with per-head running softmax
state in VMEM scratch.

Outputs at padding query rows (``i >= chunk_lens[b]``) are exact zeros
in both the kernels and the jnp oracles, so parity tests compare full
tensors.  Queries attend nothing outside ``kpos <= base + i`` — for a
valid query that is exactly the row's live prefix, so no per-element
length mask beyond causality is needed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (KV_BLOCK_VMEM_BYTES, NEG_INF,
                                            kv_block, kv_row_bytes,
                                            pad_lanes, past_seq_to_zero)


def write_chunk(cache: jnp.ndarray, new: jnp.ndarray, base: jnp.ndarray,
                chunk_lens: jnp.ndarray) -> jnp.ndarray:
    """Scatter ``new [B, T, ...]`` into ``cache [B, S, ...]`` at per-row
    offsets ``base [B]``; positions at or past ``chunk_lens[b]`` drop."""
    B, T = new.shape[0], new.shape[1]
    S = cache.shape[1]
    j = jnp.arange(T)[None, :]
    pos = jnp.where(j < chunk_lens[:, None], base[:, None] + j, S)
    rows = jnp.broadcast_to(jnp.arange(B)[:, None], (B, T))
    return cache.at[rows, pos].set(new.astype(cache.dtype), mode="drop")


def write_chunk_paged(pages: jnp.ndarray, block_table: jnp.ndarray,
                      new: jnp.ndarray, base: jnp.ndarray,
                      chunk_lens: jnp.ndarray, layer) -> jnp.ndarray:
    """Scatter ``new [B, T, ...]`` through per-row block tables into layer
    ``layer`` of the stacked page pools ``[layers, num_pages, page_size,
    ...]``, in place, zero-padded to the pools' width.  Unallocated
    logical pages hit the sentinel (>= num_pages) and the write drops, as
    do padding positions."""
    num_pages, page_size = pages.shape[1], pages.shape[2]
    B, T = new.shape[0], new.shape[1]
    max_pages = block_table.shape[1]
    j = jnp.arange(T)[None, :]
    pos = base[:, None] + j
    lp = pos // page_size
    off = pos % page_size
    rows = jnp.arange(B)[:, None]
    phys = jnp.where(
        (j < chunk_lens[:, None]) & (lp < max_pages),
        block_table[rows, jnp.minimum(lp, max_pages - 1)],
        num_pages,
    )
    return pages.at[layer, phys, off].set(
        pad_lanes(new, pages.shape[-1]).astype(pages.dtype), mode="drop")


def _pf_kernel(base_ref, clen_ref, q_ref, k_ref, v_ref, o_ref,
               m_scr, l_scr, acc_scr, *,
               scale: float, block_q: int, block_k: int, seq: int = 0):
    """q/o [1, H, bq, D] (head-major), k/v [1, bk, KV, D]; scratch holds
    each head's running (max, denom, acc) across the KV-span axis.
    ``seq`` is the cache length when the last span runs past it, else 0."""
    b = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    ns = pl.num_programs(2)
    base = base_ref[b]
    clen = clen_ref[b]
    H, KV = q_ref.shape[1], k_ref.shape[2]
    G = H // KV

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    lo = kj * block_k
    # skip: KV blocks wholly past the tile's causal frontier, and query
    # tiles wholly past the row's ragged chunk length
    live = jnp.logical_and(lo <= base + (qi + 1) * block_q - 1,
                           qi * block_q < clen)

    @pl.when(live)
    def _compute():
        qpos = base + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = lo + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = kpos <= qpos
        for kv in range(KV):  # static head index: a strided VMEM read
            k = k_ref[0, :, kv, :].astype(jnp.float32)    # [bk, D]
            v = v_ref[0, :, kv, :].astype(jnp.float32)
            if seq % block_k:
                v = past_seq_to_zero(v, lo, seq)
            for h in range(kv * G, (kv + 1) * G):
                q = q_ref[0, h].astype(jnp.float32)       # [bq, D]
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale                                 # [bq, bk]
                s = jnp.where(mask, s, NEG_INF)
                m_prev = m_scr[h]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1,
                                                    keepdims=True))
                # a fully-masked row (padding query) must contribute l = 0
                p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
                alpha = jnp.exp(m_prev - m_new)
                l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=-1,
                                                      keepdims=True)
                acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                m_scr[h] = m_new

    @pl.when(kj == ns - 1)
    def _finalize():
        row = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, acc_scr.shape[1:], 0)
        for h in range(H):
            out = acc_scr[h] / jnp.maximum(l_scr[h], 1e-30)
            # padding query rows are exact zeros (oracle parity)
            o_ref[0, h] = jnp.where(row < clen, out, 0.0).astype(o_ref.dtype)


def _q_tile(T: int, block_q: int, H: int, D: int, kv_bytes: int) -> int:
    """Query rows per program: at most ``block_q``, shrunk while q, o (both
    double-buffered, counted at 4 bytes) and the f32 scratch — whose
    ``[bq, 1]`` max/denom columns pad to 128 lanes — overflow the VMEM the
    K/V blocks leave free."""
    block_q = max(min(block_q, T), 1)
    lanes = -(-D // 128) * 128
    while block_q > 8 and (
            kv_bytes + H * block_q * (5 * lanes + 2 * 128) * 4
            > 2 * KV_BLOCK_VMEM_BYTES):
        block_q //= 2
    return block_q


def _call(kernel, q, base, clens, kv_operands, kv_spec, ns: int,
          prefetch, block_q: int, block_k: int, interpret: bool,
          scale: float | None = None):
    """Shared pallas_call of both layouts: grid (B, q tiles, KV spans).
    ``scale`` defaults to ``1/sqrt(D)`` of ``q``'s own width."""
    B, T, H, D = q.shape
    KV = kv_operands[0].shape[-2]
    kv_bytes = 4 * block_k * kv_row_bytes(KV, D, kv_operands[0].dtype)
    block_q = _q_tile(T, block_q, H, D, kv_bytes)
    Tp = pl.cdiv(T, block_q) * block_q
    q_r = q.transpose(0, 2, 1, 3)                      # [B, H, T, D]
    if Tp != T:
        q_r = jnp.pad(q_r, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    q_idx = lambda b, qi, kj, *_: (b, 0, qi, 0)       # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 + len(prefetch),
        grid=(B, Tp // block_q, ns),
        in_specs=[pl.BlockSpec((1, H, block_q, D), q_idx), kv_spec, kv_spec],
        out_specs=pl.BlockSpec((1, H, block_q, D), q_idx),
        scratch_shapes=[
            pltpu.VMEM((H, block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((H, block_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((H, block_q, D), jnp.float32),   # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(kernel,
                          scale=scale or 1.0 / math.sqrt(D),
                          block_q=block_q, block_k=block_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Tp, D), q.dtype),
        interpret=interpret,
    )(base, clens, *prefetch, q_r, *kv_operands)
    return out[:, :, :T].transpose(0, 2, 1, 3)


def _vec(x, B):
    return jnp.broadcast_to(jnp.asarray(x, jnp.int32).reshape(-1), (B,))


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_k", "interpret")
)
def prefill_attention(
    q: jnp.ndarray,          # [B, T, H, D]   fresh-chunk queries
    k_new: jnp.ndarray,      # [B, T, KV, D]  fresh K/V to append
    v_new: jnp.ndarray,
    k_cache: jnp.ndarray,    # [B, S, KV, D]  cache-native layout
    v_cache: jnp.ndarray,
    base: jnp.ndarray,       # [] or [B] int32: cached prefix per row
    chunk_lens: jnp.ndarray,  # [] or [B] int32: valid tokens in the chunk
    *,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    """Returns ``(out [B, T, H, D], k_cache', v_cache')``."""
    B = q.shape[0]
    S, KV, D = k_cache.shape[1], k_cache.shape[2], k_cache.shape[3]
    base = _vec(base, B)
    clens = _vec(chunk_lens, B)
    kc = write_chunk(k_cache, k_new, base, clens)
    vc = write_chunk(v_cache, v_new, base, clens)
    block_k = kv_block(S, block_k, KV, D, kc.dtype)
    kv_spec = pl.BlockSpec((1, block_k, KV, D),
                           lambda b, qi, kj, br, cr: (b, kj, 0, 0))
    out = _call(functools.partial(_pf_kernel, seq=S), q, base, clens,
                (kc, vc), kv_spec, pl.cdiv(S, block_k), (), block_q, block_k,
                interpret)
    return out, kc, vc


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def prefill_attention_paged(
    q: jnp.ndarray,            # [B, T, H, D]
    k_new: jnp.ndarray,        # [B, T, KV, D]
    v_new: jnp.ndarray,
    k_pages: jnp.ndarray,      # [layers, num_pages, page_size, KV, D]
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, max_pages] int32 (sentinel >= num_pages)
    base: jnp.ndarray,         # [] or [B] int32
    chunk_lens: jnp.ndarray,   # [] or [B] int32
    layer: jnp.ndarray,        # [] int32: the layer written and read
    *,
    block_q: int = 128,
    interpret: bool = False,
):
    """Returns ``(out [B, T, H, D], k_pages', v_pages')``: the whole
    stacks, with the chunk written into layer ``layer``.  Pools wider
    than ``q`` (lane-aligned) read zero-padded queries and rows."""
    B, D = q.shape[0], q.shape[-1]
    _, num_pages, page_size, KV, Dp = k_pages.shape
    base = _vec(base, B)
    clens = _vec(chunk_lens, B)
    kp = write_chunk_paged(k_pages, block_table, k_new, base, clens, layer)
    vp = write_chunk_paged(v_pages, block_table, v_new, base, clens, layer)

    # clamp sentinels in-range: they only address positions at or past a
    # row's live prefix, which the causal mask / block skip discards
    bt = jnp.clip(block_table.astype(jnp.int32), 0, num_pages - 1)

    def page_map(b, qi, kj, br, cr, layer_ref, btr):
        return (layer_ref[0], btr[b, kj], 0, 0, 0)

    def paged_kernel(base_ref, clen_ref, layer_ref, bt_ref, *rest, **kw):
        # layer_ref and bt_ref are consumed by the BlockSpec index maps
        # above; the body only needs the per-row base/chunk lengths
        del layer_ref, bt_ref
        _pf_kernel(base_ref, clen_ref, *rest, **kw)

    out = _call(paged_kernel, pad_lanes(q, Dp), base, clens, (kp, vp),
                pl.BlockSpec((None, 1, page_size, KV, Dp), page_map),
                block_table.shape[1],
                (jnp.asarray(layer, jnp.int32).reshape(1), bt), block_q,
                page_size, interpret, scale=1.0 / math.sqrt(D))
    return out[..., :D], kp, vp
