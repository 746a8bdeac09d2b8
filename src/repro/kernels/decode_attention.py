"""Flash-decoding (split-K) GQA decode attention — Pallas TPU kernels.

FlashDecoding (arXiv:2311.01282) splits the KV cache across the grid so a
single query token saturates the chip: each program reduces one KV span
into a partial (max, denom, weighted-V) triple; a cheap jnp combine merges
the partials.  GPU→TPU adaptation: per-SM split-K becomes grid programs
over VMEM-resident cache tiles; the GQA head group is packed into one MXU
matmul ([G, D] x [D, block_k]) instead of warp-level broadcast.

Each grid program is one (batch row, KV span) pair and reads the span's
block over ALL KV heads, ``(1, block_k, KV, D)``, then loops over the
heads inside the kernel.  Mosaic tiles the last two block dims in
(sublane, lane) units and accepts a block only when each of them is a
multiple of (8, 128) or spans the whole array dim; ``(KV, D)`` spans both,
so the cache-native ``[.., S, KV, D]`` layout needs no transpose.  The
span length is picked from the shapes (``kv_block``) so the
double-buffered K and V blocks fit the default scoped VMEM; when it does
not divide the cache length, the last span runs past the array and its
tail rows are masked like any position past ``cache_len``.

Two layouts, one kernel family:

* ``decode_attention`` — contiguous cache rows ``[B, S, KV, D]`` (the
  model-native slot-cache layout, so the hot path never transposes).
  ``cache_len`` may be a scalar or a per-row ``[B]`` vector (continuous
  batching: every slot is at a different point in its sequence).  Lengths
  ride in as scalar-prefetch operands, masking happens at K-block
  granularity inside the kernel, and split-K blocks entirely past a row's
  valid prefix (or entirely before its attention window) are skipped —
  the skipped program writes neutral partials the combine ignores.
* ``decode_attention_paged`` — a shared page pool ``[num_pages,
  page_size, KV, D]`` addressed through a per-row block table
  ``[B, max_pages]``: the block table is a scalar-prefetch operand and the
  K/V BlockSpec index maps *gather the physical page* for each (row,
  logical-page) grid step, so one sequence's KV need not be contiguous in
  memory (vLLM-style PagedAttention, arXiv:2309.06180).  Out-of-range
  table entries (free slots use a sentinel) are clamped — they can only
  map to blocks past the row's length, which the mask discards.

Both take a static ``window`` (0 = full attention): positions outside
``[cache_len - window, cache_len)`` are masked by the same per-row length
logic.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# bytes of double-buffered K and V blocks one program may hold in VMEM:
# half of the 16 MiB default scoped limit, leaving room for q, the
# outputs and (prefill) the per-head accumulators
KV_BLOCK_VMEM_BYTES = 8 * 1024 * 1024


def kv_row_bytes(kv: int, d: int, dtype) -> int:
    """VMEM bytes of one cache position ``[KV, D]`` once Mosaic tiles it:
    sublanes pad to 8 rows of 32-bit words (16 for bf16), lanes to 128."""
    itemsize = jnp.dtype(dtype).itemsize
    sub = 8 * max(4 // itemsize, 1)
    return -(-kv // sub) * sub * -(-d // 128) * 128 * itemsize


def kv_block(seq: int, block_k: int, kv: int, d: int, dtype) -> int:
    """KV span per grid program: at most ``block_k`` and ``seq``, and
    small enough that K and V double-buffered fit ``KV_BLOCK_VMEM_BYTES``.
    The grid takes ``cdiv(seq, span)`` spans."""
    block_k = max(min(block_k, seq), 1)
    row = kv_row_bytes(kv, d, dtype)
    while block_k > 8 and 4 * block_k * row > KV_BLOCK_VMEM_BYTES:
        block_k //= 2
    return block_k


def past_seq_to_zero(v, lo, seq: int):
    """Zero the rows of a ``[bk, D]`` V span at positions ``>= seq``.  A
    span that runs past the cache reads undefined memory there; masked
    scores give those rows weight 0, but 0 * NaN is still NaN."""
    pos = lo + jax.lax.broadcasted_iota(jnp.int32, (v.shape[0], 1), 0)
    return jnp.where(pos < seq, v, 0.0)


def _partial_softmax(q, k, v, kpos, cache_len, scale: float, window: int):
    """One split-K partial: q [G,D], k/v [bk,D], kpos [G,bk] int32 ->
    (m [G,1], l [G,1], acc [G,D]) fp32."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                      # [G, bk]
    mask = kpos < cache_len
    if window:
        mask &= kpos >= cache_len - window
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)         # [G, 1]
    p = jnp.exp(s - m)
    # a fully-masked block (all NEG_INF) must contribute l = 0, not bk:
    # exp(NEG_INF - NEG_INF) = 1 per position would poison the denominator
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                              # [G, D]
    return m, l, acc


def _dec_kernel(len_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, *,
                scale: float, block_k: int, window: int, seq: int = 0):
    """One (row, KV span) program over every KV head: q [1, KV, G, D],
    k/v [1, block_k, KV, D] -> partials [1, 1, KV, G, {1, 1, D}].
    ``seq`` is the cache length when the last span runs past it, else 0."""
    b = pl.program_id(0)
    sj = pl.program_id(1)
    cache_len = len_ref[b]
    lo = sj * block_k
    live = lo < cache_len
    if window:
        live = jnp.logical_and(live, lo + block_k > cache_len - window)
    kv, g = q_ref.shape[1], q_ref.shape[2]

    @pl.when(live)
    def _compute():
        kpos = lo + jax.lax.broadcasted_iota(jnp.int32, (g, block_k), 1)
        for h in range(kv):  # static head index: a strided VMEM read
            q = q_ref[0, h].astype(jnp.float32)            # [G, D]
            k = k_ref[0, :, h, :].astype(jnp.float32)      # [bk, D]
            v = v_ref[0, :, h, :].astype(jnp.float32)
            if seq % block_k:
                v = past_seq_to_zero(v, lo, seq)
            m, l, acc = _partial_softmax(q, k, v, kpos, cache_len, scale,
                                         window)
            m_ref[0, 0, h] = m
            l_ref[0, 0, h] = l
            acc_ref[0, 0, h] = acc

    @pl.when(jnp.logical_not(live))
    def _skip():  # span entirely outside the valid prefix (or window)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)


def _split_k_call(kernel, q, cache_len, kv_operands, kv_spec, ns: int,
                  prefetch, interpret: bool):
    """Shared pallas_call of both layouts: grid (B, ns), one program per
    (row, KV span); partials combined in jnp into [B, H, D]."""
    B, H, D = q.shape
    KV = kv_operands[0].shape[-2]
    G = H // KV
    q_r = q.reshape(B, KV, G, D)
    lens = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (B,))
    idx = lambda b, sj, *_: (b, 0, 0, 0)              # noqa: E731
    out_idx = lambda b, sj, *_: (b, sj, 0, 0, 0)      # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1 + len(prefetch),
        grid=(B, ns),
        in_specs=[pl.BlockSpec((1, KV, G, D), idx), kv_spec, kv_spec],
        out_specs=[
            pl.BlockSpec((1, 1, KV, G, 1), out_idx),
            pl.BlockSpec((1, 1, KV, G, 1), out_idx),
            pl.BlockSpec((1, 1, KV, G, D), out_idx),
        ],
    )
    m_p, l_p, acc_p = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, ns, KV, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, ns, KV, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, ns, KV, G, D), jnp.float32),
        ],
        interpret=interpret,
    )(lens, *prefetch, q_r, *kv_operands)
    # merge the split-K partials
    m_all = jnp.max(m_p, axis=1, keepdims=True)
    w = jnp.exp(m_p - m_all)
    l_tot = jnp.sum(l_p * w, axis=1)
    acc = jnp.sum(acc_p * w, axis=1)
    out = acc / jnp.maximum(l_tot, 1e-30)
    return out.reshape(B, H, D).astype(q.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "block_k", "interpret")
)
def decode_attention(
    q: jnp.ndarray,          # [B, H, D]
    k: jnp.ndarray,          # [B, S, KV, D]  (cache-native layout)
    v: jnp.ndarray,
    cache_len: jnp.ndarray,  # [] or [B] int32
    *,
    window: int = 0,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    S, KV, D = k.shape[1], k.shape[2], k.shape[3]
    block_k = kv_block(S, block_k, KV, D, k.dtype)
    kv_spec = pl.BlockSpec((1, block_k, KV, D),
                           lambda b, sj, lr: (b, sj, 0, 0))
    kernel = functools.partial(_dec_kernel, scale=1.0 / math.sqrt(D),
                               block_k=block_k, window=window, seq=S)
    return _split_k_call(kernel, q, cache_len, (k, v), kv_spec,
                         pl.cdiv(S, block_k), (), interpret)


@functools.partial(
    jax.jit, static_argnames=("window", "interpret")
)
def decode_attention_paged(
    q: jnp.ndarray,            # [B, H, D]
    k_pages: jnp.ndarray,      # [num_pages, page_size, KV, D]  shared pool
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, max_pages] int32 (sentinel >= num_pages
    cache_len: jnp.ndarray,    #   marks unallocated logical pages)
    *,
    window: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    num_pages, page_size, KV, D = k_pages.shape
    # clamp sentinel entries in-range: they only ever address positions at
    # or past cache_len, which the in-kernel mask discards
    bt = jnp.clip(block_table.astype(jnp.int32), 0, num_pages - 1)

    def page_map(b, sj, lr, btr):
        # gather the physical page through the block table (the paged read)
        return (btr[b, sj], 0, 0, 0)

    def kernel(len_ref, bt_ref, *rest):
        del bt_ref  # consumed by the index maps
        _dec_kernel(len_ref, *rest, scale=1.0 / math.sqrt(D),
                    block_k=page_size, window=window)

    return _split_k_call(kernel, q, cache_len, (k_pages, v_pages),
                         pl.BlockSpec((1, page_size, KV, D), page_map),
                         block_table.shape[1], (bt,), interpret)
