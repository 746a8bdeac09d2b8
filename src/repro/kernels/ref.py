"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth
the per-kernel shape/dtype sweeps assert against)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True) -> jnp.ndarray:
    """q [B,H,S,D]; k,v [B,KV,S,D] -> [B,H,S,D]; naive full-softmax."""
    B, H, S, D = q.shape
    KV = k.shape[1]
    G = H // KV
    kf = jnp.repeat(k, G, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v, G, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kf) / math.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vf).astype(q.dtype)


def decode_attention_ref(q, k, v, cache_len, *, window: int = 0) -> jnp.ndarray:
    """q [B,H,D]; k,v [B,S,KV,D] (cache-native) -> [B,H,D].

    ``cache_len`` is [] or [B] int32 — a [B] vector gives each batch row
    its own valid prefix (the continuous-batching slot cache).  ``window``
    > 0 additionally masks positions before ``cache_len - window``.

    The cache is sequence-sharded over the model axis (flash-decoding
    style); the contraction over S becomes a partial-softmax + psum under
    GSPMD — the sharding constraint keeps the GQA-repeated heads on the
    model axis instead of replicated.
    """
    from repro.distributed.sharding import constrain

    B, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    cache_axes = ("cache_batch", "cache_seq", None, None)
    kf = k if KV == H else constrain(jnp.repeat(k, G, axis=2), cache_axes)
    vf = v if KV == H else constrain(jnp.repeat(v, G, axis=2), cache_axes)
    s = jnp.einsum(
        "bhd,bshd->bhs", q.astype(jnp.float32), kf.astype(jnp.float32)
    ) / math.sqrt(D)
    cl = jnp.asarray(cache_len)
    if cl.ndim == 1:
        cl = cl[:, None, None]  # per-row lengths broadcast over [B,H,S]
    pos = jnp.arange(S)[None, None, :]
    mask = pos < cl
    if window:
        mask &= pos >= cl - window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhs,bshd->bhd", p, vf.astype(jnp.float32)).astype(q.dtype)


def decode_attention_paged_ref(q, k_pages, v_pages, block_table, cache_len,
                               layer, *, window: int = 0) -> jnp.ndarray:
    """Paged oracle: gather each row's pages of layer ``layer`` of the
    stacked pools ``[layers, num_pages, page_size, KV, D]`` through its
    block table into a contiguous [B, max_pages*page_size, KV, D] view,
    then run the masked reference on ``q``'s width of the pools' head dim
    (the rest is lane padding).  Sentinel (out-of-range) table entries
    are clamped — they only address positions past ``cache_len``, which
    the mask discards."""
    _, num_pages, page_size, KV, _ = k_pages.shape
    D = q.shape[-1]
    B, max_pages = block_table.shape
    bt = jnp.clip(block_table.astype(jnp.int32), 0, num_pages - 1)
    k = k_pages[layer, bt, ..., :D].reshape(B, max_pages * page_size, KV, D)
    v = v_pages[layer, bt, ..., :D].reshape(B, max_pages * page_size, KV, D)
    return decode_attention_ref(q, k, v, cache_len, window=window)


def prefill_attention_ref(q, k_new, v_new, k_cache, v_cache, base,
                          chunk_lens):
    """Ragged cache-writing prefill oracle, contiguous layout.

    q [B,T,H,D]; k_new, v_new [B,T,KV,D]; k_cache, v_cache [B,S,KV,D];
    base, chunk_lens [] or [B] int32.  Row ``b``'s first ``chunk_lens[b]``
    chunk tokens are appended at offset ``base[b]`` and each valid query
    ``i`` attends causally over ``[0, base[b] + i]``; padding query rows
    produce exact zeros.  Returns ``(out [B,T,H,D], k_cache', v_cache')``.
    """
    from repro.kernels.prefill_attention import write_chunk

    B = q.shape[0]
    base = jnp.broadcast_to(jnp.asarray(base, jnp.int32).reshape(-1), (B,))
    clens = jnp.broadcast_to(
        jnp.asarray(chunk_lens, jnp.int32).reshape(-1), (B,))
    kc = write_chunk(k_cache, k_new, base, clens)
    vc = write_chunk(v_cache, v_new, base, clens)
    return prefill_attend_ref(q, kc, vc, base, clens), kc, vc


def prefill_attend_ref(q, kc, vc, base, clens):
    """Masked causal attention of a [B,T] chunk over a contiguous
    [B,S,KV,D] cache at per-row offsets; padding rows exact zero."""
    T, H, D = q.shape[1], q.shape[2], q.shape[3]
    S, KV = kc.shape[1], kc.shape[2]
    G = H // KV
    kf = jnp.repeat(kc, G, axis=2).astype(jnp.float32)  # [B,S,H,D]
    vf = jnp.repeat(vc, G, axis=2).astype(jnp.float32)
    s = jnp.einsum(
        "bthd,bshd->bhts", q.astype(jnp.float32), kf) / math.sqrt(D)
    qpos = base[:, None] + jnp.arange(T)[None, :]          # [B,T]
    mask = jnp.arange(S)[None, None, :] <= qpos[:, :, None]  # [B,T,S]
    s = jnp.where(mask[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", p, vf)
    valid = jnp.arange(T)[None, :] < clens[:, None]        # [B,T]
    out = jnp.where(valid[:, :, None, None], out, 0.0)
    return out.astype(q.dtype)


def prefill_attention_paged_ref(q, k_new, v_new, k_pages, v_pages,
                                block_table, base, chunk_lens, layer):
    """Paged prefill oracle: write the chunk into layer ``layer`` of the
    stacked pools through the block tables, gather each row's pages of
    that layer into a contiguous view (``q``'s width of the head dim),
    and reuse the contiguous oracle's attention.  Returns ``(out
    [B,T,H,D], k_pages', v_pages')``."""
    from repro.kernels.prefill_attention import write_chunk_paged

    _, num_pages, page_size, KV, _ = k_pages.shape
    D = q.shape[-1]
    B, max_pages = block_table.shape
    base = jnp.broadcast_to(jnp.asarray(base, jnp.int32).reshape(-1), (B,))
    clens = jnp.broadcast_to(
        jnp.asarray(chunk_lens, jnp.int32).reshape(-1), (B,))
    kp = write_chunk_paged(k_pages, block_table, k_new, base, clens, layer)
    vp = write_chunk_paged(v_pages, block_table, v_new, base, clens, layer)
    bt = jnp.clip(block_table.astype(jnp.int32), 0, num_pages - 1)
    k = kp[layer, bt, ..., :D].reshape(B, max_pages * page_size, KV, D)
    v = vp[layer, bt, ..., :D].reshape(B, max_pages * page_size, KV, D)
    return prefill_attend_ref(q, k, v, base, clens), kp, vp


def rmsnorm_ref(x, w, *, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def hash_u32_ref(keys):
    k = keys.astype(jnp.uint32)
    h = k * jnp.uint32(2654435761)
    return h ^ (h >> 16)


def hash_partition_histogram_ref(keys, *, num_buckets: int) -> jnp.ndarray:
    """Global histogram [num_buckets] (per-block results sum to this)."""
    bucket = (hash_u32_ref(keys) % jnp.uint32(num_buckets)).astype(jnp.int32)
    return jnp.zeros((num_buckets,), jnp.int32).at[bucket].add(1)
