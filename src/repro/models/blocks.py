"""Transformer building blocks: norms, RoPE/M-RoPE, GQA/MLA attention
(chunked flash-style reference path), SwiGLU/GeGLU/GELU MLPs, GShard-style
MoE (einsum dispatch baseline + gather-dispatch optimized variant).

Every block is a pair of functions:

* ``<kind>_specs(cfg) -> PyTree[Param]`` — parameter declaration with
  logical sharding axes;
* ``<kind>_apply(cfg, params, x, ...) -> y`` — pure forward.

Attention convention: activations are [batch, seq, ...]; caches are dicts.
Compute runs in ``cfg.compute_dtype`` (bf16); norms/softmax accumulate fp32.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name

from repro.common.params import Param
from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_specs(d: int) -> Dict[str, Param]:
    return {"scale": Param((d,), (None,), init="ones")}


def rmsnorm_apply(params, x, eps: float = 1e-5):
    """f32 variance reduction, input-dtype scaling multiply (H5 in
    EXPERIMENTS §Perf: upcasting the whole tensor doubled fwd+bwd HBM
    traffic; the reduction accumulates f32 inside the fused reduce)."""
    var = jnp.mean(
        jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True
    )
    scale = (jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return x * scale * params["scale"].astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------


def _rope_freqs(dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [B, S, H, D]; positions: [B, S] int32."""
    d = x.shape[-1]
    freqs = _rope_freqs(d, theta)  # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float, sections: Tuple[int, ...]
) -> jnp.ndarray:
    """Qwen2-VL multimodal RoPE. positions: [B, S, 3] (t, h, w); ``sections``
    splits the D/2 rotary frequencies into (t, h, w) groups."""
    d = x.shape[-1]
    freqs = _rope_freqs(d, theta)  # [D/2]
    # angles per modality then stitched along the frequency dim
    ang = positions[..., None, :].astype(jnp.float32)  # [B,S,1,3]
    ang = ang * freqs[None, None, :, None]  # [B,S,D/2,3]
    sec_idx = []
    for i, s in enumerate(sections):
        sec_idx += [i] * s
    sec_idx = jnp.asarray(sec_idx[: d // 2], dtype=jnp.int32)
    angles = jnp.take_along_axis(
        ang, sec_idx[None, None, :, None].astype(jnp.int32), axis=-1
    )[..., 0]  # [B,S,D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Flash-style chunked attention (jnp reference path — differentiable, O(chunk)
# memory; the Pallas kernel in repro.kernels is the TPU fast path).
# ---------------------------------------------------------------------------

_NEG_INF = -1e30


def _check_prefill_base(raw_len) -> None:
    """S>1 prefill attends over the fresh K/V only, which is exact iff the
    cache is empty — a nonzero base would silently drop the cached prefix
    from attention.  The base must therefore be *statically* zero: pass a
    plain Python ``0`` (a traced/data-dependent length cannot be validated
    at trace time and is rejected)."""
    if getattr(raw_len, "ndim", 0) != 0:
        raise ValueError(
            "prefill (S>1) requires a scalar cache length; per-slot "
            "lengths only apply to single-token decode")
    try:
        concrete = int(raw_len)  # jit-ok: deliberate trace-time probe
    except (TypeError, jax.errors.ConcretizationTypeError) as e:
        # traced / data-dependent value: int() on a tracer raises
        # ConcretizationTypeError (a TypeError subclass)
        raise NotImplementedError(
            "prefill (S>1) needs a statically-zero cache length (pass a "
            "plain int 0): attention runs over the fresh K/V only, so "
            "appending at a data-dependent offset would silently ignore "
            "the cached prefix") from e
    if concrete != 0:
        raise NotImplementedError(
            f"prefill (S>1) writes into an EMPTY cache (got base length "
            f"{concrete}); chunked/multi-turn prefill over a warm cache is "
            f"not implemented")


def _attn_chunk(q, k, v, qpos, kpos, causal, window, scale):
    """One (q-chunk x kv-chunk) tile. q:[B,qc,H,D] k,v:[B,kc,H,D]."""
    s = jnp.einsum(
        "bqhd,bchd->bhqc", q, k, preferred_element_type=jnp.float32
    ) * scale  # [B,H,qc,kc]
    mask = jnp.ones((q.shape[1], k.shape[1]), dtype=bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window:
        mask &= (qpos[:, None] - kpos[None, :]) < window
    s = jnp.where(mask[None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1)  # [B,H,qc]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqc,bchd->bqhd", p.astype(v.dtype), v)
    return m, l, o.astype(jnp.float32)


def _repeat_kv(k: jnp.ndarray, H: int, seq_axes=("act_batch", None, "act_heads", None)):
    """[B,S,KV,D] -> [B,S,H,D] (GQA repeat), sharding-constrained so the
    repeated heads land on the model axis instead of being replicated."""
    from repro.distributed.sharding import constrain

    KV = k.shape[2]
    if KV == H:
        return k
    k = jnp.repeat(k, H // KV, axis=2)
    return constrain(k, seq_axes)


def chunked_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    q_offset: int = 0,
) -> jnp.ndarray:
    """q: [B,Sq,H,D]; k, v: [B,Skv,KV,D] -> [B,Sq,H,D].

    Outer loop over q chunks is a *python* loop (static), so causal chunks
    only visit the KV prefix they can see — the compiled FLOPs follow the
    causal triangle instead of the full rectangle.  Inner loop is a
    ``lax.scan`` over kv chunks with running-softmax accumulators.
    """
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    # GQA repeat happens per KV tile inside the scan (H4 in EXPERIMENTS
    # §Perf): repeating the full sequence up-front writes + reads G x the
    # whole K/V — per-tile repeat touches only the live block.
    per_tile_repeat = KV != H
    scale = 1.0 / math.sqrt(D)
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, k.shape[1])
    nq = (Sq + q_chunk - 1) // q_chunk
    qg = q

    outs = []
    for qi in range(nq):
        q_lo = qi * q_chunk
        qc = min(q_chunk, Sq - q_lo)
        qblk = jax.lax.slice_in_dim(qg, q_lo, q_lo + qc, axis=1)
        qpos = q_offset + q_lo + jnp.arange(qc)
        # visible kv range for this q chunk (static)
        hi = k.shape[1] if not causal else q_offset + q_lo + qc
        hi = min(hi, k.shape[1])
        lo = 0
        if window:
            lo = max(0, q_offset + q_lo - window + 1)
            lo = (lo // kv_chunk) * kv_chunk  # align
        hi_pad = ((hi - lo + kv_chunk - 1) // kv_chunk) * kv_chunk + lo
        hi_pad = min(hi_pad, k.shape[1])
        nkv = max((hi_pad - lo + kv_chunk - 1) // kv_chunk, 1)

        def kv_body(carry, j):
            m_prev, l_prev, o_prev = carry
            k_lo = lo + j * kv_chunk
            kblk = jax.lax.dynamic_slice_in_dim(k, k_lo, kv_chunk, axis=1)
            vblk = jax.lax.dynamic_slice_in_dim(v, k_lo, kv_chunk, axis=1)
            if per_tile_repeat:
                kblk = _repeat_kv(kblk, H)
                vblk = _repeat_kv(vblk, H)
            kpos = k_lo + jnp.arange(kv_chunk)
            m_new, l_new, o_new = _attn_chunk(
                qblk, kblk, vblk, qpos, kpos, causal, window, scale
            )
            m_run = jnp.maximum(m_prev, m_new)
            a = jnp.exp(m_prev - m_run)  # [B,H,qc]
            b = jnp.exp(m_new - m_run)
            l_run = l_prev * a + l_new * b
            o_run = o_prev * a.transpose(0, 2, 1)[..., None] + (
                o_new * b.transpose(0, 2, 1)[..., None]
            )
            return (m_run, l_run, o_run), None

        m0 = jnp.full((B, H, qc), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, qc), jnp.float32)
        o0 = jnp.zeros((B, qc, H, D), jnp.float32)
        # flash-style bwd: recompute score tiles instead of stacking them as
        # scan residuals (H6 in EXPERIMENTS §Perf — trades ~25% extra attn
        # FLOPs in bwd for O(S^2/chunk) saved HBM)
        (mF, lF, oF), _ = jax.lax.scan(
            jax.checkpoint(kv_body), (m0, l0, o0), jnp.arange(nkv), length=nkv
        )
        lF = jnp.maximum(lF, 1e-30)
        out = oF / lF.transpose(0, 2, 1)[..., None]
        outs.append(out)
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return out.astype(q.dtype)


def _decode_attn(cfg, q, k_cache, v_cache, cache_len, *, window: int = 0):
    """Single-step decode through the kernel dispatch (kernels/ops.py):
    under the default ``cfg.decode_impl="auto"``, Pallas flash-decode on
    TPU and the GSPMD-sharded jnp oracle elsewhere (``"interpret"`` runs
    the kernel in interpret mode).

    q: [B,1,H,D]; caches: [B,Smax,KV,D]; cache_len: [] or [B] int32 —
    number of valid positions (including current).  A [B] vector gives
    each batch row its own valid prefix — the continuous-batching slot
    cache, where every slot is at a different point in its sequence."""
    from repro.kernels import ops

    o = ops.decode_attention(q[:, 0], k_cache, v_cache, cache_len,
                             window=window, impl=cfg.decode_impl)
    return o[:, None].astype(q.dtype)


def _paged_decode_attn(cfg, q, k_pages, v_pages, block_table, cache_len):
    """Paged decode: K/V gathered from a shared page pool through the
    per-row block table (see kernels/decode_attention.py).  q: [B,1,H,D];
    pools: [num_pages, page_size, KV, D]; block_table: [B, max_pages]."""
    from repro.kernels import ops

    o = ops.decode_attention_paged(q[:, 0], k_pages, v_pages, block_table,
                                   cache_len, impl=cfg.decode_impl)
    return o[:, None].astype(q.dtype)


def _paged_append(pages, block_table, idx, row_vals):
    """Scatter one new position per row into the shared page pool.
    ``idx`` [B] is each row's append position; unallocated / out-of-range
    logical pages hit the sentinel (>= num_pages) and the write drops."""
    num_pages, page_size = pages.shape[0], pages.shape[1]
    max_pages = block_table.shape[1]
    rows = jnp.arange(block_table.shape[0])
    lp = idx // page_size
    off = idx % page_size
    phys = jnp.where(
        lp < max_pages,
        block_table[rows, jnp.minimum(lp, max_pages - 1)],
        num_pages,
    )
    return pages.at[phys, off].set(row_vals.astype(pages.dtype), mode="drop")


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------


def attn_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d, Dh = cfg.d_model, cfg.head_dim
    H, KV = cfg.padded_gqa()
    return {
        "norm": rmsnorm_specs(d),
        "wq": Param((d, H, Dh), ("embed", "heads", None)),
        "wk": Param((d, KV, Dh), ("embed", "kv_heads", None)),
        "wv": Param((d, KV, Dh), ("embed", "kv_heads", None)),
        "wo": Param((H, Dh, d), ("heads", None, "embed")),
    }


def _rope_or_mrope(cfg, x, positions):
    if cfg.mrope_sections:
        return apply_mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    if positions.ndim == 3:  # mrope positions given but plain rope cfg
        positions = positions[..., 0]
    return apply_rope(x, positions, cfg.rope_theta)


def attn_apply(
    cfg: ModelConfig,
    params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cache: Optional[Dict] = None,
    *,
    causal: bool = True,
    window: int = 0,
    kv_source: Optional[jnp.ndarray] = None,
    chunk_lens: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """GQA attention. ``cache`` (decode): {"k","v","len"}. ``kv_source``
    (cross-attention): encoder states.

    ``chunk_lens`` ([B] int32, S>1 + cache only) selects the ragged
    cache-writing prefill: row ``b``'s first ``chunk_lens[b]`` tokens of
    the [B, S] slab append into its cache at offset ``cache["len"][b]``
    and attend the full cached prefix — chunked / multi-turn prefill over
    a warm cache, on both KV layouts.  Without it, S>1 prefill keeps the
    legacy empty-cache fast path."""
    from repro.distributed.sharding import constrain

    cdt = cfg.compute_dtype
    with jax.named_scope("attn.qkv"):
        h = rmsnorm_apply(params["norm"], x, cfg.norm_eps).astype(cdt)
        src = h if kv_source is None else kv_source.astype(cdt)
        act_axes = ("act_batch", None, "act_heads", None)
        q = constrain(jnp.einsum("bsd,dhk->bshk", h,
                                 params["wq"].astype(cdt)), act_axes)
        k = jnp.einsum("bsd,dhk->bshk", src, params["wk"].astype(cdt))
        v = jnp.einsum("bsd,dhk->bshk", src, params["wv"].astype(cdt))
        is_self = kv_source is None
        if is_self and causal:
            q = _rope_or_mrope(cfg, q, positions)
            k = _rope_or_mrope(cfg, k, positions)
    new_cache = None
    if cache is not None and is_self and "k_pages" in cache:
        # paged decode (continuous batching): each row appends into its
        # block-table page at its own length, attention gathers K/V
        # through the table — no contiguous per-slot rows exist
        if window:
            raise NotImplementedError(
                "windowed attention over a paged cache needs ring-aware "
                "page recycling; the engine restricts paged serving to "
                "full-attention blocks")
        if k.shape[1] > 1:
            if chunk_lens is None:
                raise NotImplementedError(
                    "paged prefill without chunk_lens is not supported: "
                    "pass per-row chunk_lens to run the ragged "
                    "cache-writing prefill through the block tables")
            from repro.kernels import ops

            # the kernel writes the chunk into the pages itself
            with jax.named_scope("attn.core"):
                base = jnp.broadcast_to(
                    jnp.asarray(cache["len"], jnp.int32).reshape(-1),
                    (k.shape[0],))
                bt = cache["block_table"]
                o, k_pages, v_pages = ops.prefill_attention_paged(
                    q, k, v, cache["k_pages"], cache["v_pages"], bt, base,
                    chunk_lens, impl=cfg.decode_impl)
                new_cache = {"k_pages": k_pages, "v_pages": v_pages,
                             "block_table": bt, "len": base + chunk_lens}
        else:
            with jax.named_scope("attn.kv_append"):
                idx = jnp.asarray(cache["len"])
                bt = cache["block_table"]
                k_pages = _paged_append(cache["k_pages"], bt, idx, k[:, 0])
                v_pages = _paged_append(cache["v_pages"], bt, idx, v[:, 0])
                new_cache = {"k_pages": k_pages, "v_pages": v_pages,
                             "block_table": bt, "len": idx + 1}
            with jax.named_scope("attn.core"):
                o = _paged_decode_attn(cfg, q, k_pages, v_pages, bt, idx + 1)
    elif cache is not None and is_self:
        S = k.shape[1]
        slots_n = cache["k"].shape[1]
        if S > 1 and chunk_lens is not None:
            # ragged cache-writing prefill: append the chunk at each
            # row's own base offset and attend the full cached prefix
            # (kernels/prefill_attention.py via the ops dispatch)
            if window:
                raise NotImplementedError(
                    "windowed attention does not support chunked prefill "
                    "over a warm cache (ring writes need the full prompt)")
            from repro.kernels import ops

            # the kernel writes the chunk into the cache itself
            with jax.named_scope("attn.core"):
                base = jnp.broadcast_to(
                    jnp.asarray(cache["len"], jnp.int32).reshape(-1),
                    (k.shape[0],))
                o, k_cache, v_cache = ops.prefill_attention(
                    q, k, v, cache["k"], cache["v"], base, chunk_lens,
                    impl=cfg.decode_impl)
                new_cache = {"k": k_cache, "v": v_cache,
                             "len": base + chunk_lens}
        elif S > 1:
            # batched prefill: write the whole prompt's K/V into the cache
            # in one shot and run the causal flash pass over the fresh
            # K/V (exact because the cache is statically empty — enforced
            # BEFORE any array conversion, on the raw python length)
            _check_prefill_base(cache["len"])
            with jax.named_scope("attn.kv_append"):
                if window and S >= slots_n:
                    # ring cache: only the last `slots_n` positions
                    # survive, each at its position-mod-size slot
                    keep_k = k[:, S - slots_n:]
                    keep_v = v[:, S - slots_n:]
                    ring = (S - slots_n + jnp.arange(slots_n)) % slots_n
                    k_cache = cache["k"].at[:, ring].set(
                        keep_k.astype(cache["k"].dtype))
                    v_cache = cache["v"].at[:, ring].set(
                        keep_v.astype(cache["v"].dtype))
                else:
                    k_cache = jax.lax.dynamic_update_slice_in_dim(
                        cache["k"], k.astype(cache["k"].dtype), 0, axis=1)
                    v_cache = jax.lax.dynamic_update_slice_in_dim(
                        cache["v"], v.astype(cache["v"].dtype), 0, axis=1)
                new_cache = {"k": k_cache, "v": v_cache, "len": S}
            with jax.named_scope("attn.core"):
                o = chunked_attention(
                    q, k, v, causal=True, window=window,
                    q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                )
        elif jnp.asarray(cache["len"]).ndim == 1:
            # per-slot decode (continuous batching): each row appends at
            # its own length; rows past capacity are dropped, not wrapped
            with jax.named_scope("attn.kv_append"):
                idx = jnp.asarray(cache["len"])
                rows = jnp.arange(k.shape[0])
                slot = idx % slots_n if window else idx
                k_cache = cache["k"].at[rows, slot].set(
                    k[:, 0].astype(cache["k"].dtype), mode="drop")
                v_cache = cache["v"].at[rows, slot].set(
                    v[:, 0].astype(cache["v"].dtype), mode="drop")
                new_cache = {"k": k_cache, "v": v_cache, "len": idx + 1}
            with jax.named_scope("attn.core"):
                lens = jnp.minimum(idx + 1, slots_n) if window else idx + 1
                o = _decode_attn(cfg, q, k_cache, v_cache, lens)
        else:
            # decode: append to cache (ring-buffer for windowed attention)
            with jax.named_scope("attn.kv_append"):
                idx = cache["len"]
                slot = idx % slots_n if window else idx
                k_cache = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
                v_cache = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
                new_cache = {"k": k_cache, "v": v_cache, "len": idx + 1}
            with jax.named_scope("attn.core"):
                if window:
                    # ring buffer of exactly `window` slots: all valid
                    # once warm
                    o = _decode_attn(cfg, q, k_cache, v_cache,
                                     jnp.minimum(idx + 1, k_cache.shape[1]))
                else:
                    o = _decode_attn(cfg, q, k_cache, v_cache, idx + 1)
    elif cache is not None and not is_self:
        with jax.named_scope("attn.core"):
            o = _decode_attn(cfg, q, cache["xk"], cache["xv"],
                             jnp.asarray(cache["xlen"], jnp.int32))
        new_cache = cache
    else:
        with jax.named_scope("attn.core"):
            o = chunked_attention(
                q, k, v, causal=causal, window=window,
                q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
            )
    with jax.named_scope("attn.out"):
        y = jnp.einsum("bshk,hkd->bsd", o.astype(cdt),
                       params["wo"].astype(cdt))
        y = _checkpoint_name(y, "block_out")
        return x + y.astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, DeepSeek-V2 / MiniCPM3)
# ---------------------------------------------------------------------------


def mla_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d, H = cfg.d_model, cfg.num_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nd, rd, vd = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    H, _ = cfg.padded_gqa()
    specs: Dict[str, Any] = {
        "norm": rmsnorm_specs(d),
        "wkv_a": Param((d, kvr + rd), ("embed", None)),
        "kv_norm": rmsnorm_specs(kvr),
        "wk_b": Param((kvr, H, nd), ("kv_lora", "heads", None)),
        "wv_b": Param((kvr, H, vd), ("kv_lora", "heads", None)),
        "wo": Param((H, vd, d), ("heads", None, "embed")),
    }
    if qr > 0:
        specs["wq_a"] = Param((d, qr), ("embed", "q_lora"))
        specs["q_norm"] = rmsnorm_specs(qr)
        specs["wq_b"] = Param((qr, H, nd + rd), ("q_lora", "heads", None))
    else:
        specs["wq"] = Param((d, H, nd + rd), ("embed", "heads", None))
    return specs


def mla_apply(
    cfg: ModelConfig,
    params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cache: Optional[Dict] = None,
    *,
    chunk_lens: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    cdt = cfg.compute_dtype
    B, S, _ = x.shape
    H, _kv = cfg.padded_gqa()
    nd, rd, vd = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    with jax.named_scope("attn.qkv"):
        pos = positions if positions.ndim == 2 else positions[..., 0]
        h = rmsnorm_apply(params["norm"], x, cfg.norm_eps).astype(cdt)
        if cfg.q_lora_rank > 0:
            ql = jnp.einsum("bsd,dr->bsr", h, params["wq_a"].astype(cdt))
            ql = rmsnorm_apply(params["q_norm"], ql, cfg.norm_eps)
            q = jnp.einsum("bsr,rhk->bshk", ql, params["wq_b"].astype(cdt))
        else:
            q = jnp.einsum("bsd,dhk->bshk", h, params["wq"].astype(cdt))
        q_nope, q_pe = q[..., :nd], q[..., nd:]
        q_pe = apply_rope(q_pe, pos, cfg.rope_theta)
        kv = jnp.einsum("bsd,dr->bsr", h, params["wkv_a"].astype(cdt))
        c_kv, k_pe = kv[..., : cfg.kv_lora_rank], kv[..., cfg.kv_lora_rank:]
        c_kv = rmsnorm_apply(params["kv_norm"], c_kv, cfg.norm_eps)
        k_pe = apply_rope(k_pe[:, :, None, :], pos, cfg.rope_theta)  # [B,S,1,rd]

    new_cache = None
    if cache is not None and S > 1 and chunk_lens is not None:
        # ragged chunked prefill: append the latent chunk at each row's
        # own base offset (both layouts), then attend the full cached
        # latents with per-row causal masking — the latent-cache
        # counterpart of the GQA prefill kernel (latents are rank-sized,
        # so the masked dense expansion stays cheap)
        from repro.kernels.prefill_attention import (write_chunk,
                                                     write_chunk_paged)

        with jax.named_scope("attn.kv_append"):
            base = jnp.broadcast_to(
                jnp.asarray(cache["len"], jnp.int32).reshape(-1), (B,))
            if "ckv_pages" in cache:
                bt = cache["block_table"]
                ckv_pages = write_chunk_paged(
                    cache["ckv_pages"], bt, c_kv, base, chunk_lens)
                kpe_pages = write_chunk_paged(
                    cache["kpe_pages"], bt, k_pe[:, :, 0, :], base,
                    chunk_lens)
                new_cache = {"ckv_pages": ckv_pages, "kpe_pages": kpe_pages,
                             "block_table": bt, "len": base + chunk_lens}
            else:
                ckv_c = write_chunk(cache["c_kv"], c_kv, base, chunk_lens)
                kpe_c = write_chunk(cache["k_pe"], k_pe[:, :, 0, :], base,
                                    chunk_lens)
                new_cache = {"c_kv": ckv_c, "k_pe": kpe_c,
                             "len": base + chunk_lens}
        with jax.named_scope("attn.core"):
            if "ckv_pages" in cache:
                ckv_c, kpe_c = _mla_gather_pages(ckv_pages, kpe_pages, bt)
            o = _mla_ragged_prefill_attn(cfg, params, q_nope, q_pe, ckv_c,
                                         kpe_c, base, chunk_lens, cdt)
    elif cache is not None and S > 1:
        # batched prefill: write the latent K/V for the whole prompt, then
        # run the full-attention pass over the fresh latents (exact
        # because the cache is statically empty — enforced BEFORE any
        # array conversion, on the raw python length)
        _check_prefill_base(cache["len"])
        with jax.named_scope("attn.kv_append"):
            ckv_c = jax.lax.dynamic_update_slice_in_dim(
                cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), 0, axis=1)
            kpe_c = jax.lax.dynamic_update_slice_in_dim(
                cache["k_pe"], k_pe[:, :, 0, :].astype(cache["k_pe"].dtype),
                0, axis=1)
            new_cache = {"c_kv": ckv_c, "k_pe": kpe_c, "len": S}
        with jax.named_scope("attn.core"):
            o = _mla_full_attention(cfg, params, q_nope, q_pe, c_kv, k_pe,
                                    cdt)
    elif cache is not None and "ckv_pages" in cache:
        # paged decode: latents append into the shared page pool through
        # the block table, then gather (tiny: rank + rope dims only),
        # expand per head, and attend via the vector-length kernel
        if S > 1:
            raise NotImplementedError(
                "paged prefill is not supported: prefill writes a "
                "contiguous scratch cache which the engine packs into "
                "pages (page-aligned chunks)")
        with jax.named_scope("attn.kv_append"):
            idx = jnp.asarray(cache["len"])
            bt = cache["block_table"]
            ckv_pages = _paged_append(cache["ckv_pages"], bt, idx,
                                      c_kv[:, 0])
            kpe_pages = _paged_append(cache["kpe_pages"], bt, idx,
                                      k_pe[:, 0, 0, :])
            new_cache = {"ckv_pages": ckv_pages, "kpe_pages": kpe_pages,
                         "block_table": bt, "len": idx + 1}
        with jax.named_scope("attn.core"):
            ckv_c, kpe_c = _mla_gather_pages(ckv_pages, kpe_pages, bt)
            o = _mla_expanded_decode(cfg, params, q_nope, q_pe, ckv_c,
                                     kpe_c, idx + 1, cdt)
    elif cache is not None:
        with jax.named_scope("attn.kv_append"):
            idx = jnp.asarray(cache["len"])
            if idx.ndim == 1:
                # per-slot decode (continuous batching): row-wise append
                rows = jnp.arange(B)
                ckv_c = cache["c_kv"].at[rows, idx].set(
                    c_kv[:, 0].astype(cache["c_kv"].dtype), mode="drop")
                kpe_c = cache["k_pe"].at[rows, idx].set(
                    k_pe[:, 0, 0, :].astype(cache["k_pe"].dtype),
                    mode="drop")
            else:
                ckv_c = jax.lax.dynamic_update_slice_in_dim(
                    cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), idx,
                    axis=1)
                kpe_c = jax.lax.dynamic_update_slice_in_dim(
                    cache["k_pe"],
                    k_pe[:, :, 0, :].astype(cache["k_pe"].dtype), idx,
                    axis=1)
            new_cache = {"c_kv": ckv_c, "k_pe": kpe_c, "len": idx + 1}
        with jax.named_scope("attn.core"):
            o = _mla_expanded_decode(cfg, params, q_nope, q_pe, ckv_c,
                                     kpe_c, idx + 1, cdt)
    else:
        with jax.named_scope("attn.core"):
            o = _mla_full_attention(cfg, params, q_nope, q_pe, c_kv, k_pe,
                                    cdt)
    with jax.named_scope("attn.out"):
        y = jnp.einsum("bshk,hkd->bsd", o.astype(cdt),
                       params["wo"].astype(cdt))
        y = _checkpoint_name(y, "block_out")
        return x + y.astype(x.dtype), new_cache


def _mla_gather_pages(ckv_pages, kpe_pages, bt):
    """Every row's latent and rope-key pages gathered through its block
    table into contiguous ``[B, max_pages x page, ...]`` caches."""
    B, mp = bt.shape
    num_pages, page = ckv_pages.shape[0], ckv_pages.shape[1]
    btc = jnp.clip(bt, 0, num_pages - 1)
    return (ckv_pages[btc].reshape(B, mp * page, ckv_pages.shape[-1]),
            kpe_pages[btc].reshape(B, mp * page, kpe_pages.shape[-1]))


def _mla_ragged_prefill_attn(cfg, params, q_nope, q_pe, ckv_c, kpe_c,
                             base, clens, cdt):
    """Ragged MLA prefill attention: expand the full cached latents to
    per-head K/V and attend the [B,T] query chunk with per-row offsets
    (padding query rows exact zero) — the masked oracle shared with the
    GQA prefill kernels."""
    from repro.kernels.ref import prefill_attend_ref

    B, Sc = ckv_c.shape[0], ckv_c.shape[1]
    H = q_nope.shape[2]
    nd, rd, vd = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv_c.astype(cdt),
                        params["wk_b"].astype(cdt))
    v_full = jnp.einsum("bsr,rhk->bshk", ckv_c.astype(cdt),
                        params["wv_b"].astype(cdt))
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kpe_c[:, :, None, :].astype(k_nope.dtype),
                                  (B, Sc, H, rd))], axis=-1)
    q_full = jnp.concatenate([q_nope, q_pe], axis=-1)  # [B,T,H,nd+rd]
    if vd < nd + rd:
        v_pad = jnp.pad(v_full, ((0, 0), (0, 0), (0, 0), (0, nd + rd - vd)))
    else:
        v_pad = v_full
    return prefill_attend_ref(q_full, k_full, v_pad, base, clens)[..., :vd]


def _mla_expanded_decode(cfg, params, q_nope, q_pe, ckv_c, kpe_c, lens, cdt):
    """MLA single-step decode: expand cached latents to full K/V per head
    and run the shared decode kernel (KV == H after expansion, so the GQA
    group is 1).  V is zero-padded to the qk head dim for the kernel, then
    trimmed — padded columns contribute exact zeros."""
    B, Sc = ckv_c.shape[0], ckv_c.shape[1]
    H = q_nope.shape[2]
    nd, rd, vd = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv_c.astype(cdt),
                        params["wk_b"].astype(cdt))
    v_full = jnp.einsum("bsr,rhk->bshk", ckv_c.astype(cdt),
                        params["wv_b"].astype(cdt))
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kpe_c[:, :, None, :].astype(k_nope.dtype),
                                  (B, Sc, H, rd))], axis=-1)
    q_full = jnp.concatenate([q_nope, q_pe], axis=-1)  # [B,1,H,nd+rd]
    if vd < nd + rd:
        v_pad = jnp.pad(v_full, ((0, 0), (0, 0), (0, 0), (0, nd + rd - vd)))
    else:
        v_pad = v_full
    return _decode_attn(cfg, q_full, k_full, v_pad, lens)[..., :vd]


def _mla_full_attention(cfg, params, q_nope, q_pe, c_kv, k_pe, cdt):
    """Full causal MLA pass over in-flight latents (training forward and
    the batched-prefill cache write share this)."""
    B, S, H, _ = q_nope.shape
    nd, rd, vd = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, params["wk_b"].astype(cdt))
    v_full = jnp.einsum("bsr,rhk->bshk", c_kv, params["wv_b"].astype(cdt))
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (B, S, H, rd)).astype(k_nope.dtype)], axis=-1
    )
    q_full = jnp.concatenate([q_nope, q_pe], axis=-1)
    # pad v to qk head dim for the shared chunked kernel, then trim
    if vd < nd + rd:
        v_pad = jnp.pad(v_full, ((0, 0), (0, 0), (0, 0), (0, nd + rd - vd)))
    else:
        v_pad = v_full
    return chunked_attention(
        q_full, k_full, v_pad, causal=True,
        q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
    )[..., :vd]


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_specs(cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict[str, Any]:
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    specs = {
        "norm": rmsnorm_specs(d),
        "w1": Param((d, ff), ("embed", "mlp")),
        "w2": Param((ff, d), ("mlp", "embed")),
    }
    if cfg.mlp_act in ("swiglu", "geglu"):
        specs["w3"] = Param((d, ff), ("embed", "mlp"))
    return specs


def _act(name: str, x):
    if name == "swiglu":
        return jax.nn.silu(x)
    if name == "geglu":
        return jax.nn.gelu(x)
    return jax.nn.gelu(x)


@jax.named_scope("mlp")
def mlp_apply(cfg: ModelConfig, params, x: jnp.ndarray) -> jnp.ndarray:
    cdt = cfg.compute_dtype
    h = rmsnorm_apply(params["norm"], x, cfg.norm_eps).astype(cdt)
    u = jnp.einsum("bsd,df->bsf", h, params["w1"].astype(cdt))
    if "w3" in params:
        g = jnp.einsum("bsd,df->bsf", h, params["w3"].astype(cdt))
        u = _act(cfg.mlp_act, u) * g
    else:
        u = _act(cfg.mlp_act, u)
    y = jnp.einsum("bsf,fd->bsd", u, params["w2"].astype(cdt))
    y = _checkpoint_name(y, "block_out")
    return x + y.astype(x.dtype)


# ---------------------------------------------------------------------------
# MoE (GShard capacity-based top-k)
# ---------------------------------------------------------------------------


def moe_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    specs: Dict[str, Any] = {
        "norm": rmsnorm_specs(d),
        "router": Param((d, E), ("embed", "experts"), init="normal", scale=0.02),
        "w1": Param((E, d, ff), ("experts", "embed", "expert_mlp")),
        "w2": Param((E, ff, d), ("experts", "expert_mlp", "embed")),
    }
    if cfg.mlp_act in ("swiglu", "geglu"):
        specs["w3"] = Param((E, d, ff), ("experts", "embed", "expert_mlp"))
    if cfg.moe_dense_residual:
        dd = cfg.dense_ff or cfg.d_ff
        specs["dense"] = mlp_specs(cfg, dd)
    return specs


def _capacity(tokens_per_group: int, cfg: ModelConfig) -> int:
    c = int(math.ceil(tokens_per_group * cfg.top_k * cfg.capacity_factor / cfg.num_experts))
    return max(c, 4)


def _route(cfg, params, h):
    """h: [G,S,d] -> gates [G,S,k], idx [G,S,k], aux_loss."""
    logits = jnp.einsum("gsd,de->gse", h, params["router"].astype(h.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    # load-balance aux loss (Switch-style)
    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx[..., 0], cfg.num_experts, dtype=jnp.float32), axis=-2),
        axis=0,
    ) / probs.shape[1]
    aux = jnp.sum(me * ce) * cfg.num_experts
    return gates.astype(h.dtype), idx, aux


def _positions_in_expert(idx, E, S):
    """idx: [G,S,k] -> pos [G,S,k] slot positions per expert (priority by k
    then token order), plus expert one-hots [G,S,k,E]."""
    G, _, K = idx.shape
    onehots = jax.nn.one_hot(idx, E, dtype=jnp.int32)  # [G,S,k,E]
    # flatten (k major per token? GShard: priority k=0 first across all tokens)
    flat = jnp.transpose(onehots, (0, 2, 1, 3)).reshape(G, K * S, E)
    pos_flat = jnp.cumsum(flat, axis=1) - 1  # [G,k*S,E]
    pos_flat = jnp.sum(pos_flat * flat, axis=-1)  # [G,k*S]
    pos = jnp.transpose(pos_flat.reshape(G, K, S), (0, 2, 1))  # [G,S,k]
    return pos, onehots


@jax.named_scope("moe")
def moe_apply(cfg: ModelConfig, params, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y, aux_loss)."""
    cdt = cfg.compute_dtype
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.top_k
    h = rmsnorm_apply(params["norm"], x, cfg.norm_eps).astype(cdt)
    G = B  # one routing group per batch row (keeps groups data-sharded)
    hg = h.reshape(G, S, d)
    C = _capacity(S, cfg)
    gates, idx, aux = _route(cfg, params, hg)
    pos, onehots = _positions_in_expert(idx, E, S)
    keep = ((pos < C) & (gates > 0)).astype(cdt)

    if cfg.moe_impl == "einsum":
        # GShard-classic: dense one-hot dispatch/combine einsums.
        pos_oh = jax.nn.one_hot(pos, C, dtype=cdt)  # [G,S,k,C]
        disp = jnp.einsum(
            "gske,gskc->gsec", onehots.astype(cdt) * keep[..., None], pos_oh
        )  # [G,S,E,C]
        expert_in = jnp.einsum("gsec,gsd->gecd", disp, hg)
        expert_out = _expert_ffn(cfg, params, expert_in)
        # combine tensor is gate-weighted PER k-choice (outer-producting the
        # summed dispatch with gates would weight each chosen expert by
        # sum(gates)=1 instead of its own gate)
        comb = jnp.einsum(
            "gske,gskc,gsk->gsec",
            onehots.astype(cdt) * keep[..., None], pos_oh, gates * keep,
        )
        y = jnp.einsum("gsec,gecd->gsd", comb, expert_out)
        y = y.reshape(B, S, d)
    else:
        # gather dispatch: no O(S*E*C) dense einsums.
        gidx = jnp.arange(G)[:, None, None]
        slot_token = jnp.full((G, E, C), S, jnp.int32)  # sentinel = S
        tok = jnp.broadcast_to(jnp.arange(S)[None, :, None], idx.shape)
        # out-of-capacity (pos >= C) indices fall outside the slot dim and
        # are dropped by the scatter — they must NOT clobber slot C-1
        slot_token = slot_token.at[gidx, idx, pos].set(tok, mode="drop")
        h_pad = jnp.concatenate([hg, jnp.zeros((G, 1, d), hg.dtype)], axis=1)
        expert_in = jnp.take_along_axis(
            h_pad[:, :, None, :], slot_token.reshape(G, E * C, 1, 1).clip(0, S), axis=1
        ).reshape(G, E, C, d)
        expert_out = _expert_ffn(cfg, params, expert_in)
        eo_flat = expert_out.reshape(G, E * C, d)
        slot_of_tok = jnp.clip(idx * C + jnp.clip(pos, 0, C - 1), 0, E * C - 1)  # [G,S,k]
        picked = jnp.take_along_axis(
            eo_flat[:, :, None, :], slot_of_tok.reshape(G, S * K, 1, 1), axis=1
        ).reshape(G, S, K, d)
        y = jnp.sum(picked * (gates * keep)[..., None], axis=2).reshape(B, S, d)

    if cfg.moe_dense_residual:
        y = y + (mlp_apply(cfg, params["dense"], x) - x)
    return x + y.astype(x.dtype), aux


def _expert_ffn(cfg, params, expert_in):
    """expert_in: [G,E,C,d] -> [G,E,C,d]."""
    cdt = cfg.compute_dtype
    u = jnp.einsum("gecd,edf->gecf", expert_in, params["w1"].astype(cdt))
    if "w3" in params:
        g = jnp.einsum("gecd,edf->gecf", expert_in, params["w3"].astype(cdt))
        u = _act(cfg.mlp_act, u) * g
    else:
        u = _act(cfg.mlp_act, u)
    return jnp.einsum("gecf,efd->gecd", u, params["w2"].astype(cdt))
