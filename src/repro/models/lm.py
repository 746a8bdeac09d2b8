"""Unified decoder-style LM covering dense / MoE / VLM / SSM / hybrid
families via ``configs.base.block_pattern``.

Layers are grouped into a repeating *unit* which is ``lax.scan``-ned over
(stacked parameters, stacked caches); head/tail layers run unscanned.  This
keeps compile time O(unit) instead of O(num_layers) — essential for the
512-device dry-runs — while the HLO cost analyzer multiplies while-bodies by
their trip count so roofline numbers stay honest.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.params import Param, is_param
from repro.configs.base import ModelConfig, block_pattern
from repro.models import blocks as B
from repro.models import recurrent as R

# ---------------------------------------------------------------------------
# block kind dispatch
# ---------------------------------------------------------------------------


def _temporal_specs(kind: str, cfg: ModelConfig):
    if kind in ("attn", "local"):
        return B.attn_specs(cfg)
    if kind == "mla":
        return B.mla_specs(cfg)
    if kind == "rglru":
        return R.rglru_specs(cfg)
    if kind == "mlstm":
        return R.mlstm_specs(cfg)
    if kind == "slstm":
        return R.slstm_specs(cfg)
    raise ValueError(kind)


def _temporal_apply(kind: str, cfg, params, x, positions, cache,
                    chunk_lens=None):
    if kind == "attn":
        return B.attn_apply(cfg, params, x, positions, cache, causal=True,
                            chunk_lens=chunk_lens)
    if kind == "local":
        if chunk_lens is not None:
            raise NotImplementedError(
                "chunked prefill does not support windowed (local) "
                "attention: ring cache writes need the full prompt")
        return B.attn_apply(cfg, params, x, positions, cache, causal=True, window=cfg.window)
    if kind == "mla":
        return B.mla_apply(cfg, params, x, positions, cache,
                           chunk_lens=chunk_lens)
    if chunk_lens is not None:
        raise NotImplementedError(
            f"chunked prefill supports attention-family blocks only, "
            f"got {kind!r}")
    if kind == "rglru":
        return R.rglru_block_apply(cfg, params, x, cache)
    if kind == "mlstm":
        return R.mlstm_block_apply(cfg, params, x, cache)
    if kind == "slstm":
        return R.slstm_block_apply(cfg, params, x, cache)
    raise ValueError(kind)


def _layer_specs(cfg: ModelConfig, tk: str, ck: Optional[str]):
    specs = {"t": _temporal_specs(tk, cfg)}
    if ck == "mlp":
        # in MoE stacks the dense head/tail layers use dense_ff if set
        ff = cfg.dense_ff if (cfg.num_experts > 0 and cfg.dense_ff) else None
        specs["c"] = B.mlp_specs(cfg, ff)
    elif ck == "moe":
        specs["c"] = B.moe_specs(cfg)
    return specs


def _layer_apply(cfg, tk, ck, params, x, positions, cache, chunk_lens=None):
    x, new_cache = _temporal_apply(tk, cfg, params["t"], x, positions, cache,
                                   chunk_lens)
    aux = jnp.zeros((), jnp.float32)
    if ck == "mlp":
        x = B.mlp_apply(cfg, params["c"], x)
    elif ck == "moe":
        x, aux = B.moe_apply(cfg, params["c"], x)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# cache declarations (as Param trees so the dry-run can make abstract caches)
# ---------------------------------------------------------------------------


def _temporal_cache_specs(kind: str, cfg: ModelConfig, batch: int, max_len: int):
    cdt = cfg.compute_dtype
    if kind in ("attn", "local"):
        _, KV = cfg.padded_gqa()
        slots = min(max_len, cfg.window) if (kind == "local" and cfg.window) else max_len
        return {
            "k": Param((batch, slots, KV, cfg.qk_head_dim),
                       ("cache_batch", "cache_seq", "cache_heads", None), dtype=cdt, init="zeros"),
            "v": Param((batch, slots, KV, cfg.head_dim if kind != "mla" else cfg.v_head_dim),
                       ("cache_batch", "cache_seq", "cache_heads", None), dtype=cdt, init="zeros"),
        }
    if kind == "mla":
        return {
            "c_kv": Param((batch, max_len, cfg.kv_lora_rank),
                          ("cache_batch", "cache_seq", None), dtype=cdt, init="zeros"),
            "k_pe": Param((batch, max_len, cfg.rope_head_dim),
                          ("cache_batch", "cache_seq", None), dtype=cdt, init="zeros"),
        }
    if kind == "rglru":
        r, w = cfg.rnn_width, cfg.conv_width
        return {
            "conv": Param((batch, w - 1, r), ("cache_batch", None, "rnn"), dtype=cdt, init="zeros"),
            "h": Param((batch, r), ("cache_batch", "rnn"), dtype=jnp.float32, init="zeros"),
        }
    if kind == "mlstm":
        m = 2 * cfg.d_model
        nh = cfg.num_heads
        dh = m // nh
        return {
            "conv": Param((batch, cfg.conv_width - 1, m), ("cache_batch", None, "rnn"), dtype=cdt, init="zeros"),
            "C": Param((batch, nh, dh, dh), ("cache_batch", None, None, None), dtype=jnp.float32, init="zeros"),
            "n": Param((batch, nh, dh), ("cache_batch", None, None), dtype=jnp.float32, init="zeros"),
            "m": Param((batch, nh), ("cache_batch", None), dtype=jnp.float32, init="zeros"),
        }
    if kind == "slstm":
        d = cfg.d_model
        return {
            "c": Param((batch, d), ("cache_batch", "rnn"), dtype=jnp.float32, init="zeros"),
            "n": Param((batch, d), ("cache_batch", "rnn"), dtype=jnp.float32, init="zeros"),
            "h": Param((batch, d), ("cache_batch", "rnn"), dtype=jnp.float32, init="zeros"),
            "m": Param((batch, d), ("cache_batch", "rnn"), dtype=jnp.float32, init="zeros"),
        }
    raise ValueError(kind)


def _temporal_paged_cache_specs(kind: str, cfg: ModelConfig,
                                num_pages: int, page_size: int):
    """Paged serving cache: one shared page pool per layer (``[num_pages,
    page_size, ...]``), addressed through a per-slot block table that
    lives OUTSIDE the cache tree (it is shared by every layer — all
    layers append at the same positions).  Attention-family kinds only:
    recurrent state caches have no sequence axis to page."""
    cdt = cfg.compute_dtype
    if kind == "attn":
        _, KV = cfg.padded_gqa()
        return {
            "k_pages": Param((num_pages, page_size, KV, cfg.qk_head_dim),
                             ("cache_seq", None, "cache_heads", None),
                             dtype=cdt, init="zeros"),
            "v_pages": Param((num_pages, page_size, KV, cfg.head_dim),
                             ("cache_seq", None, "cache_heads", None),
                             dtype=cdt, init="zeros"),
        }
    if kind == "mla":
        return {
            "ckv_pages": Param((num_pages, page_size, cfg.kv_lora_rank),
                               ("cache_seq", None, None), dtype=cdt,
                               init="zeros"),
            "kpe_pages": Param((num_pages, page_size, cfg.rope_head_dim),
                               ("cache_seq", None, None), dtype=cdt,
                               init="zeros"),
        }
    raise NotImplementedError(
        f"paged KV cache supports full-attention blocks only, got {kind!r}")


def lm_paged_cache_specs(cfg: ModelConfig, num_pages: int,
                         page_size: int) -> Dict[str, Any]:
    head, unit, reps, tail = block_pattern(cfg)
    return {
        "head_layers": {
            f"h{i}": _temporal_paged_cache_specs(tk, cfg, num_pages, page_size)
            for i, (tk, _) in enumerate(head)
        },
        "unit": _stack(
            {f"b{i}": _temporal_paged_cache_specs(tk, cfg, num_pages,
                                                  page_size)
             for i, (tk, _) in enumerate(unit)},
            reps,
        ),
        "tail_layers": {
            f"t{i}": _temporal_paged_cache_specs(tk, cfg, num_pages,
                                                 page_size)
            for i, (tk, _) in enumerate(tail)
        },
    }


def _pack_cache(kind: str, raw: Dict, length, block_table=None) -> Dict:
    """Join declared cache arrays with the runtime length scalar (and, for
    paged caches, the shared block table) into the structure the
    block-apply functions expect."""
    if kind in ("attn", "local"):
        if "k_pages" in raw:
            return {"k_pages": raw["k_pages"], "v_pages": raw["v_pages"],
                    "block_table": block_table, "len": length}
        return {"k": raw["k"], "v": raw["v"], "len": length}
    if kind == "mla":
        if "ckv_pages" in raw:
            return {"ckv_pages": raw["ckv_pages"],
                    "kpe_pages": raw["kpe_pages"],
                    "block_table": block_table, "len": length}
        return {"c_kv": raw["c_kv"], "k_pe": raw["k_pe"], "len": length}
    if kind == "rglru":
        return {"conv": raw["conv"], "h": raw["h"]}
    if kind == "mlstm":
        return {"conv": raw["conv"], "state": (raw["C"], raw["n"], raw["m"])}
    if kind == "slstm":
        return {"state": (raw["c"], raw["n"], raw["h"], raw["m"])}
    raise ValueError(kind)


def _unpack_cache(kind: str, cache: Dict) -> Dict:
    if kind in ("attn", "local"):
        if "k_pages" in cache:
            return {"k_pages": cache["k_pages"], "v_pages": cache["v_pages"]}
        return {"k": cache["k"], "v": cache["v"]}
    if kind == "mla":
        if "ckv_pages" in cache:
            return {"ckv_pages": cache["ckv_pages"],
                    "kpe_pages": cache["kpe_pages"]}
        return {"c_kv": cache["c_kv"], "k_pe": cache["k_pe"]}
    if kind == "rglru":
        return {"conv": cache["conv"], "h": cache["h"]}
    if kind == "mlstm":
        C, n, m = cache["state"]
        return {"conv": cache["conv"], "C": C, "n": n, "m": m}
    if kind == "slstm":
        c, n, h, m = cache["state"]
        return {"c": c, "n": n, "h": h, "m": m}
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# stacking helpers
# ---------------------------------------------------------------------------


def _stack(specs: Any, reps: int) -> Any:
    return jax.tree.map(
        lambda p: Param((reps,) + p.shape, ("layers",) + p.axes, p.dtype, p.init, p.scale),
        specs,
        is_leaf=is_param,
    )


# ---------------------------------------------------------------------------
# LM
# ---------------------------------------------------------------------------


def lm_specs(cfg: ModelConfig) -> Dict[str, Any]:
    head, unit, reps, tail = block_pattern(cfg)
    specs: Dict[str, Any] = {
        "embed": Param((cfg.padded_vocab, cfg.d_model), ("vocab", "embed"), init="embed"),
        "final_norm": B.rmsnorm_specs(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = Param((cfg.d_model, cfg.padded_vocab), ("embed", "vocab"))
    specs["head_layers"] = {
        f"h{i}": _layer_specs(cfg, tk, ck) for i, (tk, ck) in enumerate(head)
    }
    specs["unit"] = _stack(
        {f"b{i}": _layer_specs(cfg, tk, ck) for i, (tk, ck) in enumerate(unit)}, reps
    )
    specs["tail_layers"] = {
        f"t{i}": _layer_specs(cfg, tk, ck) for i, (tk, ck) in enumerate(tail)
    }
    return specs


def lm_cache_specs(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    head, unit, reps, tail = block_pattern(cfg)
    return {
        "head_layers": {
            f"h{i}": _temporal_cache_specs(tk, cfg, batch, max_len)
            for i, (tk, _) in enumerate(head)
        },
        "unit": _stack(
            {f"b{i}": _temporal_cache_specs(tk, cfg, batch, max_len)
             for i, (tk, _) in enumerate(unit)},
            reps,
        ),
        "tail_layers": {
            f"t{i}": _temporal_cache_specs(tk, cfg, batch, max_len)
            for i, (tk, _) in enumerate(tail)
        },
    }


def _embed_tokens(cfg, params, tokens):
    emb = jnp.take(params["embed"], tokens, axis=0)
    return emb.astype(cfg.compute_dtype)


def lm_apply(
    cfg: ModelConfig,
    params: Dict[str, Any],
    inputs: jnp.ndarray,
    positions: Optional[jnp.ndarray] = None,
    cache: Optional[Dict] = None,
    cache_len=None,
    *,
    block_table=None,
    chunk_lens=None,
    remat: bool = True,
    last_only: bool = False,
) -> Tuple[jnp.ndarray, Optional[Dict], jnp.ndarray]:
    """Returns (logits, new_cache, aux_loss).

    inputs: int tokens [B,S] or embeds [B,S,d] (vlm/audio frontends).
    cache/cache_len: decode mode (S==1) or batched prefill (S>1 with a
    scalar cache_len — the full-sequence K/V is written into the cache in
    one forward).  A [B]-vector cache_len runs per-slot decode: every row
    appends and attends at its own length (continuous batching).
    ``block_table`` ([B, max_pages] int32) rides alongside a *paged* cache
    (``lm_paged_cache_specs``): it is shared by every layer, so it threads
    through here rather than living in the per-layer cache tree.
    ``chunk_lens`` ([B] int32, S>1 + cache only) switches prefill to the
    ragged cache-writing path: ``cache_len`` is then each row's *base*
    offset (cached-prefix length, scalar or [B]) and row ``b``'s first
    ``chunk_lens[b]`` tokens append at it — chunked prefill over a warm
    cache on either KV layout.  Positions default to ``base + arange(S)``
    per row.
    """
    head, unit, reps, tail = block_pattern(cfg)
    with jax.named_scope("embed"):
        if inputs.ndim == 2:
            x = _embed_tokens(cfg, params, inputs)
        else:
            x = inputs.astype(cfg.compute_dtype)
    Bsz, S = x.shape[0], x.shape[1]
    if positions is None:
        if chunk_lens is not None:
            # ragged chunked prefill: row b's tokens sit at base + [0, S)
            base = jnp.broadcast_to(
                jnp.asarray(cache_len if cache_len is not None else 0,
                            jnp.int32).reshape(-1), (Bsz,))
            positions = base[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        elif cache_len is not None:
            cl = jnp.asarray(cache_len)
            if cl.ndim == 1:  # per-slot lengths: each row decodes at its own position
                positions = cl[:, None].astype(jnp.int32)
            else:
                positions = jnp.broadcast_to(cl[None, None], (Bsz, 1)).astype(jnp.int32)
        else:
            positions = jnp.broadcast_to(jnp.arange(S)[None, :], (Bsz, S)).astype(jnp.int32)

    aux_total = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {"head_layers": {}, "tail_layers": {}}

    def run_layer(tk, ck, p, x, c):
        cc = (_pack_cache(tk, c, cache_len, block_table)
              if c is not None else None)
        x, nc, aux = _layer_apply(cfg, tk, ck, p, x, positions, cc,
                                  chunk_lens)
        return x, (_unpack_cache(tk, nc) if nc is not None else None), aux

    # the layer stack: head layers, the scanned unit, tail layers
    with jax.named_scope("layers"):
        # head
        for i, (tk, ck) in enumerate(head):
            c = cache["head_layers"][f"h{i}"] if cache is not None else None
            x, nc, aux = run_layer(tk, ck, params["head_layers"][f"h{i}"], x, c)
            aux_total += aux
            if nc is not None:
                new_cache["head_layers"][f"h{i}"] = nc

        # scanned unit
        if reps > 0:
            unit_params = params["unit"]
            unit_cache = cache["unit"] if cache is not None else None

            if unit_cache is None:

                def unit_body(carry, p_i):
                    x, aux_acc = carry
                    # barrier pins the saved-residual dtype: without it XLA:CPU
                    # hoists the first-use f32 convert through the scan's
                    # dynamic-update-slice and stacks the residuals twice
                    # (bf16 + f32) — a 3x memory hit at 4k seq.
                    x = jax.lax.optimization_barrier(x)
                    if cfg.seq_parallel:
                        # Megatron SP: the saved residual is seq-sharded over
                        # the model axis (16x smaller stack); GSPMD inserts the
                        # gather at the first full-sequence consumer
                        from repro.distributed.sharding import constrain
                        x = constrain(x, ("act_batch", "act_seq_sp", None))
                    aux_sum = jnp.zeros((), jnp.float32)
                    for j, (tk, ck) in enumerate(unit):
                        x, _, aux = run_layer(tk, ck, p_i[f"b{j}"], x, None)
                        aux_sum += aux
                    return (x, aux_acc + aux_sum), None

                if remat and cfg.remat_policy == "save_block_outputs":
                    body = jax.checkpoint(
                        unit_body,
                        policy=jax.checkpoint_policies.save_only_these_names("block_out"),
                    )
                elif remat:
                    body = jax.checkpoint(unit_body)
                else:
                    body = unit_body
                (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), unit_params)
            else:

                def unit_body_c(carry, xs):
                    x, aux_acc = carry
                    p_i, c_i = xs
                    nc_i = {}
                    aux_sum = jnp.zeros((), jnp.float32)
                    for j, (tk, ck) in enumerate(unit):
                        x, nc, aux = run_layer(tk, ck, p_i[f"b{j}"], x, c_i[f"b{j}"])
                        aux_sum += aux
                        nc_i[f"b{j}"] = nc
                    return (x, aux_acc + aux_sum), nc_i

                (x, aux_total), scanned_cache = jax.lax.scan(
                    unit_body_c, (x, aux_total), (unit_params, unit_cache)
                )
                new_cache["unit"] = scanned_cache

        # tail
        for i, (tk, ck) in enumerate(tail):
            c = cache["tail_layers"][f"t{i}"] if cache is not None else None
            x, nc, aux = run_layer(tk, ck, params["tail_layers"][f"t{i}"], x, c)
            aux_total += aux
            if nc is not None:
                new_cache["tail_layers"][f"t{i}"] = nc

    with jax.named_scope("final"):
        if last_only:
            x = x[:, -1:]
        x = B.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
        head_w = (
            params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        ).astype(cfg.compute_dtype)
        logits = jnp.einsum("bsd,dv->bsv", x.astype(cfg.compute_dtype),
                            head_w)
    return logits, (new_cache if cache is not None else None), aux_total
