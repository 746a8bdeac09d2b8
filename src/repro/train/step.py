"""train_step / serve_step factories.

``make_train_step`` builds the jit-able update: microbatched grad
accumulation (lax.scan), fp32 loss, global-norm clipping, AdamW/Adafactor,
optional int8 gradient compression on the DP all-reduce
(distributed/collectives.py).  ``make_prefill_step`` / ``make_decode_step``
build the serving steps: batched prefill (optionally writing the KV cache
in one full-sequence forward) and single-token decode (which also
greedy-samples; accepts per-slot cache lengths for continuous batching).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.distributed.sharding import constrain
from repro.models import encdec
from repro.models.lm import lm_apply
from repro.train.optimizer import opt_update

PyTree = Any


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """logits [B,S,V] (any float dtype), labels [B,S] int32 -> mean nats."""
    logits = constrain(logits.astype(jnp.float32), ("act_batch", None, "act_vocab"))
    m = jnp.max(logits, axis=-1, keepdims=True)
    shifted = logits - jax.lax.stop_gradient(m)
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    label_logit = jnp.take_along_axis(shifted, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - label_logit)


def _forward_loss(cfg: ModelConfig, params, batch: Dict, remat: bool):
    if cfg.is_encoder_decoder:
        enc_out = encdec.encode(cfg, params, batch["frames"], remat=remat)
        logits = encdec.decode_train(cfg, params, enc_out, batch["tokens"], remat=remat)
        loss = cross_entropy(logits, batch["labels"])
        return loss, logits
    inputs = batch.get("tokens", batch.get("embeds"))
    positions = batch.get("positions")
    logits, _, aux = lm_apply(cfg, params, inputs, positions, remat=remat)
    loss = cross_entropy(logits, batch["labels"]) + 0.01 * aux
    return loss, logits


def make_loss_fn(cfg: ModelConfig, run_cfg: RunConfig):
    remat = run_cfg.remat != "none"

    def loss_fn(params, batch):
        loss, _ = _forward_loss(cfg, params, batch, remat)
        return loss

    return loss_fn


def global_norm(tree: PyTree) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(l.astype(jnp.float32) ** 2) for l in leaves))


def clip_by_global_norm(tree: PyTree, max_norm: float) -> Tuple[PyTree, jnp.ndarray]:
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-6))
    return jax.tree.map(lambda l: (l.astype(jnp.float32) * scale).astype(l.dtype), tree), norm


def make_train_step(cfg: ModelConfig, run_cfg: RunConfig):
    loss_fn = make_loss_fn(cfg, run_cfg)
    n_micro = run_cfg.num_microbatches

    def split_micro(batch):
        def rs(x):
            b = x.shape[0]
            y = x.reshape((n_micro, b // n_micro) + x.shape[1:])
            return y

        return jax.tree.map(rs, batch)

    def train_step(state: Dict, batch: Dict) -> Tuple[Dict, Dict]:
        params = state["params"]
        if n_micro == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        else:
            micro = split_micro(batch)
            acc_dt = cfg.grad_accum_dtype

            def acc_body(carry, mb):
                loss_acc, g_acc = carry
                mb = jax.tree.map(
                    lambda x: constrain(x, ("act_batch",) + (None,) * (x.ndim - 1)), mb
                )
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                g = jax.tree.map(lambda a, b: (a + b.astype(acc_dt)).astype(acc_dt), g_acc, g)
                return (loss_acc + l, g), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dt), params)
            (loss, grads), _ = jax.lax.scan(acc_body, (jnp.zeros(()), g0), micro)
            loss = loss / n_micro
            grads = jax.tree.map(lambda g: g / n_micro, grads)

        if run_cfg.grad_clip > 0:
            grads, gnorm = clip_by_global_norm(grads, run_cfg.grad_clip)
        else:
            gnorm = jnp.zeros(())
        new_params, new_opt = opt_update(
            grads, state["opt"], params, state["step"], run_cfg
        )
        new_state = {"params": new_params, "opt": new_opt, "step": state["step"] + 1}
        return new_state, {"loss": loss, "grad_norm": gnorm}

    return train_step


def make_prefill_step(cfg: ModelConfig, run_cfg: Optional[RunConfig] = None,
                      *, with_cache: bool = False, max_len: Optional[int] = None):
    """Prefill step factory.

    Default mode returns the last-position logits (what a serving system
    samples from) — returning the full [B,S,V] tensor would materialize
    hundreds of GB at 32k x 100k-vocab.

    ``with_cache=True`` builds the serving prefill: ONE jitted full-sequence
    causal forward (the flash/chunked pass, not a token-replay loop) that
    also writes the prompt's K/V into a fresh ``[B, max_len]`` cache.
    ``prefill_step(params, tokens, lengths)`` takes right-padded prompts
    ``tokens [B,P]`` with true lengths ``lengths [B]`` and returns
    ``(next_token [B], last_logits [B,V], cache)`` where ``last_logits`` is
    read at each row's final *valid* position.  Positions past a row's
    length hold junk K/V but sit beyond that row's cache length, so they
    are masked in every subsequent decode and overwritten as the row
    generates.  Token-LM archs with attention-family temporal blocks only
    (recurrent state caches need a step-scan prefill).
    """
    if with_cache:
        if cfg.is_encoder_decoder or cfg.input_kind != "tokens":
            raise NotImplementedError(
                "cache-writing prefill targets token-LM archs")
        if max_len is None:
            raise ValueError("with_cache=True requires max_len")
        from repro.configs.base import block_pattern
        from repro.models.lm import lm_cache_specs
        from repro.common.params import is_param

        head, unit, _, tail = block_pattern(cfg)
        kinds = {tk for tk, _ in (*head, *unit, *tail)}
        if not kinds <= {"attn", "mla"}:
            # 'local' is excluded: the windowed ring cache keeps the last
            # positions of the PADDED sequence, so right-padding junk from
            # shorter rows would land inside the attention window where
            # the per-slot length mask cannot exclude it
            raise NotImplementedError(
                f"cache-writing prefill supports full-attention blocks "
                f"only, got {sorted(kinds)} (recurrent state caches need a "
                f"step-scan prefill; windowed ring caches need per-row "
                f"length-aware writes)")

        def prefill_step(params, tokens: jnp.ndarray, lengths: jnp.ndarray):
            B, P = tokens.shape
            specs = lm_cache_specs(cfg, B, max_len)
            cache = jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype),
                                 specs, is_leaf=is_param)
            # ragged cache-writing prefill at base 0: per-row lengths ride
            # as chunk_lens, so padding tokens never write K/V and each
            # row attends exactly its own prompt
            logits, new_cache, _ = lm_apply(
                cfg, params, tokens, None, cache,
                jnp.zeros((B,), jnp.int32),
                chunk_lens=lengths.astype(jnp.int32), remat=False)
            last = jnp.take_along_axis(
                logits, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1
            )[:, 0]
            last = constrain(last, ("act_batch", "act_vocab"))
            next_token = jnp.argmax(last, axis=-1).astype(jnp.int32)
            return next_token, last, new_cache

        return prefill_step

    def prefill_step(params, batch: Dict) -> jnp.ndarray:
        if cfg.is_encoder_decoder:
            enc_out = encdec.encode(cfg, params, batch["frames"], remat=False)
            logits = encdec.decode_train(cfg, params, enc_out, batch["tokens"],
                                         remat=False, last_only=True)
        else:
            inputs = batch.get("tokens", batch.get("embeds"))
            logits, _, _ = lm_apply(cfg, params, inputs, batch.get("positions"),
                                    remat=False, last_only=True)
        out = logits[:, -1, :]
        return constrain(out, ("act_batch", "act_vocab"))

    return prefill_step


def make_prefill_chunk_step(cfg: ModelConfig,
                            run_cfg: Optional[RunConfig] = None):
    """Chunked-prefill step factory (Sarathi-style serving prefill).

    ``chunk_step(params, tokens, base, chunk_lens, cache, block_table=None)``
    appends a ``[B, T]`` token slab into an EXISTING cache: row ``b``'s
    first ``chunk_lens[b]`` tokens land at offset ``base[b]`` (its cached
    prefix length) and attend the full warm prefix through the ragged
    prefill kernel — rows with ``chunk_lens[b] == 0`` are inert.  Works on
    both the contiguous slot cache and the paged pool (``block_table``
    selects paged).  Returns ``(next_token [B], last_logits [B, V],
    new_cache)`` with the last logits read at each row's final valid chunk
    position (junk for inert rows — callers gate on their own bookkeeping).
    Token-LM archs with full-attention temporal blocks only, mirroring
    ``make_prefill_step(with_cache=True)``.
    """
    if cfg.is_encoder_decoder or cfg.input_kind != "tokens":
        raise NotImplementedError(
            "chunked prefill targets token-LM archs")
    from repro.configs.base import block_pattern

    head, unit, _, tail = block_pattern(cfg)
    kinds = {tk for tk, _ in (*head, *unit, *tail)}
    if not kinds <= {"attn", "mla"}:
        raise NotImplementedError(
            f"chunked prefill supports full-attention blocks only, got "
            f"{sorted(kinds)} (recurrent state caches need a step-scan "
            f"prefill; windowed ring caches need per-row length-aware "
            f"writes)")

    @jax.named_scope("prefill_chunk")
    def chunk_step(params, tokens, base, chunk_lens, cache,
                   block_table=None):
        base = jnp.asarray(base, jnp.int32)
        chunk_lens = jnp.asarray(chunk_lens, jnp.int32)
        logits, new_cache, _ = lm_apply(
            cfg, params, tokens, None, cache, base,
            block_table=block_table, chunk_lens=chunk_lens, remat=False)
        last = jnp.take_along_axis(
            logits, jnp.maximum(chunk_lens - 1, 0)[:, None, None], axis=1
        )[:, 0]
        last = constrain(last, ("act_batch", "act_vocab"))
        next_token = jnp.argmax(last, axis=-1).astype(jnp.int32)
        return next_token, last, new_cache

    return chunk_step


def make_decode_step(cfg: ModelConfig, run_cfg: Optional[RunConfig] = None):
    """One new token against a pre-filled KV cache.  ``block_table``
    ([B, max_pages] int32) selects the paged-cache path: ``cache`` then
    holds shared page pools (``lm_paged_cache_specs``) instead of
    contiguous per-row caches."""

    @jax.named_scope("decode_step")
    def decode_step(params, tokens, cache, cache_len, block_table=None):
        if cfg.is_encoder_decoder:
            logits, new_cache = encdec.decode_step(cfg, params, tokens, cache, cache_len)
        else:
            positions = None
            if cfg.mrope_sections:
                Bsz = tokens.shape[0]
                positions = jnp.broadcast_to(
                    cache_len[None, None, None], (Bsz, 1, 3)
                ).astype(jnp.int32)
            logits, new_cache, _ = lm_apply(
                cfg, params, tokens, positions, cache, cache_len,
                block_table=block_table, remat=False
            )
        next_token = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_token, logits, new_cache

    return decode_step
