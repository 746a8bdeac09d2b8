"""Kernel contract pass — device-free shape/dtype verification.

``jax.eval_shape`` abstractly evaluates the serving steps that feed every
``kernels/ops.py`` dispatch (prefill -> flash_attention, chunked prefill
-> prefill_attention / prefill_attention_paged, decode ->
decode_attention / decode_attention_paged, rmsnorm throughout) across

- the full config matrix: all 11 ``configs/*`` modules (10 registered
  archs' smoke configs + the ``base`` default ``ModelConfig``),
- the power-of-two prefill/decode bucket grid the ServeEngine retraces
  over, and
- both KV layouts (contiguous ``lm_cache_specs`` and paged
  ``lm_paged_cache_specs``).

Contracts checked: ``next_token [B] int32``; ``last_logits [B, V]`` /
decode ``logits [B, 1, V]``; the returned cache tree preserves the spec
tree's structure, shapes and dtypes (a layout change would silently
retrace every step).  Archs outside the serving envelope (encoder-
decoder, embed-input, recurrent-state) must refuse with a clean
``NotImplementedError`` — any other exception is a finding.

Whether Mosaic accepts the kernels themselves (block tiling, VMEM) is not
checked here: ``tests/test_tpu_compile.py`` compiles them for a described
TPU v5e at served widths.
"""
from __future__ import annotations

import importlib
from typing import Any, Dict, List, Tuple

from repro.analysis.findings import Finding

_PREFILL_BUCKETS = (8, 16)        # powers of two, like ServeEngine buckets
_CHUNK_BUCKETS = (4, 8)           # chunked-prefill token buckets
_B = 2
_MAX_LEN = 32
_PAGE_SIZE = 8


def _finding(rule: str, symbol: str, message: str) -> Finding:
    return Finding(pass_name="kernels", rule=rule, file="", line=0,
                   symbol=symbol, message=message)


def config_matrix() -> List[Tuple[str, Any]]:
    """All 11 config modules: registered archs (smoke-sized) + base."""
    from repro.configs import ARCHS, ModelConfig

    out: List[Tuple[str, Any]] = []
    for arch in sorted(ARCHS):
        mod = importlib.import_module(f"repro.configs.{ARCHS[arch]}")
        out.append((arch, mod.smoke_config()))
    out.append(("base", ModelConfig(
        name="base-default", family="dense",
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
        d_ff=128, vocab_size=256, head_dim=16,
        attention="gqa", mlp_act="swiglu",
    )))
    return out


def _serving_supported(cfg) -> bool:
    from repro.configs.base import block_pattern

    if cfg.is_encoder_decoder or cfg.input_kind != "tokens":
        return False
    head, unit, _, tail = block_pattern(cfg)
    kinds = {tk for tk, _ in (*head, *unit, *tail)}
    return kinds <= {"attn", "mla"}


def _tree_sig(tree) -> List[Tuple[str, Tuple, str]]:
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(path), tuple(leaf.shape), str(leaf.dtype))
            for path, leaf in flat]


def _sig_mismatch(expect, got) -> str:
    e, g = dict((k, (s, d)) for k, s, d in expect), dict(
        (k, (s, d)) for k, s, d in got)
    for k in sorted(set(e) | set(g)):
        if e.get(k) != g.get(k):
            return (f"cache leaf {k}: expected "
                    f"{e.get(k, 'missing')}, got {g.get(k, 'missing')}")
    return ""


def _check_supported(arch: str, cfg, findings: List[Finding]) -> None:
    import jax
    import jax.numpy as jnp

    from repro.common.params import abstract_params
    from repro.models.lm import lm_cache_specs, lm_paged_cache_specs, lm_specs
    from repro.train.step import (make_decode_step, make_prefill_chunk_step,
                                  make_prefill_step)

    sds = jax.ShapeDtypeStruct
    params = abstract_params(lm_specs(cfg))
    V = cfg.padded_vocab
    cache_abs = abstract_params(lm_cache_specs(cfg, _B, _MAX_LEN))
    cache_sig = _tree_sig(cache_abs)

    # prefill (writes the contiguous cache) across the bucket grid
    prefill = make_prefill_step(cfg, with_cache=True, max_len=_MAX_LEN)
    for P in _PREFILL_BUCKETS:
        label = f"{arch}/contiguous/prefill@P{P}"
        try:
            nt, lg, cache = jax.eval_shape(
                prefill, params, sds((_B, P), jnp.int32),
                sds((_B,), jnp.int32))
        except Exception as e:  # noqa: BLE001 - checker isolation boundary
            findings.append(_finding(
                "kernel-contract", label, f"abstract eval failed: {e!r}"))
            continue
        if tuple(nt.shape) != (_B,) or nt.dtype != jnp.int32:
            findings.append(_finding(
                "kernel-contract", label,
                f"next_token: expected [{_B}] int32, got "
                f"{tuple(nt.shape)} {nt.dtype}"))
        if tuple(lg.shape) != (_B, V):
            findings.append(_finding(
                "kernel-contract", label,
                f"last_logits: expected [{_B}, {V}], got {tuple(lg.shape)}"))
        bad = _sig_mismatch(cache_sig, _tree_sig(cache))
        if bad:
            findings.append(_finding("kernel-contract", label, bad))

    # decode, both KV layouts
    decode = make_decode_step(cfg)
    layouts = [("contiguous", cache_abs, None)]
    max_pages = _MAX_LEN // _PAGE_SIZE
    paged_abs = abstract_params(
        lm_paged_cache_specs(cfg, _B * max_pages, _PAGE_SIZE))
    layouts.append(
        ("paged", paged_abs, sds((_B, max_pages), jnp.int32)))
    for layout, cache_in, bt in layouts:
        label = f"{arch}/{layout}/decode"
        in_sig = _tree_sig(cache_in)
        try:
            nt, lg, nc = jax.eval_shape(
                decode, params, sds((_B, 1), jnp.int32), cache_in,
                sds((_B,), jnp.int32), bt)
        except Exception as e:  # noqa: BLE001 - checker isolation boundary
            findings.append(_finding(
                "kernel-contract", label, f"abstract eval failed: {e!r}"))
            continue
        if tuple(nt.shape) != (_B,) or nt.dtype != jnp.int32:
            findings.append(_finding(
                "kernel-contract", label,
                f"next_token: expected [{_B}] int32, got "
                f"{tuple(nt.shape)} {nt.dtype}"))
        if tuple(lg.shape) != (_B, 1, V):
            findings.append(_finding(
                "kernel-contract", label,
                f"decode logits: expected [{_B}, 1, {V}], got "
                f"{tuple(lg.shape)}"))
        bad = _sig_mismatch(in_sig, _tree_sig(nc))
        if bad:
            findings.append(_finding(
                "kernel-contract", label,
                f"decode must preserve the cache layout ({bad})"))

    # chunked prefill (ragged cache-writing append -> ops.prefill_attention
    # / prefill_attention_paged) across chunk buckets x both layouts
    chunk_step = make_prefill_chunk_step(cfg)
    for T in _CHUNK_BUCKETS:
        for layout, cache_in, bt in layouts:
            label = f"{arch}/{layout}/prefill_chunk@T{T}"
            in_sig = _tree_sig(cache_in)
            try:
                nt, lg, nc = jax.eval_shape(
                    chunk_step, params, sds((_B, T), jnp.int32),
                    sds((_B,), jnp.int32), sds((_B,), jnp.int32),
                    cache_in, bt)
            except Exception as e:  # noqa: BLE001 - checker isolation boundary
                findings.append(_finding(
                    "kernel-contract", label,
                    f"abstract eval failed: {e!r}"))
                continue
            if tuple(nt.shape) != (_B,) or nt.dtype != jnp.int32:
                findings.append(_finding(
                    "kernel-contract", label,
                    f"next_token: expected [{_B}] int32, got "
                    f"{tuple(nt.shape)} {nt.dtype}"))
            if tuple(lg.shape) != (_B, V):
                findings.append(_finding(
                    "kernel-contract", label,
                    f"last_logits: expected [{_B}, {V}], got "
                    f"{tuple(lg.shape)}"))
            bad = _sig_mismatch(in_sig, _tree_sig(nc))
            if bad:
                findings.append(_finding(
                    "kernel-contract", label,
                    f"chunked prefill must append in place, preserving "
                    f"the cache layout ({bad})"))


def _check_unsupported(arch: str, cfg, findings: List[Finding]) -> None:
    """Out-of-envelope archs must refuse cleanly, not mis-trace."""
    from repro.models.lm import lm_paged_cache_specs
    from repro.train.step import make_prefill_chunk_step, make_prefill_step

    for name, build in (
            ("prefill", lambda: make_prefill_step(
                cfg, with_cache=True, max_len=_MAX_LEN)),
            ("prefill_chunk", lambda: make_prefill_chunk_step(cfg))):
        try:
            build()
        except NotImplementedError:
            continue
        except Exception as e:  # noqa: BLE001 - checker isolation boundary
            findings.append(_finding(
                "kernel-contract", f"{arch}/contiguous/{name}",
                f"expected clean NotImplementedError refusal, got {e!r}"))
        else:
            findings.append(_finding(
                "kernel-contract", f"{arch}/contiguous/{name}",
                "cache-writing prefill must refuse non-token-LM / "
                "non-attention archs with NotImplementedError"))
    try:
        lm_paged_cache_specs(cfg, _B * (_MAX_LEN // _PAGE_SIZE), _PAGE_SIZE)
    except NotImplementedError:
        pass  # clean refusal: paged layout is attention-family only
    except Exception as e:  # noqa: BLE001 - checker isolation boundary
        findings.append(_finding(
            "kernel-contract", f"{arch}/paged/specs",
            f"expected NotImplementedError or success, got {e!r}"))


def run() -> List[Finding]:
    findings: List[Finding] = []
    for arch, cfg in config_matrix():
        if _serving_supported(cfg):
            _check_supported(arch, cfg, findings)
        else:
            _check_unsupported(arch, cfg, findings)
    return findings
