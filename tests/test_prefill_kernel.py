"""Ragged cache-writing prefill kernel: parity sweeps vs the jnp oracles
(interpret mode), chunk-offset equivalence, paged-vs-contiguous equality,
and the flash-attention ragged-tail regression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels import prefill_attention as pa
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _inputs(B, T, H, KV, D, S, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k_new = jax.random.normal(ks[1], (B, T, KV, D))
    v_new = jax.random.normal(ks[2], (B, T, KV, D))
    k_cache = jax.random.normal(ks[3], (B, S, KV, D))
    v_cache = jax.random.normal(ks[4], (B, S, KV, D))
    return q, k_new, v_new, k_cache, v_cache


@pytest.mark.parametrize("H,KV", [(4, 4), (8, 2), (4, 1)])  # MHA/GQA/MQA
def test_prefill_matches_ref_ragged(H, KV):
    B, T, D, S = 3, 8, 32, 64
    q, kn, vn, kc, vc = _inputs(B, T, H, KV, D, S)
    base = jnp.array([0, 5, 13], jnp.int32)
    clens = jnp.array([8, 3, 0], jnp.int32)  # full / partial / inert row
    got, gkc, gvc = pa.prefill_attention(
        q, kn, vn, kc, vc, base, clens, block_q=8, block_k=16,
        interpret=True)
    want, wkc, wvc = ref.prefill_attention_ref(q, kn, vn, kc, vc, base,
                                               clens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # cache writes are a masked scatter of the same values: exact
    np.testing.assert_array_equal(np.asarray(gkc), np.asarray(wkc))
    np.testing.assert_array_equal(np.asarray(gvc), np.asarray(wvc))


def test_prefill_span_past_cache_matches_ref():
    """S = 40 with 16-row spans: the last span reads 8 rows past the cache,
    and row 1's queries (positions 30..35) attend into that span."""
    B, T, H, KV, D, S = 2, 8, 8, 2, 32, 40
    q, kn, vn, kc, vc = _inputs(B, T, H, KV, D, S, seed=2)
    base = jnp.array([0, 30], jnp.int32)
    clens = jnp.array([8, 6], jnp.int32)
    got, _, _ = pa.prefill_attention(q, kn, vn, kc, vc, base, clens,
                                     block_q=8, block_k=16, interpret=True)
    want, _, _ = ref.prefill_attention_ref(q, kn, vn, kc, vc, base, clens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_prefill_padding_rows_exact_zero():
    B, T, H, KV, D, S = 2, 8, 4, 2, 32, 32
    q, kn, vn, kc, vc = _inputs(B, T, H, KV, D, S)
    clens = jnp.array([5, 0], jnp.int32)
    out, _, _ = pa.prefill_attention(
        q, kn, vn, kc, vc, jnp.array([0, 7], jnp.int32), clens,
        block_q=8, block_k=16, interpret=True)
    out = np.asarray(out)
    assert (out[0, 5:] == 0.0).all() and (out[1] == 0.0).all()
    assert np.isfinite(out).all()


def test_prefill_chunked_equals_one_shot():
    """Two chunks at offsets 0 and T1 == one whole-prompt pass."""
    B, T, H, KV, D, S = 2, 8, 4, 2, 32, 64
    T1 = 4
    q, kn, vn, kc, vc = _inputs(B, T, H, KV, D, S)
    full = jnp.full((B,), T, jnp.int32)
    zero = jnp.zeros((B,), jnp.int32)
    o_all, kc_all, vc_all = pa.prefill_attention(
        q, kn, vn, kc, vc, zero, full, block_q=4, block_k=16,
        interpret=True)
    o1, kc1, vc1 = pa.prefill_attention(
        q[:, :T1], kn[:, :T1], vn[:, :T1], kc, vc, zero,
        jnp.full((B,), T1, jnp.int32), block_q=4, block_k=16,
        interpret=True)
    o2, kc2, vc2 = pa.prefill_attention(
        q[:, T1:], kn[:, T1:], vn[:, T1:], kc1, vc1,
        jnp.full((B,), T1, jnp.int32), jnp.full((B,), T - T1, jnp.int32),
        block_q=4, block_k=16, interpret=True)
    np.testing.assert_array_equal(np.asarray(kc2), np.asarray(kc_all))
    np.testing.assert_array_equal(np.asarray(vc2), np.asarray(vc_all))
    got = np.concatenate([np.asarray(o1), np.asarray(o2)], axis=1)
    np.testing.assert_allclose(got, np.asarray(o_all), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("H,KV", [(4, 4), (8, 2), (4, 1)])
def test_prefill_paged_matches_ref(H, KV):
    B, T, D = 3, 8, 32
    page, max_pages, num_pages = 16, 4, 16
    q, kn, vn, _, _ = _inputs(B, T, H, KV, D, 1)
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    kp = jax.random.normal(ks[0], (num_pages, page, KV, D))
    vp = jax.random.normal(ks[1], (num_pages, page, KV, D))
    # scrambled physical pages + sentinel (unallocated) tail entries
    bt = jnp.array([[5, 9, 2, num_pages],
                    [0, 7, num_pages, num_pages],
                    [11, 3, 8, 1]], jnp.int32)
    base = jnp.array([0, 5, 13], jnp.int32)
    clens = jnp.array([8, 3, 0], jnp.int32)
    got, gkp, gvp = pa.prefill_attention_paged(
        q, kn, vn, kp, vp, bt, base, clens, block_q=8, interpret=True)
    want, wkp, wvp = ref.prefill_attention_paged_ref(
        q, kn, vn, kp, vp, bt, base, clens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(gkp), np.asarray(wkp))
    np.testing.assert_array_equal(np.asarray(gvp), np.asarray(wvp))


def test_prefill_paged_equals_contiguous():
    """An identity-mapped page pool IS a contiguous cache: both layouts
    must produce bitwise-identical outputs (f32 path)."""
    B, T, H, KV, D = 2, 8, 4, 2, 32
    page, max_pages = 16, 3
    S = page * max_pages
    q, kn, vn, kc, vc = _inputs(B, T, H, KV, D, S)
    bt = jnp.arange(B * max_pages, dtype=jnp.int32).reshape(B, max_pages)
    kp = kc.reshape(B * max_pages, page, KV, D)
    vp = vc.reshape(B * max_pages, page, KV, D)
    base = jnp.array([0, 17], jnp.int32)
    clens = jnp.array([8, 6], jnp.int32)
    oc, kcc, _ = pa.prefill_attention(q, kn, vn, kc, vc, base, clens,
                                      block_q=8, block_k=16,
                                      interpret=True)
    op, kpp, _ = pa.prefill_attention_paged(q, kn, vn, kp, vp, bt, base,
                                            clens, block_q=8,
                                            interpret=True)
    np.testing.assert_array_equal(np.asarray(op), np.asarray(oc))
    np.testing.assert_array_equal(
        np.asarray(kpp).reshape(B, S, KV, D), np.asarray(kcc))


def test_prefill_ops_dispatch():
    """ops.prefill_attention impl= routing: ref and interpret agree."""
    B, T, H, KV, D, S = 2, 4, 4, 2, 32, 32
    q, kn, vn, kc, vc = _inputs(B, T, H, KV, D, S)
    base = jnp.array([0, 9], jnp.int32)
    clens = jnp.array([4, 2], jnp.int32)
    o_ref, krf, _ = ops.prefill_attention(q, kn, vn, kc, vc, base, clens,
                                          impl="ref")
    o_int, kin, _ = ops.prefill_attention(q, kn, vn, kc, vc, base, clens,
                                          impl="interpret")
    np.testing.assert_allclose(np.asarray(o_int), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(kin), np.asarray(krf))


def test_flash_attention_ragged_tail():
    """Regression: S not a multiple of the block no longer silently
    truncates trailing queries/keys (old grid was S // block_q)."""
    B, H, S, D = 1, 4, 130, 64
    q = jax.random.normal(KEY, (B, H, S, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, H, S, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, H, S, D))
    for causal in (True, False):
        got = fa.flash_attention(q, k, v, causal=causal, block_q=64,
                                 block_k=64, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=causal)
        assert got.shape == (B, H, S, D)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
