"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode),
plus hypothesis property tests on kernel invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import hash_partition as hp
from repro.kernels import ref
from repro.kernels import rmsnorm as rn
from repro.kernels import ops

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("B,H,KV,S,D", [
    (1, 4, 4, 128, 64),    # MHA
    (2, 8, 2, 256, 64),    # GQA 4x
    (1, 4, 1, 128, 128),   # MQA
    (1, 8, 8, 192, 32),    # non-128 block tail
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, H, KV, S, D, dtype):
    q = jax.random.normal(KEY, (B, H, S, D), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, KV, S, D), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, KV, S, D), dtype)
    got = fa.flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                             interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_flash_attention_causality():
    """Output at position i must not depend on tokens > i."""
    B, H, S, D = 1, 2, 128, 64
    q = jax.random.normal(KEY, (B, H, S, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, H, S, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, H, S, D))
    out1 = fa.flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=True)
    k2 = k.at[:, :, 64:].set(99.0)
    v2 = v.at[:, :, 64:].set(-99.0)
    out2 = fa.flash_attention(q, k2, v2, causal=True, block_q=64, block_k=64,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(out1[:, :, :64]),
                               np.asarray(out2[:, :, :64]), atol=1e-5, rtol=1e-5)


def _decode_inputs(B, H, KV, S, D, seed=0):
    """Cache-native layout: q [B,H,D]; k, v [B,S,KV,D]."""
    q = jax.random.normal(jax.random.PRNGKey(seed), (B, H, D))
    k = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, KV, D))
    v = jax.random.normal(jax.random.PRNGKey(seed + 2), (B, S, KV, D))
    return q, k, v


def _paged_inputs(B, H, KV, S, D, page, seed=0, scramble=True, layers=3):
    """Pools of ``layers`` layers stacked ``[layers, num_pages, page, KV,
    D]`` (each layer's pages differ) + a scrambled block table covering
    [B, S] logical positions, with spare pages left unused."""
    rng = np.random.default_rng(seed)
    mp = S // page
    num_pages = B * mp + 3  # spare pages: gather must ignore them
    q = jax.random.normal(jax.random.PRNGKey(seed), (B, H, D))
    kp = jax.random.normal(jax.random.PRNGKey(seed + 1),
                           (layers, num_pages, page, KV, D))
    vp = jax.random.normal(jax.random.PRNGKey(seed + 2),
                           (layers, num_pages, page, KV, D))
    ids = (rng.permutation(num_pages)[:B * mp] if scramble
           else np.arange(B * mp))
    bt = jnp.asarray(ids.reshape(B, mp).astype(np.int32))
    return q, kp, vp, bt, num_pages


# -- vector-length (per-row [B] cache lengths) parity -----------------------

@pytest.mark.parametrize("B,H,KV,S,D,bk", [
    (2, 8, 2, 512, 64, 128),   # GQA 4x
    (1, 4, 4, 256, 128, 64),   # MHA
    (4, 16, 1, 1024, 64, 256),  # MQA
    (2, 4, 4, 128, 48, 64),    # MLA-expanded layout (KV == H, qk dim 48)
    (2, 8, 2, 100, 64, 64),    # last span runs past the cache
])
def test_decode_attention_matches_ref(B, H, KV, S, D, bk):
    q, k, v = _decode_inputs(B, H, KV, S, D)
    for cl in (jnp.asarray(S * 3 // 4, jnp.int32),          # scalar
               jnp.asarray(np.random.default_rng(B).integers(1, S + 1, B),
                           jnp.int32)):                     # ragged [B]
        got = da.decode_attention(q, k, v, cl, block_k=bk, interpret=True)
        want = ref.decode_attention_ref(q, k, v, cl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [8, 64, 100])
def test_decode_attention_windowed_matches_ref(window):
    """Windowed/local masks ride the same per-row length logic: positions
    outside [len - window, len) never contribute."""
    B, H, KV, S, D = 3, 8, 2, 256, 32
    q, k, v = _decode_inputs(B, H, KV, S, D, seed=3)
    lens = jnp.asarray([S, S // 2, window + 1], jnp.int32)
    got = da.decode_attention(q, k, v, lens, window=window, block_k=64,
                              interpret=True)
    want = ref.decode_attention_ref(q, k, v, lens, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@given(cache_len=st.integers(min_value=1, max_value=256))
@settings(deadline=None, max_examples=10)
def test_decode_attention_cache_len_property(cache_len):
    """Positions >= cache_len never contribute."""
    B, H, KV, S, D = 1, 2, 2, 256, 32
    q, k, v = _decode_inputs(B, H, KV, S, D)
    cl = jnp.asarray(cache_len, jnp.int32)
    base = da.decode_attention(q, k, v, cl, block_k=64, interpret=True)
    k2 = k.at[:, cache_len:].set(7.0)
    v2 = v.at[:, cache_len:].set(-7.0)
    got = da.decode_attention(q, k2, v2, cl, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(base), np.asarray(got),
                               atol=1e-5, rtol=1e-5)


def test_decode_attention_rows_independent():
    """A [B] length vector must mask each row independently: row i's
    output equals a B=1 call at its own length."""
    B, H, KV, S, D = 4, 8, 2, 128, 32
    q, k, v = _decode_inputs(B, H, KV, S, D, seed=5)
    lens = jnp.asarray([1, 37, 64, 128], jnp.int32)
    got = da.decode_attention(q, k, v, lens, block_k=32, interpret=True)
    for i in range(B):
        solo = da.decode_attention(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                   lens[i], block_k=32, interpret=True)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(solo[0]),
                                   atol=1e-5, rtol=1e-5)


# -- paged (block-table gather) parity --------------------------------------

@pytest.mark.parametrize("B,H,KV,S,D,page", [
    (2, 8, 2, 256, 64, 64),    # GQA 4x
    (1, 4, 4, 128, 32, 32),    # MHA
    (4, 16, 1, 512, 64, 128),  # MQA
    (2, 4, 4, 128, 48, 32),    # MLA-expanded layout
])
def test_paged_decode_matches_ref(B, H, KV, S, D, page):
    """Layer ``l`` of the stacked pools, addressed in place, matches the
    oracle and the same kernel over that layer's pool sliced out (a
    one-layer stack), with the rows' unused table tails on the sentinel;
    over lane-aligned pools it reads the same."""
    q, kp, vp, bt, num_pages = _paged_inputs(B, H, KV, S, D, page, seed=7)
    lens = np.random.default_rng(B).integers(1, S + 1, B)
    bt = np.array(bt)
    for b in range(B):
        bt[b, -(-int(lens[b]) // page):] = num_pages
    bt, lens = jnp.asarray(bt), jnp.asarray(lens, jnp.int32)
    for layer in (0, 2):
        got = da.decode_attention_paged(q, kp, vp, bt, lens, layer,
                                        interpret=True)
        want = ref.decode_attention_paged_ref(q, kp, vp, bt, lens, layer)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
        sliced = da.decode_attention_paged(
            q, kp[layer][None], vp[layer][None], bt, lens, 0,
            interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(sliced))
    # lane-aligned pools, as the model holds them: what the padding lanes
    # hold reads as nothing
    junk = jax.random.normal(jax.random.PRNGKey(5),
                             kp.shape[:-1] + (da.lane_aligned(D) - D,))
    kw, vw = (jnp.concatenate([p, junk], -1) for p in (kp, vp))
    want = ref.decode_attention_paged_ref(q, kp, vp, bt, lens, 2)
    for got in (da.decode_attention_paged(q, kw, vw, bt, lens, 2,
                                          interpret=True),
                ref.decode_attention_paged_ref(q, kw, vw, bt, lens, 2)):
        assert got.shape == q.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def test_paged_decode_matches_contiguous():
    """A paged cache whose gathered view equals a contiguous cache must
    produce the contiguous kernel's output — including lengths that end
    exactly on, one past, and one before a page boundary."""
    B, H, KV, S, D, page = 3, 8, 2, 256, 32, 64
    q, kp, vp, bt, _ = _paged_inputs(B, H, KV, S, D, page, seed=9)
    k = kp[1][bt].reshape(B, S, KV, D)
    v = vp[1][bt].reshape(B, S, KV, D)
    for lens in ([page, 2 * page, 3 * page],        # exactly on boundaries
                 [page + 1, 2 * page - 1, S],       # straddling
                 [1, page // 2, S - 1]):
        cl = jnp.asarray(lens, jnp.int32)
        got = da.decode_attention_paged(q, kp, vp, bt, cl, 1,
                                        interpret=True)
        want = da.decode_attention(q, k, v, cl, block_k=page, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


def test_paged_decode_sentinel_entries_ignored():
    """Unallocated logical pages carry a sentinel id (>= num_pages): any
    such page sits at or past the row's length and must not contribute,
    whatever garbage the clamped page holds."""
    B, H, KV, S, D, page = 2, 4, 2, 256, 32, 64
    q, kp, vp, bt, num_pages = _paged_inputs(B, H, KV, S, D, page, seed=11)
    lens = jnp.asarray([page, 2 * page], jnp.int32)
    base = da.decode_attention_paged(q, kp, vp, bt, lens, 2, interpret=True)
    bt_s = np.array(bt)
    bt_s[0, 1:] = num_pages  # rows only keep their live-prefix pages
    bt_s[1, 2:] = num_pages
    got = da.decode_attention_paged(q, kp, vp, jnp.asarray(bt_s), lens, 2,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(base), np.asarray(got),
                               atol=1e-5, rtol=1e-5)


def test_paged_decode_windowed_matches_ref():
    B, H, KV, S, D, page = 2, 8, 2, 256, 32, 64
    q, kp, vp, bt, _ = _paged_inputs(B, H, KV, S, D, page, seed=13)
    lens = jnp.asarray([S, S // 2 + 3], jnp.int32)
    got = da.decode_attention_paged(q, kp, vp, bt, lens, 1, window=48,
                                    interpret=True)
    want = ref.decode_attention_paged_ref(q, kp, vp, bt, lens, 1, window=48)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("shape", [(32, 128), (4, 17, 256), (1, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_ref(shape, dtype):
    x = jax.random.normal(KEY, shape, dtype)
    w = jax.random.normal(jax.random.PRNGKey(3), (shape[-1],), dtype)
    got = rn.rmsnorm(x, w, block_rows=16, interpret=True)
    want = ref.rmsnorm_ref(x, w)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@given(n=st.integers(100, 5000), p=st.sampled_from([4, 16, 64]))
@settings(deadline=None, max_examples=10)
def test_hash_partition_histogram_property(n, p):
    """Per-block histograms sum to the exact global histogram."""
    keys = jax.random.randint(jax.random.PRNGKey(n), (n,), 0, 10_000)
    hist = hp.hash_partition_histogram(keys, num_buckets=p, block=512,
                                       interpret=True)
    want = ref.hash_partition_histogram_ref(keys, num_buckets=p)
    np.testing.assert_array_equal(np.asarray(hist.sum(0)), np.asarray(want))
    assert int(hist.sum()) == n


def test_partition_order_bucket_contiguous():
    keys = jax.random.randint(KEY, (5000,), 0, 10_000)
    order, offsets = hp.partition_order(keys, 16, interpret=True)
    b = np.asarray((ref.hash_u32_ref(keys) % jnp.uint32(16)).astype(jnp.int32))
    assert np.all(np.diff(b[np.asarray(order)]) >= 0)
    assert offsets.shape == (16,)


def test_ops_dispatch_ref_path():
    """impl='ref' and impl='interpret' agree (CPU container has no TPU)."""
    q = jax.random.normal(KEY, (1, 4, 64, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 64, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 4, 64, 32))
    a = ops.flash_attention(q, k, v, impl="ref")
    b = ops.flash_attention(q, k, v, impl="interpret", block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)
