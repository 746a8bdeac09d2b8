"""Paged KV pools addressed in place by (layer, page).

Every layer's pools are stacked ``[layers, num_pages, page, ...]`` and a
layer writes and reads its own pages at its index: at the block level
that must equal the same block over the layer's pool sliced out of the
stack, and leave every other layer's pages bit-identical; at the model
level, the scanned unit carrying the stacked pools (and unscanned head
layers on their own pools) must equal the contiguous slot cache, for a
decode step and a ragged prefill chunk, GQA (kernels in interpret mode
and the jnp oracles) and MLA alike."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.params import init_params, is_param
from repro.configs import get_config
from repro.models import blocks as B
from repro.models.lm import lm_apply, lm_cache_specs, lm_paged_cache_specs
from repro.train.state import model_specs

PAGE, MAX_PAGES, ROWS = 8, 4, 3
SPARE = 2  # pages no row owns: no write may touch them
# decode: each row appends at its length; chunk: ragged rows at their own
# base offsets (the last row inert)
LENS = np.array([5, 17, 30], np.int32)
BASE, CHUNK, T = np.array([0, 8, 13], np.int32), np.array([6, 8, 0]), 8


def _cfg(arch, impl):
    cfg = get_config(arch, smoke=True).with_overrides(decode_impl=impl)
    return dataclasses.replace(cfg, compute_dtype=jnp.float32)


def _table(frontier, num_pages, seed):
    """A scrambled block table over ``ROWS`` rows; each row owns the pages
    up to its frontier, the rest of its row is the sentinel."""
    rng = np.random.default_rng(seed)
    full = rng.permutation(ROWS * MAX_PAGES).reshape(ROWS, MAX_PAGES)
    owned = -(-np.maximum(frontier, 1) // PAGE)
    bt = np.where(np.arange(MAX_PAGES)[None] < owned[:, None], full,
                  num_pages)
    return full.astype(np.int32), bt.astype(np.int32)


def _step_inputs(mode, d_model, seed):
    """(x [ROWS, S, d], positions, cache length, chunk_lens, write
    frontier) of a decode step or a ragged chunk."""
    S = 1 if mode == "decode" else T
    x = jax.random.normal(jax.random.PRNGKey(seed), (ROWS, S, d_model))
    if mode == "decode":
        return x, jnp.asarray(LENS[:, None]), jnp.asarray(LENS), None, LENS + 1
    pos = jnp.asarray(BASE[:, None] + np.arange(T)[None])
    return (x, pos, jnp.asarray(BASE), jnp.asarray(CHUNK, jnp.int32),
            BASE + CHUNK)


# -- one block: the stack at layer i == the layer's pool sliced out --------

@pytest.mark.parametrize("mode", ["decode", "chunk"])
@pytest.mark.parametrize("arch,impl", [("tinyllama-1.1b", "interpret"),
                                       ("tinyllama-1.1b", "ref"),
                                       ("minicpm3-4b", "ref")])
def test_block_at_layer_matches_sliced_pool(arch, impl, mode):
    cfg = _cfg(arch, impl)
    layers, num_pages = 3, ROWS * MAX_PAGES + SPARE
    kind = "mla" if cfg.attention == "mla" else "attn"
    specs = B.mla_specs(cfg) if kind == "mla" else B.attn_specs(cfg)
    params = init_params(jax.random.PRNGKey(1), specs)
    pool_specs = lm_paged_cache_specs(cfg, num_pages, PAGE)["unit"]["b0"]
    pools = {name: jax.random.normal(jax.random.PRNGKey(i),
                                     (layers,) + p.shape[1:])
             for i, (name, p) in enumerate(sorted(pool_specs.items()))}
    x, pos, length, clens, frontier = _step_inputs(mode, cfg.d_model, 2)
    _, bt = _table(frontier, num_pages, seed=3)
    apply = B.mla_apply if kind == "mla" else B.attn_apply

    def run(stack, layer):
        cache = dict(stack, layer=layer, block_table=jnp.asarray(bt),
                     len=length)
        y, new = apply(cfg, params, x, pos, cache, chunk_lens=clens)
        return np.asarray(y), {k: np.asarray(new[k]) for k in stack}

    for layer in (0, layers - 1):
        y, new = run(pools, layer)
        y1, new1 = run({k: a[layer][None] for k, a in pools.items()}, 0)
        np.testing.assert_array_equal(y, y1)
        for name, a in pools.items():
            a = np.asarray(a)
            np.testing.assert_array_equal(new[name][layer], new1[name][0])
            assert not np.array_equal(new[name][layer], a[layer])
            others = np.arange(layers) != layer
            np.testing.assert_array_equal(new[name][others], a[others])


# -- the model: scanned stacked pools == the contiguous slot cache ---------

def _paged_from_contiguous(contig, specs, full, seed):
    """Pools of the paged cache's shapes holding each row's contiguous
    cache rows in the pages its table names; every other page, and the
    lanes past a row's width (a lane-aligned pool's padding), random."""
    key = iter(jax.random.split(jax.random.PRNGKey(seed), 64))

    def fill(c, spec, stacked):
        rest = c.shape[(3 if stacked else 2):]
        lead = c.shape[:1] if stacked else ()
        rows = c.reshape(lead + (ROWS * MAX_PAGES, PAGE) + rest)
        pool = jax.random.normal(next(key), spec.shape)
        ids = jnp.asarray(full.reshape(-1))
        w = rest[-1]
        return (pool.at[:, ids, ..., :w].set(rows) if stacked
                else pool.at[ids, ..., :w].set(rows))

    names = {"k": "k_pages", "v": "v_pages", "c_kv": "ckv_pages",
             "k_pe": "kpe_pages"}
    return {part: {ln: {names[k]: fill(a, specs[part][ln][names[k]],
                                       part == "unit")
                        for k, a in layer.items()}
                   for ln, layer in contig[part].items()}
            for part in ("head_layers", "unit", "tail_layers")}


def _gathered(paged, full):
    """The pools read back through the full tables, in contiguous form
    (lane padding included)."""
    ids = jnp.asarray(full)

    def rows(p, stacked):
        g = p[:, ids] if stacked else p[ids]
        lead = p.shape[:1] if stacked else ()
        return g.reshape(lead + (ROWS, MAX_PAGES * PAGE) + p.shape[
            (3 if stacked else 2):])

    return {part: {ln: {k: rows(a, part == "unit") for k, a in layer.items()}
                   for ln, layer in paged[part].items()}
            for part in ("head_layers", "unit", "tail_layers")}


@pytest.mark.parametrize("mode", ["decode", "chunk"])
@pytest.mark.parametrize("arch,impl", [
    ("tinyllama-1.1b", "interpret"),       # GQA kernels, scanned unit
    ("moonshot-v1-16b-a3b", "ref"),        # GQA oracles, an unscanned head
    ("minicpm3-4b", "ref"),                # MLA latent pages
])
def test_paged_scan_matches_contiguous(arch, impl, mode):
    cfg = _cfg(arch, impl)
    params = init_params(jax.random.PRNGKey(0), model_specs(cfg))
    num_pages = ROWS * MAX_PAGES + SPARE
    S = MAX_PAGES * PAGE
    contig = jax.tree.map(
        lambda p: jax.random.normal(jax.random.PRNGKey(len(p.shape)), p.shape),
        lm_cache_specs(cfg, ROWS, S), is_leaf=is_param)
    _, pos, length, clens, frontier = _step_inputs(mode, cfg.d_model, 4)
    tokens = jax.random.randint(jax.random.PRNGKey(5), pos.shape, 1,
                                cfg.vocab_size)
    full, bt = _table(frontier, num_pages, seed=6)
    specs = lm_paged_cache_specs(cfg, num_pages, PAGE)
    paged = _paged_from_contiguous(contig, specs, full, seed=7)
    want, want_cache, _ = lm_apply(cfg, params, tokens, None, contig, length,
                                   chunk_lens=clens, remat=False)
    got, got_cache, _ = lm_apply(cfg, params, tokens, None, paged, length,
                                 block_table=jnp.asarray(bt),
                                 chunk_lens=clens, remat=False)
    live = (np.arange(ROWS) if mode == "decode"
            else np.flatnonzero(CHUNK))  # an inert row's logits are junk
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5, rtol=2e-5)
    # every position a row holds reads back equal; the pages no row's
    # table names (spare pages, the sentinel tails) were never written
    held = np.arange(S)[None] < frontier[:, None]
    written = held & (np.arange(S)[None] >= np.asarray(length)[:, None])
    back = _gathered(got_cache, full)
    for part in ("head_layers", "unit", "tail_layers"):
        lead = (slice(None),) if part == "unit" else ()
        for g, w in zip(jax.tree.leaves(back[part]),
                        jax.tree.leaves(want_cache[part])):
            g, w = np.asarray(g), np.asarray(w)
            width = w.shape[-1]
            np.testing.assert_allclose(g[lead + (held,)][..., :width],
                                       w[lead + (held,)],
                                       atol=1e-6, rtol=1e-6)
            # the step's own writes zero a row's lane padding
            assert not np.any(g[lead + (written,)][..., width:])
        unowned = np.setdiff1d(np.arange(num_pages), bt)
        for new, old in zip(jax.tree.leaves(got_cache[part]),
                            jax.tree.leaves(paged[part])):
            np.testing.assert_array_equal(np.asarray(new)[lead + (unowned,)],
                                          np.asarray(old)[lead + (unowned,)])
