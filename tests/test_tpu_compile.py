"""The kernels compile for a real chip: each of the four attention kernels
on the main serving path (and the flash-attention and rmsnorm kernels
beside it) is lowered and compiled by the TPU compiler against a described
(not attached) TPU v5e topology, at the widths the served models use.
Interpret-mode parity cannot show this: Mosaic's block-tiling and VMEM
rules only bite here.  The paged engine's own step programs compile there
too, and are checked to update the KV pools in place.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.  Keep these compiles in this one file."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.common.params import is_param
from repro.configs import get_config
from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import prefill_attention as pa
from repro.kernels import rmsnorm as rn
from repro.serve import ServeEngine
from repro.train.state import model_specs

# served widths: batch, q heads, kv heads, head dim, cache rows, page size,
# prefill chunk tokens (the engine's default chunk budget)
LAYOUTS = {
    # tinyllama-1.1b: GQA 32 q heads over 4 kv heads, head dim 64
    "tinyllama-1.1b": dict(B=8, H=32, KV=4, D=64, S=2048, page=16, T=64),
    # minicpm3-4b MLA decode expands latents to KV == H heads (40 padded
    # to 48) with the qk head dim (nope 64 + rope 32)
    "minicpm3-4b-mla-expanded": dict(B=8, H=48, KV=48, D=96, S=2048,
                                     page=16, T=64),
    # a cache length no VMEM-sized span divides (256-token prompts + 32
    # new tokens + 1): the last span runs past the cache
    "minicpm3-4b-mla-expanded-s289": dict(B=8, H=48, KV=48, D=96, S=289,
                                          page=16, T=64),
}
KERNELS = ["decode_attention", "decode_attention_paged",
           "prefill_attention", "prefill_attention_paged"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A described-chip compile lands in the persistent cache but cannot be
    read back without the chip; keep the cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _lowerable(kernel, dims, sds):
    """(function, abstract args) for one kernel at one layout."""
    B, H, KV, D, S, page, T = (dims[k] for k in
                               ("B", "H", "KV", "D", "S", "page", "T"))
    bf16, i32 = jnp.bfloat16, jnp.int32
    num_pages, max_pages = B * S // page, S // page
    cache = sds((B, S, KV, D), bf16)
    # two layers' pools, lane-aligned as the model holds them
    pool = sds((2, num_pages, page, KV, da.lane_aligned(D)), bf16)
    layer = sds((), i32)
    table = sds((B, max_pages), i32)
    lens = sds((B,), i32)
    q1 = sds((B, H, D), bf16)
    qT, kvT = sds((B, T, H, D), bf16), sds((B, T, KV, D), bf16)
    return {
        "decode_attention": (da.decode_attention, (q1, cache, cache, lens)),
        "decode_attention_paged": (da.decode_attention_paged,
                                   (q1, pool, pool, table, lens, layer)),
        "prefill_attention": (pa.prefill_attention,
                              (qT, kvT, kvT, cache, cache, lens, lens)),
        "prefill_attention_paged": (pa.prefill_attention_paged,
                                    (qT, kvT, kvT, pool, pool, table, lens,
                                     lens, layer)),
    }[kernel]


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(kernel, layout, one_chip,
                                 no_persistent_cache):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _lowerable(kernel, LAYOUTS[layout], sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["flash_attention", "rmsnorm"])
def test_other_kernel_compiles_for_v5e(kernel, one_chip, no_persistent_cache):
    """tinyllama-1.1b widths: a 2048-token causal pass, d_model 2048."""
    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    if kernel == "flash_attention":
        fn, args = fa.flash_attention, (sds(1, 32, 2048, 64),
                                        sds(1, 4, 2048, 64),
                                        sds(1, 4, 2048, 64))
    else:
        fn, args = rn.rmsnorm, (sds(8 * 2048, 2048), sds(2048))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# an HLO instruction: its result type, then its opcode
_HLO_OP = re.compile(r"\s*(?:ROOT )?%\S+ = (.*?) ([a-z][a-z-]*)\(")


def _pool_moves(hlo: str, pool: "re.Pattern") -> list:
    """Instructions (fused ones included) whose result is a pool-shaped
    copy, dynamic-slice or dynamic-update-slice."""
    moves = []
    for line in hlo.splitlines():
        m = _HLO_OP.match(line)
        if (m and m.group(2) in ("copy", "dynamic-slice",
                                 "dynamic-update-slice")
                and pool.search(m.group(1))):
            moves.append(line.strip()[:160])
    return moves


def _aliased_pools(hlo: str) -> tuple:
    """(entry parameter numbers of the KV pools, parameter numbers the
    program aliases to an output)."""
    pools = {int(n) for n in re.findall(
        r"^\s*%\S*_pages\S* = \S+ parameter\((\d+)\)", hlo, re.M)}
    header = hlo.splitlines()[0]
    aliased = {int(n) for n in re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)", header)}
    return pools, aliased


def test_engine_steps_update_pools_in_place_for_v5e(one_chip,
                                                    no_persistent_cache):
    """The paged engine's decode step and a 64-token prefill-chunk step at
    phi3-mini widths (32 KV heads of 96, page 16; 2 layers, 256 pages):
    every layer writes and reads its pages in place, so no copy,
    dynamic-slice or dynamic-update-slice yields a pool-shaped value,
    stacked or per-layer, and both pools alias an output."""
    L, pages, slots, max_len, T = 2, 256, 8, 512, 64
    cfg = get_config("phi3-mini-3.8b").with_overrides(num_layers=L)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda p: sds(p.shape, p.dtype), model_specs(cfg),
                          is_leaf=is_param)
    eng = ServeEngine(cfg, params=params, max_slots=slots, max_len=max_len,
                      page_size=16, num_pages=pages, decode_impl="pallas")
    cache = jax.tree.map(lambda a: sds(a.shape, a.dtype), eng.cache)
    ints = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    table = ints(slots, eng.max_pages)
    programs = {
        "decode": eng._decode.lower(
            params, ints(slots), cache, ints(slots), sds((slots,), bool),
            sds((slots, 2), jnp.uint32), sds((slots,), jnp.float32),
            ints(slots), table, sampling=False),
        "prefill_chunk": eng._get_prefill(T).lower(
            params, ints(slots, T), ints(slots), ints(slots), cache, table),
    }
    # the pools' head dim: 96 lanes padded to 128 in the cache's shape
    pool = re.compile(r"bf16\[(%d,)?%d,16,32,(96|128)\]" % (L, pages))
    for name, lowered in programs.items():
        hlo = lowered.compile().as_text()
        assert "tpu_custom_call" in hlo, name
        assert _pool_moves(hlo, pool) == [], name
        pools, aliased = _aliased_pools(hlo)
        assert len(pools) == 2 and pools <= aliased, (name, pools, aliased)
