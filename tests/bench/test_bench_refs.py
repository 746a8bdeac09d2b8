"""The plain float32 reference against the program at smoke size, with
the program computing in float32 so both sides agree to rounding: Phi-3
prefill then cached decode against the reference's full forward."""
import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import serve_cell as sc
from bench.harness.weights import make_weights
from bench.ref import phi3


def _program(arch):
    from repro.configs import get_config

    return get_config(arch, smoke=True).with_overrides(
        compute_dtype=jnp.float32)


def test_phi3_prefill_then_cached_decode_matches_the_full_forward():
    from repro.train.state import model_specs
    from repro.train.step import make_decode_step, make_prefill_step

    cfg = _program("phi3-mini-3.8b")
    params = make_weights(model_specs(cfg), 2**32 + 9)
    rng = np.random.default_rng(1)
    P, n, L = 11, 6, 24
    prompt = rng.integers(1, cfg.vocab_size, P).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        prefill = make_prefill_step(cfg, with_cache=True, max_len=L)
        tok, last, cache = prefill(params, jnp.asarray(prompt)[None],
                                   jnp.asarray([P]))
        got = [np.asarray(last[0])]
        toks = [int(tok[0])]
        decode = make_decode_step(cfg)
        for j in range(n - 1):
            tok, logits, cache = decode(params, tok[:, None], cache,
                                        jnp.asarray([P + j], jnp.int32))
            got.append(np.asarray(logits[0, -1]))
            toks.append(int(tok[0]))
    seq = sc.sequence(prompt, np.asarray(toks), pad_to=L)
    ref = np.asarray(phi3.logits(params, jnp.asarray(seq),
                                 eps=cfg.norm_eps, theta=cfg.rope_theta))
    np.testing.assert_allclose(np.stack(got), ref[P - 1: P - 1 + n],
                               atol=2e-4, rtol=2e-4)
    assert sc.served_gaps(ref, P, np.asarray(toks)).max() < 1e-3


def test_phi3_reference_ignores_padding_after_the_sequence():
    from repro.train.state import model_specs

    cfg = _program("phi3-mini-3.8b")
    params = make_weights(model_specs(cfg), 4)
    seq = np.arange(1, 9, dtype=np.int32)
    a = phi3.logits(params, jnp.asarray(np.pad(seq, (0, 4))), eps=1e-5,
                    theta=1e4)
    b = phi3.logits(params, jnp.asarray(np.pad(seq, (0, 12), constant_values=7)),
                    eps=1e-5, theta=1e4)
    np.testing.assert_allclose(a[:8], b[:8], atol=1e-5)
