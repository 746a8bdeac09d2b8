"""Plain float32 reference of the Phi-3 decoder (arXiv:2404.14219).

Pre-norm blocks: RMSNorm, attention with rotary positions (rotate-half
form, base ``rope_theta``) over every earlier position, residual; RMSNorm,
gated MLP ``(silu(h W1) * (h W3)) W2``, residual; final RMSNorm and an
untied output head.  Published Phi-3-mini fuses q/k/v and gate/up into
one matrix each; split matrices compute the same thing.  Its
``sliding_window`` (2047) never binds at the contexts the benchmark sends.

Weights are read from the tree the benchmark made, in the program's
layout: ``unit/b0/t`` (attention) and ``unit/b0/c`` (MLP) stacked over
layers.  Every matrix product runs at ``precision="highest"``.

``quant`` stands in for the arithmetic: ``None`` is float32; the control
passes a function that rounds both operands of every product to a lower
precision first (``bench/ref/lowp.py``).
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x [S, H, D]; positions [S]; rotate-half rotary embedding."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(eq, a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.einsum(eq, a, b, precision=HI,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "quant"))
def logits(params: Dict, tokens: jnp.ndarray, *, eps: float, theta: float,
           quant: Optional[Callable] = None) -> jnp.ndarray:
    """Causal forward over one sequence ``tokens [S]`` -> logits [S, V]
    in float32.  Positions past the real length may be padding: no
    earlier position attends them."""
    S = tokens.shape[0]
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]
    x = params["embed"].astype(jnp.float32)[tokens]
    att, mlp = params["unit"]["b0"]["t"], params["unit"]["b0"]["c"]

    def layer(x, p):
        a, m = p
        h = _rms(x, a["norm"]["scale"], eps)
        q = _rope(_mm("sd,dhk->shk", h, a["wq"], quant), pos, theta)
        k = _rope(_mm("sd,dhk->shk", h, a["wk"], quant), pos, theta)
        v = _mm("sd,dhk->shk", h, a["wv"], quant)
        rep = q.shape[1] // k.shape[1]
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        s = _mm("qhk,chk->hqc", q, k, quant) / np.sqrt(q.shape[-1])
        s = jnp.where(causal[None], s, -jnp.inf)
        o = _mm("hqc,chk->qhk", jax.nn.softmax(s, axis=-1), v, quant)
        x = x + _mm("shk,hkd->sd", o, a["wo"], quant)
        h = _rms(x, m["norm"]["scale"], eps)
        u = jax.nn.silu(_mm("sd,df->sf", h, m["w1"], quant))
        u = u * _mm("sd,df->sf", h, m["w3"], quant)
        return x + _mm("sf,fd->sd", u, m["w2"], quant), None

    layers = jax.tree.map(lambda t: t.astype(jnp.float32), (att, mlp))
    x, _ = jax.lax.scan(layer, x, layers)
    x = _rms(x, params["final_norm"]["scale"], eps)
    return _mm("sd,dv->sv", x, params["lm_head"], quant)


def forward(params: Dict, tokens: jnp.ndarray, config: Dict,
            quant: Optional[Callable] = None) -> jnp.ndarray:
    """``logits`` with the norm and rotary settings a configuration file
    states: the entry the harness calls for every ``model_type`` "phi3"."""
    return logits(params, tokens, eps=float(config["rms_norm_eps"]),
                  theta=float(config["rope_theta"]), quant=quant)
