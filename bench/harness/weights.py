"""Weights from the run's seed, made on the device in one jitted call.

The program declares its parameters as a tree of specs, each with a
shape, a dtype and an initializer tag; the values are the benchmark's
own, drawn here from the seed, so the plain reference can take them
without taking anything the program made.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from any whole seed: all of its bits count, also above
    32, where ``PRNGKey`` would drop them."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(
        2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _is_spec(x: Any) -> bool:
    return hasattr(x, "init") and hasattr(x, "shape") and hasattr(x, "axes")


def _value(key, spec) -> jax.Array:
    shape, dtype = tuple(spec.shape), spec.dtype
    if spec.init == "zeros":
        return jnp.zeros(shape, dtype)
    if spec.init == "ones":
        return jnp.ones(shape, dtype)
    if spec.init == "embed":
        std = spec.scale if spec.scale is not None else 0.02
    elif spec.init in ("normal", "scaled"):
        fan_in = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
        std = (spec.scale if spec.scale is not None
               else 1.0 / math.sqrt(max(fan_in, 1)))
    else:
        raise ValueError(f"unknown initializer {spec.init!r}")
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make_weights(specs: Any, seed: int, device=None) -> Any:
    """Every leaf of ``specs`` filled from ``seed``, in one program."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_spec)

    def fill(key):
        keys = jax.random.split(key, len(leaves))
        return [_value(k, s) for k, s in zip(keys, leaves)]

    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    values = jax.jit(fill)(key)
    return jax.tree.unflatten(treedef, values)
