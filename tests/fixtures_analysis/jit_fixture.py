"""Seeded jit-boundary violations (parsed, never executed).

Expected findings (asserted exactly in test_analysis_passes.py):

* ``time.time()`` under jit (host-sync);
* ``if y > 0`` — Python branch on a traced value (traced-branch);
* ``float(y)`` — host cast of a traced value (host-sync);
* ``if jax.sharding.reshard(...)`` — branch on a traced array that a
  ``jax.sharding`` call returned (traced-branch);
* ``leaky_step(x, scale=[...])`` — list display fed to a
  ``static_argnames`` parameter (static-unhashable).

``clean_step`` exercises the exemptions the pass must honour: shape
attributes, the mesh query ``get_abstract_mesh``, ``is None`` tests,
closure config.
"""
import functools
import time

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("scale",))
def leaky_step(x, scale):
    t0 = time.time()  # SEEDED VIOLATION: wall clock inside jit
    y = jnp.sum(x) * scale
    if y > 0:  # SEEDED VIOLATION: Python branch on a traced value
        y = y + 1.0
    peek = float(y)  # SEEDED VIOLATION: host cast of a traced value
    if jax.sharding.reshard(y, None) > 0:  # SEEDED VIOLATION: traced
        y = y - 1.0
    return y, t0, peek


@functools.partial(jax.jit, static_argnames=("bias",))
def clean_step(x, mask=None, bias=0.0):
    if mask is not None:  # static: identity test
        x = jnp.where(mask, x, 0.0)
    if x.ndim > 1 and jax.sharding.get_abstract_mesh().empty:  # static
        x = x.reshape(-1)
    return x * bias


def caller(x):
    return leaky_step(x, scale=[1, 2])  # SEEDED VIOLATION: unhashable static
