"""Lower-precision arithmetic for the controls: a reference run with every
matrix product's operands rounded first, one step below the precision a
configuration states."""
from __future__ import annotations

import jax.numpy as jnp


def fp8_round(x: jnp.ndarray) -> jnp.ndarray:
    """Round to float8 (e4m3) with one scale per tensor, as an fp8 matrix
    unit is fed; the product then accumulates in float32."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
