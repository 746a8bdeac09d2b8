"""GPipe-style pipeline parallelism over a ``pipe`` mesh axis
(shard_map + non-cyclic collective_permute).

Layers split into S stages (one per pipe rank); microbatches stream through
with the classic fill-drain schedule expressed as ``lax.scan`` over
``n_micro + S - 1`` ticks: each tick every stage applies its layers to the
microbatch it holds and permutes the activation rightward.  A feature-flag
option validated at test scale (4-stage mesh); the assigned dry-run matrix
uses DP x TP x EP (DESIGN.md §5).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(
    stage_fn: Callable,      # stage_fn(stage_params, x) -> y  (one stage)
    stage_params,            # pytree with leading [n_stages, ...] dims
    x_micro: jnp.ndarray,    # [n_micro, micro_batch, ...]
    mesh: Mesh,
    axis: str = "pipe",
) -> jnp.ndarray:
    """Returns [n_micro, micro_batch, ...] outputs (all stages applied)."""
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]

    def per_stage(params_stage, queue):
        S = jax.lax.axis_size(axis)
        stage = jax.lax.axis_index(axis)
        ticks = n_micro + S - 1
        feat_shape = queue.shape[1:]

        def tick(carry, t):
            hold, outputs = carry
            src = jnp.clip(t, 0, n_micro - 1)
            x_in = jnp.where(
                stage == 0,
                jax.lax.dynamic_index_in_dim(queue, src, keepdims=False),
                hold,
            )
            active = (t >= stage) & (t - stage < n_micro)
            y = stage_fn(jax.tree.map(lambda p: p[0], params_stage), x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # rightward non-cyclic handoff; stage 0 receives zeros
            passed = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(S - 1)]
            )
            out_idx = jnp.clip(t - (S - 1), 0, n_micro - 1)
            write = (stage == S - 1) & active
            outputs = jnp.where(
                write,
                jax.lax.dynamic_update_index_in_dim(outputs, y, out_idx, 0),
                outputs,
            )
            return (passed, outputs), None

        hold0 = jnp.zeros(feat_shape, queue.dtype)
        out0 = jnp.zeros((n_micro,) + feat_shape, queue.dtype)
        (_, outputs), _ = jax.lax.scan(tick, (hold0, out0), jnp.arange(ticks))
        return outputs[None]  # [1, n_micro, ...] per stage

    fn = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(axis),
        check_vma=False,
    )
    stacked = fn(stage_params, x_micro)  # [S, n_micro, ...]
    return stacked[-1]
