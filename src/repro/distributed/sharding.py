"""Logical-axis sharding rules -> PartitionSpecs.

Models annotate every parameter / activation dim with a *logical* axis name
("embed", "heads", "vocab", ...).  A rule table maps logical names to (tuples
of) physical mesh axes.  ``spec_for`` applies the table with two safeguards:

* divisibility — a mesh axis (product) that does not divide the dim size is
  dropped (longest usable prefix of the axis tuple wins, then ``None``);
* exclusivity — a mesh axis may appear at most once in a PartitionSpec; the
  first dim that claims it keeps it.

This is what lets one rule table serve 10 architectures whose head counts /
expert counts / batch sizes do not all divide the mesh (e.g. tinyllama's 4 KV
heads on a 16-way model axis fall back to replication automatically).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.params import Param, is_param

# ---------------------------------------------------------------------------
# Rule tables
# ---------------------------------------------------------------------------

# Default rules for the production mesh (pod?, data, model).
# Weights are ZeRO-3/FSDP-sharded over ("pod","data") on their "embed"-like
# dim and tensor-parallel over "model" on their "heads"/"mlp"/"vocab" dim.
DEFAULT_RULES: dict[str, tuple] = {
    # -- weights --
    "embed": ("data",),          # FSDP shard dim (gathered per-layer in scan)
    "embed_pod": ("pod", "data"),  # alt: FSDP over pod too (set via override)
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "q_lora": ("model",),
    "kv_lora": (),               # latent rank: small, replicate
    "mlp": ("model",),
    "experts": ("model",),
    "expert_mlp": (),
    "rnn": ("model",),           # recurrent width
    "conv": (),
    "layers": (),                # scan dim: never sharded
    "stack": (),
    # -- activations --
    "act_batch": ("pod", "data"),
    "act_seq": (),
    "act_seq_sp": ("model",),  # Megatron sequence parallelism (residual stream)
    "act_embed": (),
    "act_heads": ("model",),
    "act_mlp": ("model",),
    "act_vocab": ("model",),
    "act_experts": ("model",),
    # -- kv cache (decode): sequence-split over model (flash-decoding style),
    #    because kv_heads (1..10) rarely divide a 16-way model axis.
    "cache_batch": ("pod", "data"),
    "cache_seq": ("model",),
    "cache_heads": (),
    # -- optimizer / scalar --
    "null": (),
}


def merge_rules(overrides: Optional[dict] = None) -> dict:
    rules = dict(DEFAULT_RULES)
    if overrides:
        rules.update(overrides)
    return rules


# ---------------------------------------------------------------------------
# Spec derivation
# ---------------------------------------------------------------------------


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1


def spec_for(
    axes: Sequence[Optional[str]],
    shape: Sequence[int],
    mesh: Mesh,
    rules: dict,
) -> P:
    """Derive a PartitionSpec for one tensor."""
    used: set = set()
    entries = []
    for dim, logical in zip(shape, axes):
        if logical is None:
            entries.append(None)
            continue
        if logical not in rules:
            raise KeyError(f"no sharding rule for logical axis {logical!r}")
        candidate = tuple(a for a in rules[logical] if a in mesh.shape)
        # drop axes already used by earlier dims
        candidate = tuple(a for a in candidate if a not in used)
        # longest prefix whose size product divides the dim
        chosen: tuple = ()
        for k in range(len(candidate), 0, -1):
            prefix = candidate[:k]
            prod = 1
            for a in prefix:
                prod *= _axis_size(mesh, a)
            if prod > 1 and dim % prod == 0:
                chosen = prefix
                break
        if not chosen:
            entries.append(None)
        else:
            used.update(chosen)
            entries.append(chosen if len(chosen) > 1 else chosen[0])
    # strip trailing Nones for tidiness
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def param_specs_tree(specs: Any, mesh: Mesh, rules: Optional[dict] = None) -> Any:
    """Param tree -> PartitionSpec tree."""
    rules = merge_rules(rules)
    return jax.tree.map(
        lambda p: spec_for(p.axes, p.shape, mesh, rules), specs, is_leaf=is_param
    )


def param_shardings_tree(specs: Any, mesh: Mesh, rules: Optional[dict] = None) -> Any:
    rules = merge_rules(rules)
    return jax.tree.map(
        lambda p: NamedSharding(mesh, spec_for(p.axes, p.shape, mesh, rules)),
        specs,
        is_leaf=is_param,
    )


@dataclasses.dataclass(frozen=True)
class Axes:
    """Helper to annotate an activation with logical axes inside model code."""

    names: tuple

    def spec(self, shape, mesh, rules) -> P:
        return spec_for(self.names, shape, mesh, rules)


def constrain(x: jax.Array, axes: Sequence[Optional[str]], rules: Optional[dict] = None):
    """with_sharding_constraint via logical axes; no-op outside a mesh ctx."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:  # pragma: no cover - outside jit/mesh
        return x
    r = merge_rules(rules)
    spec = spec_for(axes, x.shape, mesh, r)
    return jax.lax.with_sharding_constraint(x, spec)
