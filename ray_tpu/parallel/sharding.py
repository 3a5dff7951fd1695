"""Logical-axis sharding rules → GSPMD PartitionSpecs.

This is where the framework's parallelisms (SURVEY.md §2.4) become XLA
shardings: parameters/activations carry *logical* axis names and a rule
table maps them onto mesh axes. XLA's GSPMD partitioner then inserts
the collectives the reference would have issued through NCCL.

Default rule table (transformer nomenclature):

    batch   → (dp, fsdp)     activations data-parallel
    seq     → sp             sequence/context parallelism
    embed   → fsdp (params)  ZeRO-3-style parameter sharding
    heads   → tp             attention-head tensor parallelism
    mlp     → tp             feed-forward tensor parallelism
    vocab   → tp             embedding/logit sharding
    expert  → ep→(sp,tp)     MoE expert parallelism
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Rules = Dict[str, Union[str, Tuple[str, ...], None]]

#: Parameter rules — fsdp shards the embed dim of weights (ZeRO-3).
#: Params are REPLICATED across dcn_dp (pure DP between slices: the
#: only cross-slice traffic GSPMD then inserts is the per-step
#: gradient all-reduce, which is what DCN can afford).
PARAM_RULES: Rules = {
    "batch": ("dcn_dp", "dp", "fsdp"),
    "seq": None,
    "embed": "fsdp",
    "heads": "tp",
    "kv_heads": "tp",
    "mlp": "tp",
    "vocab": "tp",
    "expert": None,
    "layers": None,
    "head_dim": None,
}

#: Activation rules — batch over data axes, seq over sp, heads over tp.
ACT_RULES: Rules = {
    "batch": ("dcn_dp", "dp", "fsdp"),
    "seq": "sp",
    "embed": None,
    "heads": "tp",
    "kv_heads": "tp",
    "mlp": "tp",
    "vocab": "tp",
    "expert": None,
    "head_dim": None,
}


def spec_for(logical_axes: Sequence[Optional[str]], rules: Rules) -> P:
    """Map a tuple of logical axis names to a PartitionSpec."""
    parts = []
    for name in logical_axes:
        if name is None:
            parts.append(None)
        else:
            parts.append(rules.get(name))
    return P(*parts)


def named_sharding(
    mesh: Mesh, logical_axes: Sequence[Optional[str]], rules: Rules
) -> NamedSharding:
    return NamedSharding(mesh, spec_for(logical_axes, rules))


@dataclass(frozen=True)
class Annotated:
    """A leaf annotation: array shape dims ↔ logical axis names."""

    logical_axes: Tuple[Optional[str], ...]


def annotate(*logical_axes: Optional[str]) -> Annotated:
    return Annotated(tuple(logical_axes))


def tree_shardings(
    mesh: Mesh, annotations: Any, rules: Rules
) -> Any:
    """Map a pytree of `Annotated` (or None) to NamedShardings."""

    def leaf(a):
        if isinstance(a, Annotated):
            return named_sharding(mesh, a.logical_axes, rules)
        return NamedSharding(mesh, P())

    return jax.tree.map(
        leaf, annotations, is_leaf=lambda x: isinstance(x, Annotated) or x is None
    )


def shard_tree(mesh: Mesh, tree: Any, annotations: Any, rules: Rules) -> Any:
    """Device-put a pytree according to its annotations."""
    shardings = tree_shardings(mesh, annotations, rules)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, s), tree, shardings
    )


def with_constraint(x, mesh: Mesh, logical_axes, rules: Rules):
    """In-jit sharding constraint by logical names."""
    return jax.lax.with_sharding_constraint(
        x, named_sharding(mesh, logical_axes, rules)
    )


def checked_shard_map(f, mesh: Mesh, in_specs, out_specs):
    """`jax.shard_map` over an explicit mesh with the replication
    (varying-manual-axes) check ON: psum-derived scalars are proven
    replicated, so `out_specs=P()` is a checked claim. One spelling of
    the call for the pipeline, MoE, ring-attention and sharded flash
    paths."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs
    )
