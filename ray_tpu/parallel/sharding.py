"""Logical-axis sharding rules: PartitionSpecs from semantic axis names.

Model code annotates arrays with *logical* axis names ("embed", "heads",
"batch", "seq", ...); a rules table maps logical names to mesh axes.  This is
the mechanism by which one model definition serves every parallelism layout —
swap the rules, not the model.  (The reference has no equivalent; it defers
per-strategy partitioning to torch/vLLM.  Here it is the core design, per
SURVEY.md §7.)
"""

from __future__ import annotations

from typing import Optional, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Default rules for transformer LMs.  Values are mesh axis names (or tuples
# thereof), None = replicated.  The dcn (multi-slice) axis carries plain
# data parallelism: batch splits across slices over DCN while every other
# collective stays on intra-slice ICI (SURVEY §2.5 TPU-native mapping).
DEFAULT_RULES: dict[str, Union[str, tuple, None]] = {
    "batch": ("dcn", "dp", "fsdp"),
    "seq": "sp",           # sequence/context parallelism
    "embed": "fsdp",       # ZeRO-style param sharding
    "heads": "tp",
    "kv_heads": "tp",
    "head_dim": None,
    "mlp": "tp",
    "vocab": "tp",
    "experts": "ep",
    "expert_mlp": "tp",
    "stage": "pp",
    "norm": None,
    "layers": None,        # stacked-layer scan dim: lax.scan carries it,
                           # sharding it would split the scan carry
}

# Spec-entry spelling for intentional replication, alongside plain None.
REPLICATED = "replicated"


def logical_spec(*names: Optional[str]) -> tuple:
    """A logical partition spec: tuple of logical axis names (None or
    ``"replicated"`` = replicated on purpose)."""
    return tuple(names)


def to_partition_spec(logical: tuple, rules: Optional[dict] = None) -> P:
    """Map a logical spec through a rules table to a ``PartitionSpec``.

    An axis name absent from the rules raises: silently replicating a
    typo'd name costs memory and comm without any error, which is the
    worst possible failure mode for a layout knob.  Spell intentional
    replication ``None`` or ``"replicated"`` in the spec, or add a
    ``name: None`` rule.
    """
    rules = DEFAULT_RULES if rules is None else rules
    axes = []
    for name in logical:
        if name is None or name == REPLICATED:
            axes.append(None)
        elif name in rules:
            axes.append(rules[name])
        else:
            raise ValueError(
                f"unknown logical axis {name!r}: not in the sharding rules "
                f"(known: {sorted(rules)}). Use None or 'replicated' for "
                "intentional replication, or add a rule for it.")
    return P(*axes)


def tree_partition_specs(logical_tree, rules: Optional[dict] = None):
    """Map a pytree of logical specs to a pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda spec: to_partition_spec(spec, rules),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def named_shardings(logical_tree, mesh: Mesh, rules: Optional[dict] = None):
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, to_partition_spec(spec, rules)),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def shard_tree(tree, logical_tree, mesh: Mesh, rules: Optional[dict] = None):
    """Device-put a pytree according to its logical specs."""
    shardings = named_shardings(logical_tree, mesh, rules)
    return jax.tree.map(jax.device_put, tree, shardings)
