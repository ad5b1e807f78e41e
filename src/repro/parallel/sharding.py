"""Logical-axis sharding rules (MaxText-style) → PartitionSpec/NamedSharding.

Model code annotates arrays with *logical* axis names; one rule table maps
them onto physical mesh axes. Changing the parallelism layout = changing
this table, not the model.

Default table (DESIGN.md §5), meshes ("data","model") or ("pod","data","model"):

    batch    -> (pod, data)     DP
    embed    -> data            FSDP / ZeRO-3 param shard dim
    heads    -> model           TP
    kv_heads -> model           TP
    mlp      -> model           TP
    experts  -> model           EP
    vocab    -> model           TP (output projection / embedding column)
    seq_kv   -> data            SP for long-context decode
    table    -> model           recsys embedding-table rows
    edges    -> data            GNN edge partition
    nodes    -> data            GNN node partition
    (unknown/None)              replicated
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "RULES", "logical_to_spec", "named_sharding", "shard_put",
    "tree_shardings",
]

PyTree = Any


def shard_put(arr: jax.Array, mesh: Mesh, spec: P) -> jax.Array:
    """Pin an array onto a mesh with an explicit PartitionSpec, once.

    The resident-data idiom behind segment placement (``engine/placement``):
    corpus slabs are ``shard_put`` at placement-build time, so per-query
    ``shard_map`` calls whose ``in_specs`` match find the bytes already on
    their devices — the per-query cross-device traffic drops to the
    replicated queries in and the O(k) partials out.
    """
    return jax.device_put(arr, NamedSharding(mesh, spec))


RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "embed": ("data",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "experts": ("model",),
    "expert_ff": (),  # decode rules map this to ("data",): resident EP+TP
    "vocab": ("model",),
    "seq_kv": ("data",),
    "table": ("model",),
    "table_in": ("data",),
    "edges": ("data",),
    "nodes": ("data",),
}


def logical_to_spec(
    logical: Sequence[Optional[str]], mesh: Mesh, rules: Optional[Dict] = None
) -> P:
    """('batch', None, 'heads', ...) -> PartitionSpec, dropping axes the mesh
    lacks (so one table serves single-pod and multi-pod meshes)."""
    rules = rules or RULES
    axes = []
    used: set = set()
    for name in logical:
        if name is None or name not in rules:
            axes.append(None)
            continue
        present = tuple(a for a in rules[name] if a in mesh.axis_names and a not in used)
        used.update(present)
        if not present:
            axes.append(None)
        elif len(present) == 1:
            axes.append(present[0])
        else:
            axes.append(present)
    return P(*axes)


def named_sharding(mesh: Mesh, *logical: Optional[str], rules: Optional[Dict] = None) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical, mesh, rules))


def tree_shardings(mesh: Mesh, logical_tree: PyTree, rules: Optional[Dict] = None) -> PyTree:
    """Map a pytree of logical-axis tuples to NamedShardings."""
    return jax.tree.map(
        lambda lg: named_sharding(mesh, *lg, rules=rules),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x),
    )
