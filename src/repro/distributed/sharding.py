"""Logical-axis sharding rules.

Params and activations are annotated with *logical* axis names; a rule table
maps logical names -> mesh axes per architecture family. The same model code
lowers on the single-pod (data, model) mesh, the multi-pod (pod, data, model)
mesh, and the 1-device CPU mesh used by smoke tests (all rules -> None).
"""
from __future__ import annotations

from typing import Mapping, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]

# ---------------------------------------------------------------------------
# Rule tables
# ---------------------------------------------------------------------------

# LM family. Two weight layouts resolved via ParamDef.axes / .serve_axes:
#   TRAIN  — FSDP: weights fully sharded over (pod, data, model) on one dim
#            ("fsdp"); activations token-sharded: batch->(pod,data) AND
#            seq->model (context parallelism), so GQA kv-head counts never
#            have to divide the mesh.
#   SERVE  — Megatron-TP: row-parallel inputs ("tp_in") / col-parallel ff
#            ("ff"), vocab->model, decode KV cache seq-sharded on model
#            (flash-decode split-K); long_500k shards KV seq on
#            (data, model) since batch=1.
LM_RULES: Mapping[str, MeshAxes] = {
    "batch": ("pod", "data"),
    "seq": "model",  # context-parallel tokens (train/prefill)
    "kv_seq": "model",  # decode split-K over the KV cache
    "long_kv_seq": ("data", "model"),  # 500k decode, batch=1
    "fsdp": ("pod", "data", "model"),
    "tp_in": "model",  # row-parallel contraction dim (serving)
    "ff": "model",  # col-parallel hidden dim (serving)
    "embed": None,
    "kv_heads": None,
    "head_dim": None,
    "vocab": "model",
    "experts": "model",
    "expert_dp": ("pod", "data"),  # FSDP dim of expert weights (EP train)
    "moe_in": "data",  # serve-time contraction dim of expert weights
    "expert_cap": ("pod", "data"),
    "layers": None,
    "rbf": None,
}

# RecSys: embedding-table rows are the memory -> shard rows on model;
# batch on (pod, data); dense towers replicated.
RECSYS_RULES: Mapping[str, MeshAxes] = {
    "batch": ("pod", "data"),
    "rows": "model",
    "embed": None,
    "seq": None,
    "fields": None,
    "mlp": None,
    "candidates": ("pod", "data", "model"),  # retrieval: 1M candidates, full mesh
    "layers": None,
}

# GNN: nodes/edges partitioned over (pod, data); channels on model at
# ogb_products scale (set by the launcher via rule override).
GNN_RULES: Mapping[str, MeshAxes] = {
    "nodes": ("pod", "data"),
    "edges": ("pod", "data"),
    "graphs": ("pod", "data"),
    "feat": None,
    "channels": None,
    "irrep": None,
    "rbf": None,
    "layers": None,
    "batch": ("pod", "data"),
}

FAMILY_RULES = {"lm": LM_RULES, "recsys": RECSYS_RULES, "gnn": GNN_RULES}


def adapt_rules(rules: Mapping[str, MeshAxes], mesh: Mesh) -> Mapping[str, MeshAxes]:
    """Drop mesh axes that don't exist on this mesh (e.g. 'pod' on 1-pod,
    everything on the 1-device test mesh). Also threads the mesh itself
    (under "__mesh__") for shard_map-based modules."""
    names = set(mesh.axis_names)

    def fix(ax: MeshAxes) -> MeshAxes:
        if ax is None:
            return None
        if isinstance(ax, str):
            return ax if ax in names else None
        kept = tuple(a for a in ax if a in names)
        return kept if kept else None

    out = {k: fix(v) for k, v in rules.items()}
    out["__mesh__"] = mesh  # type: ignore[assignment]
    return out


def pspec(axes: Sequence[Optional[str]], rules: Mapping[str, MeshAxes]) -> P:
    """logical axes tuple -> PartitionSpec via the rule table."""
    out = []
    used: set = set()

    def dedup(ax: MeshAxes) -> MeshAxes:
        # a mesh axis may appear at most once in a PartitionSpec
        if ax is None:
            return None
        if isinstance(ax, str):
            return None if ax in used else (used.add(ax) or ax)
        kept = tuple(a for a in ax if a not in used)
        used.update(kept)
        if not kept:
            return None
        # canonical form: when dedup collapses the rule to its trailing
        # (minor-most) axis, emit the bare axis rather than a 1-tuple; a
        # surviving leading axis keeps the tuple so the spec still shows
        # where the rule was truncated
        if len(kept) == 1 and kept[0] == ax[-1]:
            return kept[0]
        return kept

    for name in axes:
        if name is None:
            out.append(None)
            continue
        if name not in rules:
            raise KeyError(f"no sharding rule for logical axis {name!r}")
        out.append(dedup(rules[name]))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def named_sharding(mesh: Mesh, axes: Sequence[Optional[str]], rules) -> NamedSharding:
    return NamedSharding(mesh, pspec(axes, rules))


def constrain(x, axes: Sequence[Optional[str]], rules) -> jax.Array:
    """with_sharding_constraint by logical axes (no-op outside jit/mesh)."""
    try:
        return jax.lax.with_sharding_constraint(x, pspec(axes, rules))
    except (ValueError, RuntimeError):
        return x


def tree_pspecs(axes_tree, rules):
    """Map a pytree of logical-axes tuples to PartitionSpecs."""
    return jax.tree.map(
        lambda axes: pspec(axes, rules),
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x),
    )
