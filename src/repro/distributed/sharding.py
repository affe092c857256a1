"""Logical-axis -> mesh-axis mapping (FSDP / TP / EP / SP).

The model substrate annotates every parameter dim with a *logical* axis name
(see models/params.py). This module turns those names into
``jax.sharding.PartitionSpec`` against a concrete mesh, with divisibility
fallbacks: a logical axis is only mapped onto a mesh axis when the dim size is
divisible by the mesh-axis size; otherwise the dim is replicated. That keeps a
single production mesh (16x16 or 2x16x16) valid for every assigned arch — the
9-head arch simply replicates its attention weights where the 128-head arch
tensor-parallelizes them (the roofline table then shows the cost, which is the
honest outcome).

Rule sets are small data, so per-arch overrides and hillclimb variants are
plain dicts (see configs/*.py and EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import jax
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec as P

PyTree = Any


# -- mesh-level wrappers ----------------------------------------------------

def shard_map(f, mesh: Mesh, in_specs, out_specs, *,
              axis_names=None, check_vma: bool = False):
    """Partially-manual shard_map: manual over ``axis_names`` only."""
    kw = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)


def mesh_context(mesh: Mesh):
    """Ambient-mesh context manager."""
    return jax.set_mesh(mesh)


def abstract_mesh(sizes: Sequence[int], names: Sequence[str]):
    """Device-free AbstractMesh (rule evaluation without devices)."""
    return AbstractMesh(tuple(sizes), tuple(names))


# Baseline rules: logical axis -> mesh axis (or tuple of mesh axes), None = replicate.
# FSDP shards the model dimension over 'data'; TP shards vocab/heads/mlp/expert
# over 'model'. 'pod' stays pure DP for params (no cross-pod param collectives
# on the slow DCI link).
DEFAULT_RULES: dict[str, Any] = {
    "vocab": "model",
    "embed": "data",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "expert": "model",
    "e_mlp": None,
    "layers": None,
    "lora": None,
    "state": None,
    "conv": None,
    None: None,
}

# Hillclimb variant: fully-sharded params over both axes (zero-1 style).
FSDP_TP_RULES = dict(DEFAULT_RULES)

# Variant for small models where TP is wasteful: everything FSDP over the
# flattened ('data','model') axes pair on the largest dim, batch over the
# whole mesh (pure data parallel + ZeRO-3). Kills both the model-axis
# compute redundancy (useful-flops ratio) and the Megatron activation
# all-reduces; collectives become per-layer weight all-gathers only.
PURE_DP_RULES = dict(
    DEFAULT_RULES,
    vocab=("data", "model"),
    embed=("data", "model"),
    heads=None,
    kv_heads=None,
    mlp=None,
    expert=None,
)

RULE_SETS = {
    "default": DEFAULT_RULES,
    "pure_dp": PURE_DP_RULES,
}


def batch_over_model(rules) -> bool:
    """pure_dp rules want activations batch-sharded over 'model' too."""
    return rules is PURE_DP_RULES or rules == PURE_DP_RULES


def _axes_sizes(mesh: Mesh) -> dict[str, int]:
    # Mesh.shape / AbstractMesh.shape are both axis-name -> size mappings,
    # so rule evaluation works without real devices (tests use AbstractMesh).
    return dict(mesh.shape)


def _resolve_dim(dim: int, logical: str | None, rules: Mapping, mesh_sizes: dict):
    """Map one logical dim to mesh axes, dropping axes that don't divide."""
    target = rules.get(logical, None)
    if target is None:
        return None
    if isinstance(target, str):
        target = (target,)
    kept = []
    prod = 1
    for ax in target:
        size = mesh_sizes.get(ax, 1)
        if dim % (prod * size) == 0:
            kept.append(ax)
            prod *= size
    if not kept:
        return None
    return tuple(kept) if len(kept) > 1 else kept[0]


def spec_for(shape: Sequence[int], axes: Sequence[str | None], rules: Mapping,
             mesh: Mesh) -> P:
    sizes = _axes_sizes(mesh)
    used: set[str] = set()
    parts = []
    for dim, logical in zip(shape, axes):
        resolved = _resolve_dim(dim, logical, rules, sizes)
        # one mesh axis may appear at most once in a PartitionSpec
        if resolved is not None:
            flat = (resolved,) if isinstance(resolved, str) else resolved
            flat = tuple(a for a in flat if a not in used)
            if not flat:
                resolved = None
            else:
                used.update(flat)
                resolved = flat if len(flat) > 1 else flat[0]
        parts.append(resolved)
    return P(*parts)


def tree_partition_specs(abstract_tree: PyTree, axes_tree: PyTree, rules: Mapping,
                         mesh: Mesh) -> PyTree:
    """PartitionSpec pytree for a param tree (abstract or concrete)."""
    return jax.tree.map(
        lambda leaf, axes: spec_for(leaf.shape, axes, rules, mesh),
        abstract_tree,
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(a, (str, type(None))) for a in x),
    )


def tree_shardings(abstract_tree: PyTree, axes_tree: PyTree, rules: Mapping,
                   mesh: Mesh) -> PyTree:
    specs = tree_partition_specs(abstract_tree, axes_tree, rules, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)


# ---------------------------------------------------------------------------
# Activation constraints. Inside jitted step functions we pin the key
# activation tensors; XLA propagates the rest.
# ---------------------------------------------------------------------------

def dp_axes(mesh: Mesh) -> tuple:
    """Mesh axes used for the batch dim: ('pod','data') when pod exists."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def constrain(x, *parts):
    """with_sharding_constraint that is a no-op outside a mesh context."""
    try:
        return jax.lax.with_sharding_constraint(x, P(*parts))
    except (ValueError, RuntimeError):
        return x


def batch_spec(mesh: Mesh, batch: int, extra_model: bool = False) -> P:
    """PartitionSpec for (batch, seq, ...) activations.

    When the per-(pod,data) batch still divides over 'model' and the arch policy
    asks for it (pure-DP small models), the batch dim may also take 'model'.
    """
    axes = list(dp_axes(mesh))
    sizes = _axes_sizes(mesh)
    prod = 1
    kept = []
    for a in axes:
        if batch % (prod * sizes[a]) == 0:
            kept.append(a)
            prod *= sizes[a]
    if extra_model and "model" in sizes and batch % (prod * sizes["model"]) == 0:
        kept.append("model")
    if not kept:
        return P()
    return P(tuple(kept) if len(kept) > 1 else kept[0])
