"""Logical-axis sharding rules with divisibility-aware fallback.

Model code annotates every parameter/activation with a tuple of *logical axis*
names (e.g. ``("layers", "embed", "heads")``).  :func:`logical_to_spec` resolves
those names against the active mesh through a rule table, dropping any mesh axis
that does not evenly divide the corresponding dimension (GSPMD rejects uneven
*input* shardings, so the fallback is replication on that axis — recorded in
DESIGN.md §5 for qwen1.5-32b / whisper / granite).
"""
from __future__ import annotations

from typing import Mapping, Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[str, tuple, None]

# Default logical->mesh rules.  "fsdp" and "tp" are *roles* resolved per mesh:
#   single pod : fsdp=("data",)      tp=("model",)
#   multi-pod  : fsdp=("pod","data") tp=("model",)   (pod as extra DP/FSDP dim)
# Activations use "batch" (data-parallel) and "seq_sp" (sequence parallelism
# over the tp axis between blocks).
LOGICAL_RULES: dict[str, str] = {
    # parameters
    "embed": "fsdp",         # d_model dim of weights: FSDP-sharded
    "heads": "tp",
    "kv_heads": "tp",
    "qkv": "tp",             # fused qkv output dim
    "ff": "tp",
    "vocab": "tp",
    "expert": "ep",          # expert axis (EP); falls back per-expert TP via "expert_ff"
    "expert_ff": "tp",
    "moe_cap": "dp_tp",      # MoE capacity dim: data axis (+ model when EP unused)
    "ssm_heads": "tp",
    "ssm_inner": "tp",
    "ssm_state": None,
    "layers": None,
    "stack": None,
    # activations
    "batch": "dp",
    "seq": None,
    "seq_sp": "tp",          # sequence-parallel activations between blocks
    "seq_kv": "tp",          # KV-cache sequence dim for long-context decode
    "act_embed": None,
    "frames": None,
}


def mesh_roles(mesh: Mesh) -> dict[str, tuple[str, ...]]:
    names = mesh.axis_names
    multi = "pod" in names
    dp = ("pod", "data") if multi else ("data",)
    return {
        "dp": dp,
        "fsdp": dp,
        "tp": ("model",),
        "ep": ("model",),
        "dp_tp": dp + ("model",),
    }


def _axis_size(mesh: Mesh, axes: tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def logical_to_spec(
    logical: Sequence[Optional[str]],
    shape: Sequence[int],
    mesh: Mesh,
    rules: Optional[Mapping[str, str]] = None,
) -> P:
    """Resolve logical axis names to a PartitionSpec, honoring divisibility."""
    rules = dict(LOGICAL_RULES, **(rules or {}))
    roles = mesh_roles(mesh)
    used: set[str] = set()
    spec: list[Axis] = []
    assert len(logical) == len(shape), (logical, shape)
    for name, dim in zip(logical, shape):
        role = rules.get(name) if name else None
        if role is None:
            spec.append(None)
            continue
        axes = roles[role]
        # never map the same mesh axis to two tensor dims
        axes = tuple(a for a in axes if a not in used)
        if not axes or dim % _axis_size(mesh, axes) != 0:
            # try a prefix that still divides (e.g. drop "pod" but keep "data")
            while axes and dim % _axis_size(mesh, axes) != 0:
                axes = axes[1:]
            if not axes:
                spec.append(None)
                continue
        used.update(axes)
        spec.append(axes[0] if len(axes) == 1 else tuple(axes))
    while spec and spec[-1] is None:
        spec.pop()
    return P(*spec)


def named_sharding(logical, shape, mesh, rules=None) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical, shape, mesh, rules))


def tree_shardings(logical_tree, shape_tree, mesh, rules=None):
    """Map a pytree of logical-axis tuples + matching ShapeDtypeStructs to shardings."""
    return jax.tree.map(
        lambda lg, sd: named_sharding(lg, sd.shape, mesh, rules),
        logical_tree,
        shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
    )


def _is_logical_leaf(x):
    return isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)


def tree_constraint(tree, logical_tree, mesh, rules=None):
    """with_sharding_constraint a whole pytree by a parallel logical-axis tree.

    Used on gradient trees: without it XLA may materialize full-size replicated
    gradients (observed: 1.6 GB f32 embedding grads all-reduced per microbatch)
    instead of reduce-scattering into the parameter sharding.
    """
    leaves, tdef = jax.tree.flatten(tree)
    logical = jax.tree.leaves(logical_tree, is_leaf=_is_logical_leaf)
    assert len(leaves) == len(logical), (len(leaves), len(logical))
    out = [
        jax.lax.with_sharding_constraint(
            x, named_sharding(lg, x.shape, mesh, rules))
        for x, lg in zip(leaves, logical)
    ]
    return jax.tree.unflatten(tdef, out)


# --- active-mesh context -----------------------------------------------------
# Model code calls constraint(x, logical) without threading a mesh through every
# layer; the step builders (train/serve/dryrun) install the mesh here.  When no
# mesh is active (unit tests on one device) constraints are a no-op.

_ACTIVE_MESH: list[Optional[Mesh]] = [None]


class use_mesh:
    """Context manager installing the active mesh for logical constraints."""

    def __init__(self, mesh: Optional[Mesh]):
        self.mesh = mesh

    def __enter__(self):
        _ACTIVE_MESH.append(self.mesh)
        return self.mesh

    def __exit__(self, *exc):
        _ACTIVE_MESH.pop()


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH[-1]


def constraint(x, logical, mesh=None, rules=None):
    """with_sharding_constraint by logical axes (no-op when no mesh active)."""
    mesh = mesh or active_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, named_sharding(logical, x.shape, mesh, rules))
