"""Logical-axis sharding rules with divisibility fallbacks.

Every parameter and activation carries *logical* axis names; this module maps
them onto whatever mesh is in scope.  The mapping degrades gracefully: a
logical axis whose dimension does not divide the assigned mesh axes is left
replicated (e.g. yi-34b's 56 q-heads on a 16-way `model` axis), and the model
layer then falls back to its alternative parallelism (context parallelism for
attention, expert-TP for MoE) — decided once per config in
:func:`repro.models.model.resolve_parallelism`.

Logical axes:
    batch   -> (pod, data)   data parallel (pod axis only on multi-pod meshes)
    seq     -> model          sequence / context parallelism at layer bounds
    tp      -> model          tensor parallel (heads, d_ff, vocab, experts,
                              butterfly block-diagonals)
    fsdp    -> data           ZeRO-3 parameter sharding
    expert  -> model          expert parallelism
    pages   -> pages          the paged KV pool's page axis (serve meshes
                              only; absent axis -> pools replicate, which is
                              the single-chip behaviour)
    None    -> replicated
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "RULES",
    "ParamSpec",
    "spec_for",
    "sharding_for",
    "constrain",
    "init_tree",
    "abstract_tree",
    "sharding_tree",
]


# logical axis -> candidate mesh axes (in priority order; all present ones used)
RULES: dict[str | None, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": ("model",),
    "tp": ("model",),
    "fsdp": ("data",),
    "expert": ("model",),
    "vocab": ("model",),
    "pages": ("pages",),
    None: (),
}

# pure data parallelism: no TP — batch spreads over the model axis too and
# parameters FSDP over both axes.  The right regime for small models where
# TP collectives dwarf compute (mamba2-130m hillclimb, §Perf).
RULES_PURE_DP: dict[str | None, tuple[str, ...]] = {
    "batch": ("pod", "data", "model"),
    "seq": (),
    "tp": (),
    "fsdp": ("data", "model"),
    "expert": (),
    "vocab": ("model",),
    "pages": ("pages",),
    None: (),
}


def _mesh_axis_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def spec_for(
    shape: Sequence[int],
    axes: Sequence[str | None],
    mesh: Mesh,
    rules: dict | None = None,
) -> P:
    """PartitionSpec for `shape` under logical `axes`, with divisibility
    fallback (non-dividing dims replicate) and no mesh axis used twice."""
    rules = rules or RULES
    sizes = _mesh_axis_sizes(mesh)
    used: set[str] = set()
    out: list[Any] = []
    assert len(shape) == len(axes), (shape, axes)
    for dim, logical in zip(shape, axes):
        cands = [
            a
            for a in rules.get(logical, ())
            if a in sizes and a not in used
        ]
        take: list[str] = []
        prod = 1
        for a in cands:
            if dim % (prod * sizes[a]) == 0:
                take.append(a)
                prod *= sizes[a]
        if take:
            used.update(take)
            out.append(tuple(take) if len(take) > 1 else take[0])
        else:
            out.append(None)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def sharding_for(
    shape: Sequence[int], axes: Sequence[str | None], mesh: Mesh, rules: dict | None = None
) -> NamedSharding:
    return NamedSharding(mesh, spec_for(shape, axes, mesh, rules))


def constrain(
    x: jax.Array,
    axes: Sequence[str | None],
    mesh: Mesh | None,
    rules: dict | None = None,
) -> jax.Array:
    """with_sharding_constraint under logical axes; no-op without a mesh or on
    a single-device mesh (keeps smoke tests free of sharding machinery)."""
    if mesh is None or math.prod(mesh.devices.shape) == 1:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec_for(x.shape, axes, mesh, rules))
    )


# --------------------------------------------------------------------------
# Parameter specs — single source of truth for shapes, init and sharding.
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"  # normal | zeros | ones
    scale: float | None = None  # None -> 1/sqrt(fan_in = shape[-2] or [-1])

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)

    def initializer(self) -> Callable[[jax.Array, Any], jax.Array]:
        if self.init == "zeros":
            return lambda k, dt: jnp.zeros(self.shape, dt)
        if self.init == "ones":
            return lambda k, dt: jnp.ones(self.shape, dt)
        scale = self.scale
        if scale is None:
            fan_in = self.shape[-2] if len(self.shape) >= 2 else self.shape[-1]
            scale = 1.0 / math.sqrt(max(fan_in, 1))
        return lambda k, dt: (jax.random.normal(k, self.shape, jnp.float32) * scale).astype(dt)


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def init_tree(specs, key: jax.Array, dtype=jnp.float32):
    """Materialise a parameter pytree from a ParamSpec tree (deterministic:
    keys are folded from the flattened path order)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    vals = [s.initializer()(k, dtype) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_tree(specs, dtype=jnp.float32):
    """ShapeDtypeStruct tree (for dry-run lowering — no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype), specs, is_leaf=_is_spec
    )


def sharding_tree(specs, mesh: Mesh, rules: dict | None = None):
    return jax.tree.map(
        lambda s: sharding_for(s.shape, s.axes, mesh, rules), specs, is_leaf=_is_spec
    )


def data_shardings(tree, mesh: Mesh):
    """Batch-dim-0 shardings for an input batch tree (ShapeDtypeStructs or
    arrays); falls back to replicated when the batch doesn't divide (e.g. the
    long_500k single-sequence decode)."""
    return jax.tree.map(
        lambda s: sharding_for(s.shape, ("batch",) + (None,) * (len(s.shape) - 1), mesh),
        tree,
    )
