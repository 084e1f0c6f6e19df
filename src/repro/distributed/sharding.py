"""Logical-axis sharding helpers.

Models annotate activations with *logical* axes ("batch", "model", ...) via
:func:`shard`; the launcher installs the physical mesh with
:func:`set_current_mesh`.  Outside a mesh (CPU smoke tests) every annotation
is a no-op, so model code is identical on 1 device and 512.
"""
from __future__ import annotations

import threading
from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

_state = threading.local()


def set_current_mesh(mesh: Optional[Mesh]) -> None:
    _state.mesh = mesh


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def batch_axes(mesh: Optional[Mesh] = None):
    """Physical axes the global batch is sharded over ("pod" + "data")."""
    mesh = mesh or current_mesh()
    if mesh is None:
        return None
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return axes if axes else None


def _normalize(axes):
    """Canonical pspec entry: 1-tuples become the bare axis name, so
    PartitionSpec equality matches hand-written specs."""
    if isinstance(axes, tuple) and len(axes) == 1:
        return axes[0]
    return axes


def _resolve(entry: Any, mesh: Mesh) -> Any:
    """Map a logical entry to physical mesh axes (or None)."""
    if entry is None:
        return None
    if entry == "batch":
        return _normalize(batch_axes(mesh))
    if entry == "model":
        return "model" if "model" in mesh.axis_names else None
    if isinstance(entry, tuple):
        out = []
        for e in entry:
            r = _resolve(e, mesh)
            if isinstance(r, tuple):
                out.extend(r)
            elif r is not None:
                out.append(r)
        return _normalize(tuple(out)) if out else None
    return entry if entry in mesh.axis_names else None


def resolve_pspec(entries: tuple) -> PartitionSpec:
    mesh = current_mesh()
    if mesh is None:
        return PartitionSpec()
    return PartitionSpec(*(_resolve(e, mesh) for e in entries))


def shard(x, *entries):
    """with_sharding_constraint by logical axes; no-op without a mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = resolve_pspec(entries)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _axes_size(mesh: Mesh, resolved) -> int:
    if resolved is None:
        return 1
    if isinstance(resolved, tuple):
        n = 1
        for a in resolved:
            n *= mesh.shape[a]
        return n
    return mesh.shape[resolved]


def named_sharding(mesh: Mesh, entries: tuple, shape: Optional[tuple] = None) -> NamedSharding:
    """Resolve logical pspec entries against a concrete mesh.

    When ``shape`` is given, entries whose mesh-axis product does not divide
    the dim are dropped (e.g. a batch-sharded dim of size 1 in long_500k, or
    8 kv heads on a 16-way model axis) — replication instead of failure.
    """
    resolved = [_resolve(e, mesh) for e in entries]
    if shape is not None:
        for i, r in enumerate(resolved):
            if r is not None and i < len(shape) and shape[i] % _axes_size(mesh, r) != 0:
                resolved[i] = None
    return NamedSharding(mesh, PartitionSpec(*resolved))


def spec_tree_shardings(spec_tree, mesh: Mesh):
    """Spec tree -> NamedSharding tree (for jit in_/out_shardings)."""
    from repro.models.params import tree_map_specs

    return tree_map_specs(lambda s: named_sharding(mesh, tuple(s.pspec), s.shape), spec_tree)


def entry_tree_shardings(entry_tree, mesh: Mesh, abstract_tree=None):
    """Tree of logical pspec-entry tuples -> NamedSharding tree.

    ``abstract_tree``: optional matching tree of ShapeDtypeStructs for
    divisibility-aware resolution."""
    is_leaf = lambda x: isinstance(x, tuple)  # noqa: E731
    if abstract_tree is None:
        return jax.tree_util.tree_map(
            lambda e: named_sharding(mesh, tuple(e)), entry_tree, is_leaf=is_leaf
        )
    return jax.tree_util.tree_map(
        lambda e, a: named_sharding(mesh, tuple(e), tuple(a.shape)),
        entry_tree,
        abstract_tree,
        is_leaf=is_leaf,
    )


def maybe_axis(logical: str, dim_size: int, par: int) -> Optional[str]:
    """Use a sharded axis only when the dim divides evenly (e.g. 56 heads on a
    16-way model axis do NOT shard; head_dim 128 does)."""
    return logical if par > 0 and dim_size % max(par, 1) == 0 and par > 1 else None
