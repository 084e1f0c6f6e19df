"""Parameter-definition trees.

A model is described once as a nested dict of :class:`Spec` leaves; from that
single description we derive (a) materialized arrays for smoke tests /
examples, (b) ``ShapeDtypeStruct`` trees for the dry-run (no allocation), and
(c) ``PartitionSpec`` trees for pjit in/out shardings.  Keeping the three in
one tree makes it impossible for shapes and shardings to drift apart.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec


@dataclasses.dataclass(frozen=True)
class Spec:
    """One parameter leaf: shape + partition entries + init recipe."""

    shape: tuple[int, ...]
    # One entry per dim: None (replicated) or a mesh-axis name ("model").
    pspec: tuple[Any, ...] = ()
    init: str = "normal"  # normal | zeros | ones | neg_ones | small_normal | lambda_init
    scale: float | None = None  # stddev override for normal init
    dtype: str | None = None  # per-leaf dtype override (e.g. int32 cache pos)


def _is_spec(x) -> bool:
    return isinstance(x, Spec)


def tree_map_specs(fn, tree):
    return jax.tree_util.tree_map(fn, tree, is_leaf=_is_spec)


def stack_layers(n_layers: int, tree):
    """Prepend a layer dim (for scan-over-layers stacked params)."""

    def add_dim(s: Spec) -> Spec:
        return Spec((n_layers,) + s.shape, (None,) + tuple(s.pspec), s.init, s.scale, s.dtype)

    return tree_map_specs(add_dim, tree)


def abstract(tree, dtype) -> Any:
    """ShapeDtypeStruct tree — used by the dry-run, never allocates."""

    def mk(s: Spec):
        return jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype or dtype))

    return tree_map_specs(mk, tree)


def pspecs(tree) -> Any:
    def mk(s: Spec):
        return PartitionSpec(*s.pspec) if s.pspec else PartitionSpec()

    return tree_map_specs(mk, tree)


def n_params(tree) -> int:
    total = 0
    for s in jax.tree_util.tree_leaves(tree, is_leaf=_is_spec):
        n = 1
        for d in s.shape:
            n *= d
        total += n
    return total


def materialize(tree, key, dtype):
    """Materialize real arrays (smoke tests / examples only)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))

    def mk(s: Spec, k):
        dt = jnp.dtype(s.dtype or dtype)
        if s.init == "zeros":
            return jnp.zeros(s.shape, dt)
        if s.init == "ones":
            return jnp.ones(s.shape, dt)
        if s.init == "neg_ones":
            return jnp.full(s.shape, -1, dt)
        if s.init == "lambda_init":
            # RG-LRU Lambda parametrization: softplus-inverse of decay in
            # (0.9, 0.999); stored pre-activation.
            u = jax.random.uniform(k, s.shape, jnp.float32, 0.9, 0.999)
            lam = -jnp.log(jnp.expm1(-jnp.log(u)))  # inverse of a = exp(-softplus(lam))
            return lam.astype(dt)
        scale = s.scale
        if scale is None:
            fan_in = s.shape[-2] if len(s.shape) >= 2 else s.shape[-1]
            scale = fan_in ** -0.5
        return _normal(k, s.shape, scale, dt)

    out = [mk(s, k) for s, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, out)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, scale, dtype):
    # One program per leaf, so only the cast result is ever held: a float32
    # draw of a whole layer stack would need twice the weights' bytes again.
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)
