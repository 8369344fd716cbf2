"""Pytree-as-vector math.

FedOSAA's Anderson-acceleration step is linear algebra over the *flattened*
parameter vector, but flattening billion-parameter pytrees into one array
destroys sharding and wastes memory. Everything here operates leaf-wise so
that sharded pytrees stay sharded; reductions (dot products, norms) compile
to per-leaf reduces + a scalar psum under pjit.
"""
from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

Pytree = Any


def _sum_leaves(leaves: Sequence[jax.Array]) -> jax.Array:
    """Sum same-shape per-leaf reductions with one stacked jnp.sum.

    ``functools.reduce(jnp.add, leaves)`` builds an O(n_leaves)-deep chain of
    binary adds — at 70+-leaf transformer scale (configs/ registry) that is a
    long sequential dependency XLA cannot reassociate. Stacking into one
    [n_leaves, ...] array and reducing axis 0 gives a single balanced reduce.
    """
    if len(leaves) == 1:
        return leaves[0]
    return jnp.sum(jnp.stack(leaves), axis=0)


def tree_add(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(alpha, a: Pytree) -> Pytree:
    return jax.tree.map(lambda x: alpha * x, a)


def tree_axpy(alpha, x: Pytree, y: Pytree) -> Pytree:
    """alpha * x + y, leaf-wise."""
    return jax.tree.map(lambda xi, yi: alpha * xi + yi, x, y)


def _acc(x: jax.Array) -> jax.Array:
    """Cast to the accumulation dtype: at least f32 (bf16/f16 widen), and
    f64 stays f64 — an f64 run's AA Gram must not round through f32, whose
    noise the Gram solve amplifies by its condition number."""
    return x.astype(jnp.promote_types(x.dtype, jnp.float32))


def tree_dot(a: Pytree, b: Pytree) -> jax.Array:
    """<a, b> over all leaves, accumulated in at least f32."""
    leaves = jax.tree.map(lambda x, y: jnp.sum(_acc(x) * _acc(y)), a, b)
    return _sum_leaves(jax.tree.leaves(leaves))


def tree_vdot_stacked(stack: Pytree, v: Pytree) -> jax.Array:
    """Given a pytree whose leaves carry a leading history axis [m, ...] and a
    plain pytree v, return the length-m vector of dot products  stackᵀ v.

    Sharding note (§Perf, AA-step iteration): contraction uses tensordot over
    the original axes — NOT reshape-to-flat — so sharded leaves contract
    locally and only the [m] result is psum'd. A reshape across a sharded
    dim would force an all-gather of the whole stack (measured: 157 GiB per
    AA step at qwen3-4b scale).
    """
    def leaf(s, x):
        axes = list(range(1, s.ndim))
        return jnp.tensordot(_acc(s), _acc(x),
                             axes=(axes, list(range(x.ndim))))

    leaves = jax.tree.leaves(jax.tree.map(leaf, stack, v))
    return _sum_leaves(leaves)


def tree_gram(stack_a: Pytree, stack_b: Pytree) -> jax.Array:
    """[m, m] Gram matrix  AᵀB  between two stacked pytrees (leading axis m).
    Axis-preserving contraction — see tree_vdot_stacked sharding note."""
    def leaf(a, b):
        axes = list(range(1, a.ndim))
        return jnp.tensordot(_acc(a), _acc(b), axes=(axes, axes))

    leaves = jax.tree.leaves(jax.tree.map(leaf, stack_a, stack_b))
    return _sum_leaves(leaves)


def tree_combine_stacked(stack: Pytree, coeff: jax.Array) -> Pytree:
    """Σ_i coeff[i] * stack[i]  — contraction of the history axis."""
    def leaf(s):
        sa = _acc(s)
        return jnp.tensordot(coeff.astype(sa.dtype), sa, axes=1).astype(s.dtype)

    return jax.tree.map(leaf, stack)


def tree_norm(a: Pytree) -> jax.Array:
    return jnp.sqrt(tree_dot(a, a))


def tree_zeros_like(a: Pytree) -> Pytree:
    return jax.tree.map(jnp.zeros_like, a)


def tree_stack(trees: Sequence[Pytree]) -> Pytree:
    """Stack a python list of pytrees into one pytree with leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def tree_unstack_index(stack: Pytree, i) -> Pytree:
    return jax.tree.map(lambda s: s[i], stack)


def tree_dynamic_update(stack: Pytree, i, value: Pytree) -> Pytree:
    """stack[i] = value (dynamic index), for scan-friendly history buffers."""
    return jax.tree.map(
        lambda s, v: jax.lax.dynamic_update_index_in_dim(s, v.astype(s.dtype), i, 0),
        stack,
        value,
    )


def tree_cast(a: Pytree, dtype) -> Pytree:
    return jax.tree.map(lambda x: x.astype(dtype), a)


def tree_size(a: Pytree) -> int:
    return sum(x.size for x in jax.tree.leaves(a))


def tree_where(pred, a: Pytree, b: Pytree) -> Pytree:
    """Leaf-wise select; leaves that are the SAME object in a and b pass
    through untouched (no select op). ServerState._replace preserves object
    identity of unchanged fields, so the engine's live/stop select never
    drags pass-through state — e.g. the frozen [K, ...] client store rows of
    a cohort round — into the compiled graph."""
    return jax.tree.map(lambda x, y: x if x is y else jnp.where(pred, x, y), a, b)


def tree_random_like(key: jax.Array, a: Pytree, scale: float = 1.0) -> Pytree:
    leaves, treedef = jax.tree.flatten(a)
    keys = jax.random.split(key, len(leaves))
    new = [
        jax.random.normal(k, x.shape, x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.float32) * scale
        for k, x in zip(keys, leaves)
    ]
    return jax.tree.unflatten(treedef, new)


def global_norm(a: Pytree) -> jax.Array:
    return tree_norm(a)


def tree_map_with_path_filter(
    fn: Callable, tree: Pytree, predicate: Callable[[tuple], bool]
) -> Pytree:
    """Apply fn only to leaves whose key-path satisfies predicate."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: fn(leaf) if predicate(path) else leaf, tree
    )
