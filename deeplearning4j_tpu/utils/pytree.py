"""Flat-parameter-view utilities.

The reference keeps ALL network parameters in one flat buffer with per-layer views
(reference MultiLayerNetwork.flattenedParams:100, init:386) — updaters, parameter
averaging, and serialization all operate on that 1-D view. In JAX the natural
representation is a pytree; these helpers provide the same flat view on demand
(for ParallelWrapper-style averaging, checkpoint compatibility, and the `params()` /
`set_params()` API), with a deterministic ordering.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def flatten_params(tree: Any, dtype=None) -> Array:
    """Concatenate all leaves into one 1-D float vector (deterministic pytree order).
    dtype=None keeps the leaves' promoted dtype (float64 under enable_x64 for
    gradient checks); pass jnp.float32 for the standard flat view."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((0,), dtype or jnp.float32)
    if dtype is None:
        dtype = jnp.result_type(*leaves)
    return jnp.concatenate([jnp.ravel(l).astype(dtype) for l in leaves])


def unflatten_params(tree_like: Any, flat: Array) -> Any:
    """Inverse of flatten_params given a structure/shape template."""
    leaves, treedef = jax.tree_util.tree_flatten(tree_like)
    out = []
    pos = 0
    for l in leaves:
        n = int(np.prod(l.shape)) if l.shape else 1
        out.append(jnp.reshape(flat[pos:pos + n], l.shape).astype(l.dtype))
        pos += n
    if pos != flat.shape[0]:
        raise ValueError(f"Flat vector length {flat.shape[0]} != param count {pos}")
    return jax.tree_util.tree_unflatten(treedef, out)


def num_params(tree: Any) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree))


def tree_average(trees: list) -> Any:
    """Elementwise average of identically-structured pytrees (parameter averaging,
    reference Nd4j.averageAndPropagate at ParallelWrapper.java:179)."""
    return jax.tree_util.tree_map(lambda *xs: sum(xs) / len(xs), *trees)
