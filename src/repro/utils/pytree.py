"""Pytree utilities used across the framework.

Conventions
-----------
* "stacked" pytrees carry a leading client axis of size N on every leaf
  (client i's state is ``tree_index(stacked, i)``).
* All norms are *global* L2 norms across every leaf (the paper's
  ``|.|`` over the flattened parameter vector θ ∈ R^d).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def tree_zeros_like(tree):
    return jax.tree.map(jnp.zeros_like, tree)


def tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a, b):
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(tree, c):
    return jax.tree.map(lambda x: x * c, tree)


def tree_axpy(a, x, y):
    """a*x + y, leafwise."""
    return jax.tree.map(lambda xl, yl: a * xl + yl, x, y)


def tree_dot(a, b):
    """Global inner product across all leaves (fp32 accumulation)."""
    parts = jax.tree.map(
        lambda x, y: jnp.vdot(x.astype(jnp.float32), y.astype(jnp.float32)), a, b
    )
    return jax.tree.reduce(jnp.add, parts, jnp.float32(0.0))


def tree_sq_norm(tree):
    parts = jax.tree.map(
        lambda x: jnp.sum(jnp.square(x.astype(jnp.float32))), tree
    )
    return jax.tree.reduce(jnp.add, parts, jnp.float32(0.0))


def tree_norm(tree):
    return jnp.sqrt(tree_sq_norm(tree))


@jax.jit
def tree_stack(trees):
    """Stack a list of pytrees along a new leading axis.

    One device program: a call is one dispatch, not one eager op per
    array, and jit keys its cache by the list's structure, shapes and
    length, so each compiles once.
    """
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def tree_unstack(tree, n):
    return [jax.tree.map(lambda x: x[i], tree) for i in range(n)]


def tree_index(tree, i):
    return jax.tree.map(lambda x: x[i], tree)


def tree_broadcast_like(tree, n):
    """Tile a pytree along a new leading client axis of size n."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), tree
    )


def tree_where(mask, a, b):
    """Leafwise select with a per-client boolean mask over the leading axis.

    mask: (N,) bool; a, b: stacked pytrees with leading axis N.
    """

    def sel(x, y):
        m = mask.reshape((-1,) + (1,) * (x.ndim - 1))
        return jnp.where(m, x, y)

    return jax.tree.map(sel, a, b)


def stacked_sq_norms(stacked_diff):
    """Per-client global squared norms of a stacked pytree.

    Returns (N,) fp32 vector: ``r_i = Σ_leaves ‖leaf[i]‖²``.
    """
    parts = jax.tree.map(
        lambda x: jnp.sum(
            jnp.square(x.astype(jnp.float32)).reshape(x.shape[0], -1), axis=1
        ),
        stacked_diff,
    )
    return jax.tree.reduce(jnp.add, parts, jnp.float32(0.0))


def tree_size(tree):
    """Total number of scalars in the pytree."""
    return sum(
        int(np.prod(x.shape))  # tracecheck: ok (static shapes)
        for x in jax.tree.leaves(tree))


def tree_bytes(tree):
    return sum(
        int(np.prod(x.shape))  # tracecheck: ok (static shapes)
        * jnp.dtype(x.dtype).itemsize
        for x in jax.tree.leaves(tree)
    )


def tree_cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), tree)


def tree_ravel(tree):
    """Flatten a pytree into a single 1-D vector (fp32)."""
    leaves = [x.astype(jnp.float32).reshape(-1) for x in jax.tree.leaves(tree)]
    return jnp.concatenate(leaves) if leaves else jnp.zeros((0,), jnp.float32)
