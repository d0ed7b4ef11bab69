"""Served weights made by the benchmark: on the device, in one jitted call,
from ``--seed``, in float32. A helper for the families whose configurations
store float32 (``families/<family>``: ``weights``); the program is handed
this tree and so is the reference, and neither takes anything from the
other. The one draw holds the whole tree twice for a moment, so a family
whose tree does not fit beside a second copy of itself draws leaf by leaf,
in its stored type, and not through here."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

KERNEL_STD = 0.02  # BERT's initializer_range


def seed_key(seed: int):
    """A key from any whole number up to a little over 2**31 and beyond."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make(shapes: dict, seed: int) -> dict:
    """Random weights for a tree of shapes. Matrices and embeddings are
    N(0, 0.02); biases N(0, 0.02) so that none of them is a no-op; LayerNorm
    scales 1 + N(0, 0.1)."""
    paths_and_shapes, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    names = [p[-1].key for p, _ in paths_and_shapes]
    leaf_shapes = [s for _, s in paths_and_shapes]

    sizes = [math.prod(shape) for shape in leaf_shapes]

    def build(key):
        # One draw for the whole tree, cut into its leaves: a program of a
        # few hundred slices compiles in seconds where one draw per leaf
        # takes a minute.
        noise = jax.random.normal(key, (sum(sizes),), jnp.float32)
        leaves, at = [], 0
        for name, shape, size in zip(names, leaf_shapes, sizes):
            leaf = noise[at:at + size].reshape(shape)
            at += size
            leaves.append(1.0 + 0.1 * leaf if name == "scale"
                          else KERNEL_STD * leaf)
        return leaves

    leaves = jax.jit(build)(seed_key(seed))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def count(shapes: dict) -> int:
    return sum(math.prod(shape) for shape in jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))
