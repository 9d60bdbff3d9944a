"""Random weights from a tree of shapes, on the device, in one jitted call
from the seed, in the type they are served in.  Every family's
``init_weights`` calls ``init`` with its own shapes and spreads."""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def init(shapes: dict, key, dtype, scale: Callable[[str], float]):
    """Weights shaped like ``shapes`` (a tree of shape tuples), each leaf
    normal with the standard deviation ``scale`` gives its path (as
    ``jax.tree_util.keystr`` writes it).  A leaf of rank 3 is per-layer
    and is drawn one layer at a time."""
    dtype = jnp.dtype(dtype)
    paths, tree = jax.tree.flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    names = [jax.tree_util.keystr(p) for p, _ in paths]
    leaves = [shape for _, shape in paths]

    def make(key):
        out = []
        for k, shape, name in zip(jax.random.split(key, len(leaves)),
                                  leaves, names):
            sd = scale(name)
            if len(shape) == 3:     # one layer at a time bounds the temporaries
                one = lambda kk, s=shape[1:], sc=sd: (
                    jax.random.normal(kk, s, jnp.float32) * sc).astype(dtype)
                out.append(jax.lax.map(one, jax.random.split(k, shape[0])))
            else:
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * sd).astype(dtype))
        return jax.tree.unflatten(tree, out)

    return jax.jit(make)(key)
