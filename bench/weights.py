"""Random weights of a configuration, made on the device from the seed.

One jitted call draws every parameter in the dtype it is served in
(bfloat16), as the tree of shapes that the configuration's family gives
(``weight_shapes`` of ``bench/families/<family>.py``), laid out as the
program reads them.  The plain reference reads the same tree, so both
sides see the same numbers.

Scales, by a leaf's name: each weight matrix is normal over the square
root of its fan-in (its second-last axis), the embedding is normal times
0.02, and every RMSNorm scale (``scale``) is 1 + 0.1 * normal so that a
norm applied to the wrong axis or skipped shows.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


@functools.partial(jax.jit, static_argnames=("shapes",))
def _draw(key, shapes):
    tree = jax.tree.unflatten(shapes[0], list(shapes[1]))
    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=_is_shape)[0]]
    leaves = list(shapes[1])
    keys = jax.random.split(key, len(leaves))
    out = []
    for kk, path, shape in zip(keys, paths, leaves):
        name = path[-1].key
        z = jax.random.normal(kk, shape, jnp.float32)
        if name == "scale":
            x = 1.0 + 0.1 * z
        elif name == "embedding":
            x = 0.02 * z
        else:
            x = z * shape[-2] ** -0.5
        out.append(x.astype(jnp.bfloat16))
    return jax.tree.unflatten(shapes[0], out)


def make(shapes: Dict, seed32: int):
    """A parameter tree of ``shapes`` (nested dicts of shape tuples), drawn
    from a 32-bit seed."""
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=_is_shape)
    return _draw(jax.random.PRNGKey(seed32), (treedef, tuple(leaves)))
