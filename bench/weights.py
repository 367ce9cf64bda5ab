"""Random weights of a configuration, made on the device from the seed.

One jitted call draws every parameter in the dtype it is served in
(bfloat16), laid out as the program's dense decoder reads them: per-layer
weights stacked over layers under ``blocks``, 2-D weights as
``(d_in, d_out)`` under ``w``, the tied embedding as ``(vocab, d_model)``.
The plain reference reads the same tree, so both sides see the same
numbers.

Scales: each weight matrix is normal over the square root of its fan-in,
the embedding is normal times 0.02, and every RMSNorm scale is
1 + 0.1 * normal so that a norm applied to the wrong axis or skipped shows.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from bench.reference.dense import dims


def _shapes(cfg: Dict) -> Dict:
    k = dims(cfg)
    d, h, hkv, hd = k["d"], k["h"], k["hkv"], k["hd"]
    n, ff, v = (cfg["num_hidden_layers"], cfg["intermediate_size"],
                cfg["vocab_size"])
    attn = {"wq": {"w": (n, d, h * hd)}, "wk": {"w": (n, d, hkv * hd)},
            "wv": {"w": (n, d, hkv * hd)}, "wo": {"w": (n, h * hd, d)}}
    if k["qk_norm"]:
        attn["q_norm"] = {"scale": (n, hd)}
        attn["k_norm"] = {"scale": (n, hd)}
    return {
        "embed": {"embedding": (v, d)},
        "final_norm": {"scale": (d,)},
        "blocks": {
            "norm1": {"scale": (n, d)},
            "attn": attn,
            "norm2": {"scale": (n, d)},
            "mlp": {"gate": {"w": (n, d, ff)}, "up": {"w": (n, d, ff)},
                    "down": {"w": (n, ff, d)}},
        },
    }


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


def make(cfg: Dict, seed32: int):
    """The parameter tree of ``cfg``, drawn from a 32-bit seed."""
    leaves, treedef = jax.tree.flatten(_shapes(cfg), is_leaf=_is_shape)
    return _draw(jax.random.PRNGKey(seed32), (treedef, tuple(leaves)))
