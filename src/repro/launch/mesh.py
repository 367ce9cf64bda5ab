"""Production mesh construction.

Axes:
  pod   : outer pure-DP axis; only gradient all-reduce crosses it (DCN-
          friendly — optionally int8-compressed, optim/compress.py)
  data  : DP + FSDP (ZeRO-3 parameter/optimizer sharding)
  model : TP (heads/ffn), EP (experts), SP (long sequences)

Functions, not module constants: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before first jax init).

Every axis is ``Auto``: the sharding rules in ``launch/sharding.py`` are
GSPMD annotations that the partitioner propagates, not sharding-in-types.
Callers install a mesh with ``jax.set_mesh`` so that code inside a trace
finds it through ``jax.sharding.get_abstract_mesh()``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """1-device mesh with the same axis names (smoke tests, examples)."""
    n = jax.device_count()
    return _mesh((1, n), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_degree(mesh) -> int:
    sizes = mesh_axis_sizes(mesh)
    return sizes.get("data", 1) * sizes.get("pod", 1)
