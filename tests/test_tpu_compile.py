"""Compile the serving path's kernels for a described TPU v5e.

No chip is attached: ``jax.experimental.topologies`` describes a v5e and the
TPU compiler (installed with libtpu) compiles for it, refusing what the chip
would refuse — a block shape that breaks the (8, 128) tiling, more VMEM
than a kernel may use.  Interpret-mode tests cannot see either.

Emission is steered inside the test (``plan_region`` + ``emit_pallas(...,
interpret=False)``), because ``lower_pallas`` picks its tier from the
attached devices, which here are CPUs.  The topology is described inside a
module fixture, never at import time: only one process may hold libtpu, and
every test worker imports this file.
"""
from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.compiler import Pipeline
from repro.compiler.pallas_backend import (emit_pallas, lower_pallas,
                                           partition_regions, plan_region,
                                           tpu_tiling_ok)
from repro.core.autopump import BUILDERS
from repro.core.ir import NodeKind

FACTORS = (1, 2, 4, 8)
# Qwen3-0.6B attention at its published width: 16 query heads over 8 KV
# heads of 128, bf16, 128-row blocks
QWEN3 = dict(h=16, hkv=8, d=128)
FLASH = dict(bq=128, bkv=128, hkv=QWEN3["hkv"], causal=True,
             dtype="bfloat16", itemsize=2)
# case id -> (builder, args, kwargs); the serving path builds flash without
# its row statistics (``stats=False``)
CASES = {
    "flash_attention": ("flash_attention",
                        (1, QWEN3["h"], 2048, 2048, QWEN3["d"]), FLASH),
    "flash_attention-serving": ("flash_attention",
                                (1, QWEN3["h"], 2048, 2048, QWEN3["d"]),
                                dict(FLASH, stats=False)),
    "decode_attention": ("decode_attention", (4, QWEN3["h"], 2048, QWEN3["d"]),
                         dict(bkv=128, hkv=QWEN3["hkv"], dtype="bfloat16",
                              itemsize=2)),
    # the served decode steps: 32 slots over a 1024-slot cache, the graph's
    # own KV tile; Qwen3-0.6B's heads, then Granite-3.0-2B's (32 query heads
    # over 8 KV heads of 64)
    "decode_attention-qwen3": ("decode_attention",
                               (32, QWEN3["h"], 1024, QWEN3["d"]),
                               dict(hkv=QWEN3["hkv"], dtype="bfloat16",
                                    itemsize=2)),
    "decode_attention-granite": ("decode_attention", (32, 32, 1024, 64),
                                 dict(hkv=8, dtype="bfloat16", itemsize=2)),
    "vecadd": ("vecadd", (1 << 20,), dict(vector_width=1024)),
    "matmul": ("matmul", (1024, 1024, 1024), {}),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile_for_chip(kernel, args, kwargs, factor, one_chip):
    g, est = BUILDERS[kernel](*args, **kwargs)
    graph, _report = Pipeline.default(factor=factor, mode="T",
                                      estimate=est).run(g)
    fns = []
    for region in partition_regions(graph):
        notes = []
        plan = plan_region(graph, region, notes.append)
        assert plan is not None and plan.pallas_ok, notes
        assert tpu_tiling_ok(graph, plan)
        fns.append(emit_pallas(graph, plan, interpret=False))
    inputs = {n.name: jax.ShapeDtypeStruct(n.shape, jnp.dtype(n.dtype),
                                           sharding=one_chip)
              for n in graph.nodes.values()
              if n.kind == NodeKind.MEMORY and not graph.in_edges(n.name)}

    def run(mems):
        mems = dict(mems)
        for fn in fns:
            mems.update(fn(mems))
        return mems

    return jax.jit(run).lower(inputs).compile()


@pytest.mark.parametrize("factor", FACTORS)
@pytest.mark.parametrize("kernel", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, kernel, factor):
    kernel, args, kwargs = CASES[kernel]
    compiled = _compile_for_chip(kernel, args, kwargs, factor, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["decode_attention",
                                    "flash_attention-serving"])
def test_lowered_kernel_is_named_in_the_chip_module(topo, one_chip,
                                                    monkeypatch, kernel):
    """``lower_pallas`` names a graph's jitted wrapper after the graph, its
    pump factor and mode; compiled for the chip inside a model step, the
    kernel's custom call carries that name, which is what a profiler trace
    shows for it."""
    name, args, kwargs = CASES[kernel]
    g, est = BUILDERS[name](*args, **kwargs)
    graph, _report = Pipeline.default(factor=2, mode="T",
                                      estimate=est).run(g)
    # lower_pallas picks its tier from the attached devices: show it the chip
    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
    fn = lower_pallas(graph)
    monkeypatch.undo()
    assert fn.__name__ == f"{name}_m2T"
    inputs = {n.name: jax.ShapeDtypeStruct(n.shape, jnp.dtype(n.dtype),
                                           sharding=one_chip)
              for n in graph.nodes.values()
              if n.kind == NodeKind.MEMORY and not graph.in_edges(n.name)}

    def model_step(mems):
        return fn(mems)

    text = jax.jit(model_step).lower(inputs).compile().as_text()
    calls = [ln.split(" = ", 1)[0].strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln]
    assert calls and all(c.startswith(f"%{name}_m2T") for c in calls)


@pytest.mark.parametrize("factor", (1, 2))
@pytest.mark.parametrize("kernel", ["decode_attention-qwen3",
                                    "decode_attention-granite"])
def test_served_decode_grid_steps_per_kv_head_and_wide_tile(kernel, factor):
    """The served decode kernel takes one grid step per (slot, KV head, KV
    tile), the query heads of a KV head in one block and a tile of the
    graph's own width: b × hkv × (t / bkv) points (mode T splits the tile
    axis into the pump axis without adding any), not one per query head and
    128 keys."""
    name, (b, h, t, d), kwargs = CASES[kernel]
    g, est = BUILDERS[name](b, h, t, d, **kwargs)
    graph, _report = Pipeline.default(factor=factor, mode="T",
                                      estimate=est).run(g)
    [region] = partition_regions(graph)
    notes = []
    plan = plan_region(graph, region, notes.append)
    assert plan is not None and plan.pallas_ok and not notes, notes
    assert tpu_tiling_ok(graph, plan)
    bkv = graph.nodes["k"].shape[2] // 2      # the cache in two tiles
    hkv = kwargs["hkv"]
    want = (("bi", b), ("kvh", hkv), ("ji", t // bkv // factor))
    assert plan.grid == want + ((("_pump", factor),) if factor > 1 else ())
    points = b * hkv * (t // bkv)
    assert np.prod([e for _s, e in plan.grid]) == points
    # fewer than one per (slot, query head, 128 keys) by the GQA group
    # times the tile's width over 128
    assert points * (h // hkv) * (bkv // 128) == b * h * (t // 128)
