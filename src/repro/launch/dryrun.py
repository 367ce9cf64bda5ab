import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# The two lines above MUST run before any jax import (device count locks at
# first init).  Everything below is ordinary code.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this driver builds the jitted step (train / prefill / decode),
lowers it against abstract inputs with full production shardings, compiles,
and records:

  - memory_analysis()        → bytes/device (proves the config fits HBM)
  - cost_analysis()          → HLO FLOPs / bytes (roofline compute+memory)
  - collective byte counts   → parsed from the optimized HLO (roofline
                               collective term)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--json out.json]
"""
import argparse
import json
import re
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro import optim
from repro.configs.base import SHAPES, cells, load_arch
from repro.launch import mesh as mesh_mod
from repro.launch import steps as steps_mod

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

# parse operand shapes like f32[16,128]{1,0} / bf16[2,4,8]
_SHAPE_RE = re.compile(r"(pred|s4|s8|s16|s32|s64|u8|u16|u32|u64|bf16|f16|"
                       r"f32|f64|c64|c128)\[([0-9,]*)\]")
_BYTES = {"pred": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
          "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "c64": 8,
          "s64": 8, "u64": 8, "f64": 8, "c128": 16}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _BYTES[dtype]


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output-shape bytes of every collective op in optimized HLO."""
    out: Dict[str, int] = {k: 0 for k in COLLECTIVE_OPS}
    out["count"] = 0
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"[%\w.\-]+\s*=\s*(\S+)\s+(\S+)\(", ls)
        if not m:
            continue
        shape_part, op = m.group(1), m.group(2)
        kind = next((k for k in COLLECTIVE_OPS if op.startswith(k)), None)
        if kind is None:
            continue
        nbytes = sum(_shape_bytes(d, s)
                     for d, s in _SHAPE_RE.findall(shape_part))
        out[kind] += nbytes
        out["count"] += 1
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             pump_factor: int = 1, param_dtype=jnp.bfloat16,
             verbose: bool = True) -> Dict[str, Any]:
    cfg = load_arch(arch)
    shape = SHAPES[shape_name]
    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    optcfg = optim.AdamWConfig(moment_dtype="bfloat16")
    t0 = time.time()

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            step = steps_mod.make_train_step(cfg, optcfg, pump_factor)
            in_sh, out_sh, args = steps_mod.train_shardings(
                cfg, optcfg, mesh, shape, param_dtype, pump_factor)
            jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=(0, 1))
            lowered = jitted.lower(*args)
        elif shape.kind == "prefill":
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.launch import sharding as shard_mod
            step = steps_mod.make_prefill_step(cfg)
            params = steps_mod.abstract_params(cfg, param_dtype)
            p_sh = shard_mod.shardings(params, mesh)
            batch = steps_mod.abstract_batch(cfg, shape)
            del batch["labels"]
            bsp = shard_mod.batch_spec(mesh)
            bax = bsp[0] if len(bsp) else None
            b_sh = jax.tree.map(
                lambda l: NamedSharding(mesh, shard_mod._fit(
                    P(*((bax,) + (None,) * (l.ndim - 1))), l.shape, mesh)),
                batch)
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
            lowered = jitted.lower(params, batch)
        else:  # decode
            step = steps_mod.make_decode_step(cfg)
            p_sh, c_sh, b_sh, (params, cache, batch) = \
                steps_mod.serve_shardings(cfg, mesh, shape, param_dtype)
            jitted = jax.jit(step, in_shardings=(p_sh, c_sh, b_sh),
                             out_shardings=(None, c_sh), donate_argnums=(1,))
            lowered = jitted.lower(params, cache, batch)

        compiled = lowered.compile()

    t1 = time.time()
    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "pump_factor": pump_factor,
        "kind": shape.kind,
        "compile_s": round(t1 - t0, 1),
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": {k: v for k, v in coll.items() if v},
        "collective_total": sum(v for k, v in coll.items() if k != "count"),
        "collective_count": coll["count"],
    }
    if mem is not None:
        for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                result[attr] = int(v)
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × {result['mesh']} "
              f"OK in {result['compile_s']}s  "
              f"flops={result['flops']:.3e}  "
              f"bytes={result['bytes_accessed']:.3e}  "
              f"coll={result['collective_total']:.3e}B "
              f"({result['collective_count']} ops)")
        sys.stdout.flush()
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--pump", type=int, default=1)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    results = []
    if args.all:
        todo = cells()
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        todo = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch, shape in todo:
        for mp in meshes:
            try:
                results.append(run_cell(arch, shape, multi_pod=mp,
                                        pump_factor=args.pump))
            except Exception as e:  # noqa: BLE001 — report and continue
                failures.append((arch, shape, mp, repr(e)[:300]))
                print(f"[dryrun] FAIL {arch} × {shape} × "
                      f"{'2x16x16' if mp else '16x16'}: {e!r}"[:400])
                sys.stdout.flush()

    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    print(f"\n[dryrun] {len(results)} cells OK, {len(failures)} failed")
    if failures:
        for f in failures:
            print("  FAIL:", f)
        sys.exit(1)


if __name__ == "__main__":
    main()
