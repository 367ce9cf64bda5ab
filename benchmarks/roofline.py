"""§Roofline: three-term roofline per (arch × shape) from compiled artifacts.

Methodology (CPU container, TPU v5e target — see EXPERIMENTS.md):

XLA's cost analysis counts while-loop bodies ONCE (verified), and the layer
stack is a lax.scan, so the full-step artifact under-counts by ~L×.  We
therefore decompose each step into segments and compile each one *unrolled*
under the production mesh shardings:

  per-layer block  (fwd+bwd for train, fwd for prefill, 1-token for decode)
  embed + lm-head (+ loss)
  optimizer update (analytic: elementwise, ~20 B and ~12 flops per param,
                    sharded)

  total(term) = Σ_segments  multiplicity × per_device_cost(segment)

cost_analysis reports PER-DEVICE flops/bytes under SPMD (verified), and HLO
shapes are per-partition, so collective operand bytes parsed from the HLO
are also per-device.  Hardware constants: 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI (TPU v5e-class).

    compute_term    = flops_dev / 197e12        [s]
    memory_term     = bytes_dev / 819e9         [s]
    collective_term = coll_bytes_dev / 50e9     [s]

MODEL_FLOPS = 6·N_active·D (train) or 2·N_active·D (fwd) per token;
ratio = MODEL_FLOPS / (chips × flops_dev) flags remat/redundancy waste.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Any, Dict, Optional

import numpy as np

PEAK = 197e12
HBM = 819e9
ICI = 50e9

RESULTS_PATH = os.path.join(os.path.dirname(__file__), "..",
                            "roofline_results.json")


def _ensure_devices():
    if "XLA_FLAGS" not in os.environ or "device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=256")


@dataclasses.dataclass
class SegCost:
    flops: float = 0.0
    bytes_: float = 0.0
    coll: float = 0.0
    coll_ops: int = 0

    def scaled(self, k: float) -> "SegCost":
        return SegCost(self.flops * k, self.bytes_ * k, self.coll * k,
                       int(self.coll_ops * k))

    def __add__(self, o: "SegCost") -> "SegCost":
        return SegCost(self.flops + o.flops, self.bytes_ + o.bytes_,
                       self.coll + o.coll, self.coll_ops + o.coll_ops)


def _compile_cost(fn, args, in_shardings, mesh, donate=()) -> SegCost:
    import jax
    from repro.launch.dryrun import collective_bytes
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=in_shardings,
                          donate_argnums=donate).lower(*args)
        compiled = lowered.compile()
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    return SegCost(float(cost.get("flops", 0.0)),
                   float(cost.get("bytes accessed", 0.0)),
                   float(sum(v for k, v in coll.items() if k != "count")),
                   coll["count"])


def segment_costs(arch: str, shape_name: str, *, pump_factor: int = 1,
                  attn_block_kv: Optional[int] = None,
                  ssm_chunk: Optional[int] = None,
                  tensor_parallel: bool = True) -> Dict[str, Any]:
    """Compile per-segment artifacts and assemble the roofline terms."""
    _ensure_devices()
    import dataclasses as dc
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import SHAPES, load_arch
    from repro.launch import mesh as mesh_mod
    from repro.launch import sharding as shard_mod
    from repro.models import transformer as tf
    from repro.models import encdec, ssm as ssm_mod, model as model_mod
    from repro.models.layers import cross_entropy, rmsnorm

    cfg = load_arch(arch)
    if attn_block_kv:
        cfg = dc.replace(cfg, attn_block_kv=attn_block_kv)
    if ssm_chunk and cfg.ssm:
        cfg = dc.replace(cfg, ssm=dc.replace(cfg.ssm, chunk=ssm_chunk))
    shape = SHAPES[shape_name]
    mesh = mesh_mod.make_production_mesh()       # single-pod 16×16
    chips = mesh.devices.size
    kind = shape.kind
    b, s = shape.global_batch, shape.seq_len
    if kind == "train" and pump_factor > 1:
        b = b // pump_factor                     # per-microbatch compute
    dt = jnp.bfloat16

    bax = ("data",)
    xspec = NamedSharding(mesh, shard_mod._fit(P(bax, None, None),
                                               (b, s, cfg.d_model), mesh))

    def block_shard(params):
        specs = shard_mod.param_specs(params)
        if not tensor_parallel:
            specs = shard_mod.strip_axis(specs, "model")
        if kind == "decode" and cfg.family != "moe":
            # serve path: weights TP-resident, no per-token FSDP gathers;
            # MoE keeps FSDP (sparse expert access) — §Perf E2/E3,
            # mirrors launch/steps.serve_shardings
            specs = shard_mod.strip_axis(specs, "data")
        return shard_mod.shardings(params, mesh, specs)

    total = SegCost()
    details = {}

    # ---- per-layer blocks ---------------------------------------------------
    seg_list = tf._segments(cfg)
    for name, kindb, n in seg_list:
        init, apply = tf._BLOCKS[kindb]
        bp = jax.eval_shape(lambda k: init(k, cfg, dt), jax.random.PRNGKey(0))
        if kind == "train":
            x = jax.ShapeDtypeStruct((b, s, cfg.d_model), dt)

            def block_fn(bpp, xx):
                def f(bpp_, xx_):
                    y, aux, _ = apply(bpp_, cfg, xx_, jnp.arange(xx_.shape[1]))
                    return (y.astype(jnp.float32).sum() + aux)
                return jax.grad(f, argnums=(0, 1))(bpp, xx)

            cost = _compile_cost(block_fn, (bp, x),
                                 (block_shard(bp), xspec), mesh)
        elif kind == "prefill":
            x = jax.ShapeDtypeStruct((b, s, cfg.d_model), dt)

            def block_fn(bpp, xx):
                y, _, _ = apply(bpp, cfg, xx, jnp.arange(xx.shape[1]))
                return y

            cost = _compile_cost(block_fn, (bp, x),
                                 (block_shard(bp), xspec), mesh)
        else:  # decode: one token against a full cache
            x = jax.ShapeDtypeStruct((b, 1, cfg.d_model), dt)
            if kindb == "mamba":
                cache = jax.eval_shape(
                    lambda: ssm_mod.mamba2_cache_init(cfg, b, dt))
            elif cfg.mla:
                from repro.models import attention as attn_mod
                cache = jax.eval_shape(
                    lambda: attn_mod.mla_cache_init(cfg, b, s, dt))
            else:
                from repro.models import attention as attn_mod
                cache = jax.eval_shape(
                    lambda: attn_mod.gqa_cache_init(cfg, b, s, dt))
            cache = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct((1,) + l.shape, l.dtype)
                if l.ndim else l, cache)
            c_sh = jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                                shard_mod.cache_specs(cache, mesh),
                                is_leaf=lambda z: isinstance(z, P))
            xs1 = NamedSharding(mesh, shard_mod._fit(
                P(bax, None, None), (b, 1, cfg.d_model), mesh))

            def block_fn(bpp, xx, cc):
                cc1 = jax.tree.map(
                    lambda l: l[0] if hasattr(l, "ndim") and l.ndim else l,
                    cc)
                y, _, nc = apply(bpp, cfg, xx, jnp.zeros((1,), jnp.int32),
                                 cc1)
                return y, nc

            # donate the cache: the in-place update must not be counted
            # as a full cache copy (matches the real serve step, which
            # donates — §Perf B1)
            cost = _compile_cost(block_fn, (bp, x, cache),
                                 (block_shard(bp), xs1, c_sh), mesh,
                                 donate=(2,))
        total = total + cost.scaled(n)
        details[f"block_{name}"] = dataclasses.asdict(cost) | {"n": n}

    # hybrid shared-attn applications
    if cfg.family == "hybrid" and cfg.hybrid_attn_every:
        n_apps = cfg.n_layers // cfg.hybrid_attn_every
        # reuse dense block cost at same shapes
        init, apply = tf._BLOCKS["dense"]
        bp = jax.eval_shape(lambda k: init(k, cfg, dt), jax.random.PRNGKey(0))
        if kind in ("train",):
            x = jax.ShapeDtypeStruct((b, s, cfg.d_model), dt)

            def block_fn(bpp, xx):
                def f(bpp_, xx_):
                    y, aux, _ = apply(bpp_, cfg, xx_, jnp.arange(xx_.shape[1]))
                    return y.astype(jnp.float32).sum() + aux
                return jax.grad(f, argnums=(0, 1))(bpp, xx)
            cost = _compile_cost(block_fn, (bp, x),
                                 (block_shard(bp), xspec), mesh)
        elif kind == "prefill":
            x = jax.ShapeDtypeStruct((b, s, cfg.d_model), dt)

            def block_fn(bpp, xx):
                y, _, _ = apply(bpp, cfg, xx, jnp.arange(xx.shape[1]))
                return y
            cost = _compile_cost(block_fn, (bp, x),
                                 (block_shard(bp), xspec), mesh)
        else:
            cost = SegCost()  # counted approximately via gqa decode below
        total = total + cost.scaled(n_apps)
        details["block_shared_attn"] = dataclasses.asdict(cost) | {
            "n": n_apps}

    # ---- embed + head + loss -----------------------------------------------
    vshape = jax.ShapeDtypeStruct((cfg.vocab_size, cfg.d_model), dt)
    v_sh = NamedSharding(mesh, shard_mod._fit(
        P("model", "data"), (cfg.vocab_size, cfg.d_model), mesh))
    s_eff = 1 if kind == "decode" else s
    tok = jax.ShapeDtypeStruct((b, s_eff), jnp.int32)
    last_only_prefill = kind == "prefill"
    tok_sh = NamedSharding(mesh, shard_mod._fit(P(bax, None), (b, s_eff),
                                                mesh))

    def emb_head_fn(table, tokens):
        x = table.astype(dt)[tokens]
        if last_only_prefill:
            x = x[:, -1:]              # §Perf C1: serve prefill emits only
        logits = x.astype(jnp.float32) @ table.astype(jnp.float32).T
        if kind == "train":
            labels = jnp.roll(tokens, -1, axis=1)
            return cross_entropy(logits, labels)
        return logits

    if kind == "train":
        cost = _compile_cost(
            lambda t, tk: jax.grad(
                lambda t_, tk_: emb_head_fn(t_, tk_))(t, tk),
            (vshape, tok), (v_sh, tok_sh), mesh)
    else:
        cost = _compile_cost(emb_head_fn, (vshape, tok), (v_sh, tok_sh),
                             mesh)
    total = total + cost
    details["embed_head"] = dataclasses.asdict(cost) | {"n": 1}

    # ---- optimizer (analytic, elementwise, fully sharded) -------------------
    if kind == "train":
        n_params = cfg.param_count()
        opt_bytes = n_params * 20.0 / chips
        opt_flops = n_params * 12.0 / chips
        total = total + SegCost(opt_flops, opt_bytes, 0.0, 0)
        details["optimizer_analytic"] = {"flops": opt_flops,
                                         "bytes_": opt_bytes, "n": 1}

    # ---- microbatch multiplicity + gradient sync ----------------------------
    if kind == "train" and pump_factor > 1:
        # M microbatches of compute; collectives for grads once (captured in
        # block costs as reduce-scatter per microbatch — correct them: grads
        # sync once per wide transaction)
        comp = SegCost(total.flops * pump_factor,
                       total.bytes_ * pump_factor,
                       total.coll * 1.0,     # amortized: once per M
                       total.coll_ops)
        total = comp

    tokens = shape.global_batch * shape.seq_len if kind != "decode" \
        else shape.global_batch
    mf_per_tok = (6.0 if kind == "train" else 2.0) * cfg.active_param_count()
    model_flops = mf_per_tok * tokens

    compute_t = total.flops / PEAK
    memory_t = total.bytes_ / HBM
    coll_t = total.coll / ICI
    dom = max((compute_t, "compute"), (memory_t, "memory"),
              (coll_t, "collective"))
    useful = model_flops / (chips * total.flops) if total.flops else 0.0

    return {
        "arch": arch, "shape": shape_name, "mesh": "16x16", "chips": chips,
        "kind": kind, "pump_factor": pump_factor,
        "flops_dev": total.flops, "bytes_dev": total.bytes_,
        "coll_bytes_dev": total.coll, "coll_ops": total.coll_ops,
        "compute_term_s": compute_t, "memory_term_s": memory_t,
        "collective_term_s": coll_t,
        "dominant": dom[1],
        "model_flops": model_flops,
        "useful_flops_ratio": useful,
        "roofline_fraction": compute_t / max(compute_t, memory_t, coll_t)
        if max(compute_t, memory_t, coll_t) else 0.0,
        "details": details,
    }


def summary_rows(path: str = None) -> None:
    """CSV rows for benchmarks.run from a saved roofline JSON (prefers the
    optimized sweep, falls back to the baseline)."""
    if path is None:
        root = os.path.join(os.path.dirname(__file__), "..")
        for cand in ("roofline_optimized.json", "roofline_baseline.json",
                     "roofline_results.json"):
            p = os.path.join(root, cand)
            if os.path.exists(p):
                path = p
                break
        else:
            path = RESULTS_PATH
    if not os.path.exists(path):
        print("roofline_missing,0.0,run 'python -m benchmarks.roofline' first")
        return
    with open(path) as f:
        rows = json.load(f)
    for r in rows:
        step_s = max(r["compute_term_s"], r["memory_term_s"],
                     r["collective_term_s"])
        print(f"roofline_{r['arch']}_{r['shape']},{step_s * 1e6:.1f},"
              f"compute={r['compute_term_s']:.2e};memory={r['memory_term_s']:.2e};"
              f"collective={r['collective_term_s']:.2e};dom={r['dominant']};"
              f"useful={r['useful_flops_ratio']:.2f};"
              f"frac={r['roofline_fraction']:.2f}")


def main() -> None:
    _ensure_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--pump", type=int, default=1)
    ap.add_argument("--json", default=RESULTS_PATH)
    args = ap.parse_args()

    from repro.configs.base import cells
    todo = cells() if args.all else [(args.arch, args.shape)]
    rows = []
    for arch, shape in todo:
        try:
            r = segment_costs(arch, shape, pump_factor=args.pump)
            rows.append(r)
            print(f"[roofline] {arch} × {shape}: "
                  f"C={r['compute_term_s']:.2e}s M={r['memory_term_s']:.2e}s "
                  f"X={r['collective_term_s']:.2e}s dom={r['dominant']} "
                  f"useful={r['useful_flops_ratio']:.2f}")
        except Exception as e:  # noqa: BLE001
            print(f"[roofline] FAIL {arch} × {shape}: {e!r}"[:300])
        sys.stdout.flush()
    if args.json and rows:
        existing = []
        if os.path.exists(args.json):
            with open(args.json) as f:
                existing = json.load(f)
        keyed = {(r["arch"], r["shape"], r.get("pump_factor", 1)): r
                 for r in existing}
        for r in rows:
            keyed[(r["arch"], r["shape"], r.get("pump_factor", 1))] = r
        with open(args.json, "w") as f:
            json.dump(list(keyed.values()), f, indent=1)


if __name__ == "__main__":
    main()
