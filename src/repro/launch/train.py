"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
        --steps 100 --pump auto --ckpt /tmp/ckpt

On this CPU container use --smoke (reduced config).  On a real TPU slice the
same entry point runs the full config under make_production_mesh(); jax
initializes the distributed runtime from the TPU environment.
"""
from __future__ import annotations

import argparse

import jax

from repro import optim
from repro.configs.base import SHAPES, ShapeConfig, load_arch
from repro.launch import compile_cache, mesh as mesh_mod
from repro.train.trainer import TrainConfig, train


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + tiny shape (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--pump", default="1", help="int or 'auto'")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--failover", action="store_true",
                    help="wire the failover runtime into the loop: per-step "
                         "heartbeat stamping + straggler pump derating")
    ap.add_argument("--heartbeat-timeout", type=float, default=300.0,
                    help="seconds without progress before a worker is "
                         "considered dead (--failover)")
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = load_arch(args.arch, smoke=args.smoke)
    shape = SHAPES[args.shape]
    if args.smoke:
        shape = ShapeConfig("smoke", args.seq or 64, args.batch or 8, "train")
    elif args.batch or args.seq:
        shape = ShapeConfig("custom", args.seq or shape.seq_len,
                            args.batch or shape.global_batch, "train")

    mesh = (mesh_mod.make_production_mesh(multi_pod=args.multi_pod)
            if args.production_mesh else mesh_mod.make_host_mesh())
    pump = args.pump if args.pump == "auto" else int(args.pump)
    optcfg = optim.AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                               total_steps=args.steps)
    tcfg = TrainConfig(n_steps=args.steps, pump_factor=pump,
                       ckpt_root=args.ckpt,
                       param_dtype="float32" if args.smoke else "bfloat16")
    heartbeat = straggler = None
    if args.failover:
        from repro.runtime.failover import Heartbeat, StragglerPolicy
        heartbeat = Heartbeat(timeout_s=args.heartbeat_timeout)
        straggler = StragglerPolicy()
    out = train(cfg, shape, optcfg, tcfg, mesh=mesh,
                heartbeat=heartbeat, straggler=straggler)
    hist = out["history"]
    if hist:
        print(f"[train] done: loss {hist[0]['loss']:.4f} -> "
              f"{hist[-1]['loss']:.4f} over {args.steps} steps "
              f"(pump={out['pump']})")
    if heartbeat is not None:
        dead = heartbeat.dead_workers()
        factors = straggler.pump_factors()
        print(f"[failover] heartbeat: {len(heartbeat._step)} worker(s) "
              f"stamped, {len(dead)} dead; straggler pump factors "
              f"{factors}")


if __name__ == "__main__":
    main()
