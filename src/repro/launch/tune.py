"""Offline tuner launcher: measure the plan grid once, publish an artifact.

    PYTHONPATH=src python -m repro.launch tune --arch qwen3-0.6b --smoke \
        --batch 2 --max-len 32 --out plans.artifact.json

Runs one tuner worker (``repro.tune``) against a shared lease ledger +
compile-cache store: the (kernel × bucket) grid is enumerated from the
config, deduped by compile-cache content hash, sharded, and drained under
heartbeat-stamped leases — run the same command on N machines sharing
``--work-dir`` and they partition the grid automatically; a worker killed
mid-measurement loses its lease and a survivor reclaims the shard.  The
published artifact is schema-versioned with a per-entry verified manifest
(partial results salvage), and ``launch.serve --plan-artifact`` warm-starts
replicas from it with zero autotune measurements.
"""
from __future__ import annotations

import argparse
import json
import os
from pathlib import Path
from typing import Optional, Sequence


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64,
                    help="tune the bucket grid up to this sequence length "
                         "(match the serving ServeConfig.max_len)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="publish the plan artifact to PATH (default: "
                         "<work-dir>/plans.artifact.json)")
    ap.add_argument("--work-dir", default=None, metavar="DIR",
                    help="shared fleet directory for the lease ledger and "
                         "plan store (default: $REPRO_CACHE_DIR or "
                         "~/.cache/repro)")
    ap.add_argument("--worker-id", default=None,
                    help="fleet member id (default: tuner-<pid>)")
    ap.add_argument("--shards", type=int, default=4,
                    help="lease shards to partition the grid into")
    ap.add_argument("--ttl", type=float, default=30.0, metavar="S",
                    help="lease TTL: a worker silent for S seconds loses "
                         "its shard to reclaim")
    ap.add_argument("--backend", default="pallas")
    ap.add_argument("--attention-impl", default=None)
    ap.add_argument("--ssm-impl", default=None)
    args = ap.parse_args(argv)

    from repro.configs.base import load_arch
    from repro.launch import compile_cache
    from repro.tune import run_fleet

    compile_cache.enable()
    cfg = load_arch(args.arch, smoke=args.smoke)
    overrides = {k: v for k, v in (("attention_impl", args.attention_impl),
                                   ("ssm_impl", args.ssm_impl)) if v}
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)

    work_dir = Path(args.work_dir or os.environ.get("REPRO_CACHE_DIR")
                    or (Path.home() / ".cache" / "repro"))
    out = Path(args.out) if args.out else work_dir / "plans.artifact.json"
    worker_id = args.worker_id or f"tuner-{os.getpid()}"

    rep = run_fleet(cfg, args.batch, args.max_len,
                    ledger_path=work_dir / "tune_ledger.json",
                    store_path=work_dir / "compile_cache.json",
                    out_path=out, n_shards=args.shards,
                    worker_id=worker_id, ttl_s=args.ttl,
                    backend=args.backend)

    w = rep["worker"]
    print(f"[tune] {worker_id}: grid {rep['work_items']} request(s) -> "
          f"{rep['groups']} deduped group(s); measured {w['measured']}, "
          f"replayed {w['replayed']}, failed {len(w['failed'])}")
    print(f"[tune] ledger: "
          + ", ".join(f"{k}={v}" for k, v in sorted(rep["ledger"].items()))
          + (f"; lease errors {w['lease_errors']}"
             if w["lease_errors"] else ""))
    if w["shards_lost"]:
        print(f"[tune] LOST LEASES: {len(w['shards_lost'])} shard(s) "
              f"reclaimed by other workers — their results publish from "
              f"the new owners")
    art = rep.get("artifact")
    if art:
        status = "complete" if art["complete"] else \
            f"SALVAGED ({art['missing']} group(s) unmeasured)"
        print(f"[tune] artifact: {art['entries']} plan(s) -> {art['path']} "
              f"[{status}]")
        print(f"[tune] serve replicas warm-start with: "
              f"python -m repro.launch serve --arch {args.arch} "
              f"--plan-artifact {art['path']}")
    print(json.dumps({"worker": worker_id,
                      "measured": w["measured"],
                      "replayed": w["replayed"],
                      "artifact": art}, indent=None))


if __name__ == "__main__":
    main()
