"""Readings for the limit of a cell's ``correct``, on the chip at the
cell's own size: for each seed, the program's widest gap over the sample
that a run compares, and the control's, the float8 reference put in the
program's place over the same prompts and served tokens.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

One process serves every seed: set-up is made once, and each further seed
draws its weights into the same compiled programs and gets a fresh
scheduler.  Each seed runs the cell's pre-roll and a window of
``--seconds`` at the cell's own load, then the comparison a run makes.
Prints one JSON line per seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
# run as a script, the interpreter puts bench/ itself first on the path,
# where its modules would shadow others of the same name (``trace``)
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    from bench import run as bench_run
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    seeds = [int(x) for x in args.seeds.split(",")]
    cell = bench_run.load_cell(ROOT, args.workload)
    bench_run.enable_caches(ROOT)
    sys.path.insert(0, str(ROOT / "src"))
    bench_run.device_report(cell["chips"], True)

    def log(msg):
        print(f"[control] {msg}", file=sys.stderr, flush=True)

    server = None
    for seed in seeds:
        t0 = time.perf_counter()
        if server is None:
            server = bench_run.Server(cell, seed, log)
        else:
            server.reset(seed)
        served = bench_run.serve(server, seed, args.seconds, False, None,
                                 t0)
        served.client = None
        server.sched = None
        gc.collect()
        chk, ctrl = bench_run.check(cell, server.params, served.done, seed,
                                    log, control=True)
        print(json.dumps({"seed": seed, "program": chk, "control": ctrl,
                          "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
