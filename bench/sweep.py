"""Finds the knee of an open-loop cell once, by a sweep of arrival rates
on the chip: for each seed and rate, a window at that rate on one set-up.

    python3 bench/sweep.py --workload <cell> --rates 1,2,3 --seeds 1,2,3 \
        --seconds <s>

For each seed and rate it prints the time to first token (median and 90th
percentile over the window and over each half of it), the output tokens
per second, the requests sent and finished, and how many the queue cap held
back.  Below the knee the halves agree; above it the queue grows through
the window and the second half waits longer.  The halves agree when the
second half's median and 90th percentile are each at most ``AGREE`` times
the first half's.  The last line names the knee, the highest rate whose
halves agree on every seed, and 0.8 of it, the rate the cell's file stores.
"""
from __future__ import annotations

import argparse
import copy
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

# second half over first, for the median and the 90th percentile of TTFT
AGREE = 1.25


def _p(vals, q):
    import numpy as np
    return float(np.percentile(vals, q)) * 1e3 if vals else None


def main(argv=None) -> int:
    from bench import run as bench_run
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    rates = [float(x) for x in args.rates.split(",")]
    seeds = [int(x) for x in args.seeds.split(",")]
    cell = bench_run.load_cell(ROOT, args.workload)
    bench_run.enable_caches(ROOT)
    sys.path.insert(0, str(ROOT / "src"))
    bench_run.device_report(cell["chips"], True)

    def log(msg):
        print(f"[sweep] {msg}", file=sys.stderr, flush=True)

    server = bench_run.Server(cell, seeds[0], log)
    s = args.seconds
    agree = {rate: True for rate in rates}
    for seed in seeds:
        for rate in rates:
            c = copy.deepcopy(cell)
            c["workload"]["load"]["rate_rps"] = rate
            server.cell = c
            served = bench_run.serve(server, seed, s, False, None,
                                     time.perf_counter())
            drv = served.client
            due = [r for r in drv.reqs.values() if 0 <= r.spec.due < s]
            ttft = drv.ttft_s(s, served.gave_up_at)
            half = [[t for r, t in zip(due, ttft)
                     if (r.spec.due < s / 2) == h] for h in (True, False)]
            p = {(h, q): _p(half[h], q) for h in (0, 1) for q in (50, 90)}
            ok = all(p[1, q] is not None and p[0, q] is not None
                     and p[1, q] <= AGREE * p[0, q] for q in (50, 90))
            agree[rate] = agree[rate] and ok
            toks = sum(1 for t in drv.stamps if 0 <= t < s)
            print(json.dumps({
                "seed": seed, "rate_rps": rate, "sent": len(due),
                "finished": sum(1 for r in due if r.done),
                "held_back": len(drv.deferred),
                "ttft_p50_ms": _p(ttft, 50), "ttft_p90_ms": _p(ttft, 90),
                "ttft_p50_halves_ms": [p[0, 50], p[1, 50]],
                "ttft_p90_halves_ms": [p[0, 90], p[1, 90]],
                "halves_agree": ok,
                "output_tok_s": toks / s,
                "decode_step_ms": served.counters["decode_total_s"]
                / max(served.counters["decode_count"], 1) * 1e3,
                "prefill_ms": served.counters["prefill_total_s"]
                / max(served.counters["prefill_count"], 1) * 1e3}),
                flush=True)
            served.client = None
            server.reset()
    knee = max((r for r in rates if agree[r]), default=None)
    print(json.dumps({"agree_on_every_seed": {str(r): agree[r]
                                              for r in rates},
                      "knee_rps": knee,
                      "cell_rate_rps": None if knee is None
                      else round(0.8 * knee, 2)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
