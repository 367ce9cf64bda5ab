"""Reduces a JAX profiler trace (``.xplane.pb``) to the program's own spans,
which ``repro.obs`` writes into the capture while its tracer is on, and to
the device time of the named decode kernel.

Within the ``bench.window`` span it gives:

* per program span name (``sched.``, ``serve.``, ``engine.``): how many
  start in the window and their total seconds;
* per program instant name (counters such as ``sched.decode_steps``, and
  ``jax.compile``; ``repro.obs`` marks each with the stat ``instant``): how
  many fall in the window;
* the device time of the Pallas custom calls whose op name starts with
  ``decode_attention`` (the compiler names each kernel after its graph);
* the idle gaps of ``bench.trace``, named by the innermost program span
  that covers each gap's midpoint (``bench.trace``'s rule), overall and
  under each ``bench.*`` label.

Run as a script it serves one traced window of a cell, as
``bench/run.py --trace 1`` does, with the program's tracer on, prints the tables on standard error and the result line, with the
reduction under ``program_spans``, on standard output:

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s>
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple

ROOT = Path(__file__).resolve().parents[1]
# run as a script, the interpreter puts bench/ itself first on the path,
# where its modules would shadow others of the same name (``trace``)
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

PROGRAM_PREFIXES = ("sched.", "serve.", "engine.", "jax.compile")
DECODE_KERNEL = "decode_attention"
INSTANT_STAT = "instant"


def is_decode_kernel(text: str) -> bool:
    """A Pallas decode attention kernel, by its op name."""
    return trace.is_custom_call(text) and \
        trace.op_name(text).lstrip("%").startswith(DECODE_KERNEL)


def reduce(path: str) -> Dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    bench_spans: List[Tuple[float, float, str, int]] = []
    prog: List[Tuple[float, float, str, int]] = []
    marks: List[Tuple[float, str]] = []
    chips: List[List[Tuple[float, float, str]]] = []
    for plane in pd.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                   for line in plane.lines if line.name == trace.OPS_LINE
                   for ev in line.events]
            if ops:
                chips.append(ops)
            continue
        for line in plane.lines:
            for ev in line.events:
                sp = (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, 0)
                if ev.name.startswith(trace.SPAN_PREFIX):
                    bench_spans.append(sp)
                elif ev.name.startswith(PROGRAM_PREFIXES):
                    if any(k == INSTANT_STAT for k, _ in ev.stats):
                        marks.append((ev.start_ns, ev.name))
                    else:
                        prog.append(sp)
    windows = [sp for sp in bench_spans if sp[2] == trace.WINDOW]
    if not windows:
        raise ValueError(f"{path}: no {trace.WINDOW!r} span in the trace")
    if not chips:
        raise ValueError(f"{path}: no TPU plane with {trace.OPS_LINE!r} "
                         "events")
    lo, hi = windows[0][0], windows[0][1]
    spans: Dict[str, List[float]] = {}
    for s, e, name, _ in prog:
        if lo <= s < hi:
            c = spans.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += (min(e, hi) - s) / 1e9
    instants: Dict[str, int] = {}
    for s, name in marks:
        if lo <= s < hi:
            instants[name] = instants.get(name, 0) + 1
    kernel_ns = 0.0
    kernel_ops = set()
    busy = []
    for ops in chips:
        clipped = []
        for s, e, name in ops:
            s, e = trace._clip(s, e, lo, hi)
            if e <= s:
                continue
            clipped.append((s, e))
            if is_decode_kernel(name):
                kernel_ns += e - s
                kernel_ops.add(trace.op_name(name))
        busy.append(trace._union(clipped))
    gaps = trace._gaps(busy[0], lo, hi)
    mids = [(s + e) / 2 for s, e in gaps]
    by_prog = trace._label([sp for sp in prog if sp[1] > sp[0]], mids)
    by_bench = trace._label([sp for sp in bench_spans
                             if sp[2] != trace.WINDOW], mids)
    idle: Dict[str, float] = {}
    cross: Dict[str, Dict[str, float]] = {}
    for (s, e), p, b in zip(gaps, by_prog, by_bench):
        idle[p] = idle.get(p, 0.0) + (e - s) / 1e9
        row = cross.setdefault(b, {})
        row[p] = row.get(p, 0.0) + (e - s) / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "idle_s": sum(e - s for s, e in gaps) / 1e9,
        "spans": {k: spans[k] for k in sorted(spans)},
        "instants": {k: instants[k] for k in sorted(instants)},
        "decode_kernel_s": kernel_ns / len(chips) / 1e9,
        "decode_kernel_ops": sorted(kernel_ops),
        "idle_by_span": _desc(idle),
        "idle_by_bench_span": {b: _desc(row) for b, row in
                               sorted(cross.items(),
                                      key=lambda kv: -sum(kv[1].values()))},
    }


def _desc(d: Dict[str, float]) -> Dict[str, float]:
    return dict(sorted(d.items(), key=lambda kv: -kv[1]))


def per_decode_step_ms(red: Dict, name: str) -> Optional[float]:
    """Total seconds of span ``name`` over the window's decode steps
    (``sched.decode`` spans), in milliseconds."""
    steps = red["spans"].get("sched.decode", [0])[0]
    if not steps or name not in red["spans"]:
        return None
    return red["spans"][name][1] / steps * 1e3


def describe(red: Dict) -> List[str]:
    """The reduction as lines for standard error."""
    out = ["program spans in the window (count, s): " + ", ".join(
        f"{k} {c} {s:.3f}" for k, (c, s) in red["spans"].items())]
    out.append("program instants in the window (count): " + ", ".join(
        f"{k} {c}" for k, c in red["instants"].items()))
    out.append(f"idle {red['idle_s']:.3f} s of {red['window_s']:.3f} s, by "
               "innermost program span: " + ", ".join(
                   f"{k} {v:.3f}" for k, v in red["idle_by_span"].items()))
    for b, row in red["idle_by_bench_span"].items():
        tot = sum(row.values())
        out.append(f"idle under {b} ({tot:.3f} s) by program span: " +
                   ", ".join(f"{k} {v:.3f} ({100 * v / tot:.1f}%)"
                             for k, v in row.items()))
    for name in ("sched.sample", "sched.logits_to_host"):
        v = per_decode_step_ms(red, name)
        out.append(f"{name} per decode step: "
                   + ("none" if v is None else f"{v:.3f} ms"))
    out.append(f"decode kernel {red['decode_kernel_ops']}: "
               f"{red['decode_kernel_s']:.3f} s of device time")
    return out


def traced_run(name: str, seed: int, seconds: float, trace_dir: Path,
               cell: Optional[Dict] = None, require_chip: bool = True,
               log=None) -> Tuple[Dict, str]:
    """One traced run of a cell (``bench.run.run_cell``) with the program's
    tracer on; returns the result and the trace's path, which stays under
    ``trace_dir``."""
    from bench import run as bench_run
    cell = cell or bench_run.load_cell(ROOT, name)
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from repro import obs
    tracer = obs.get_tracer()
    was = tracer.enabled
    tracer.enabled = True
    try:
        result = bench_run.run_cell(ROOT, name, seed, seconds, True,
                                    cell=cell, trace_dir=trace_dir,
                                    require_chip=require_chip, log=log)
    finally:
        tracer.enabled = was
        tracer.clear()
    return result, trace.find(str(trace_dir))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import run as bench_run

    def log(msg):
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    trace_dir = ROOT / ".cache" / "trace" / f"{args.workload}.program"
    shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        cell = bench_run.load_cell(ROOT, args.workload)
        bench_run.enable_caches(ROOT)
        result, path = traced_run(args.workload, args.seed, args.seconds,
                                  trace_dir, cell=cell, log=log)
        red = reduce(path)
    except (bench_run.NoChip, bench_run.NoProgram, KeyError,
            FileNotFoundError) as e:
        log(f"FAILED: {e}")
        return 1
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    for line in describe(red):
        log(line)
    result["program_spans"] = red
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
