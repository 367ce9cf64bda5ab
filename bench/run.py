"""Runs one benchmark cell on the chip and prints one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the program's serving engine (``repro.serve.Engine``, Pallas
attention, measured kernel plans, bfloat16 weights and cache) with weights
drawn on the device from ``--seed``, and its continuous-batching scheduler;
it warms every prefill and decode shape the cell's traffic uses and runs
the traffic for a pre-roll.  The window then measures for ``--seconds``
seconds while the harness (``bench.harness``) steps the scheduler on the
wall clock.  Afterwards the served tokens of a sample of finished requests
are compared with the plain reference (``bench.correctness``).

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` runs the
same window under the JAX profiler and reports its per-layer metrics.  The
numbers compared for ``correct`` are printed last on standard error, each
beside its limit, and under ``checks`` at the end of the result line.

It needs a TPU whose ``device_kind`` is in ``bench.peaks`` and the program
under ``src/`` of the checkout; without either it exits non-zero and prints
no result.  JAX's compilation cache lives in ``.cache/jax`` of the checkout
(unless ``JAX_COMPILATION_CACHE_DIR`` names another) and the program's plan
store in ``.cache/repro`` (unless ``REPRO_CACHE_DIR`` names another).
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# run as a script, the interpreter puts bench/ itself first on the path,
# where its modules would shadow others of the same name (``trace``)
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# after the window: how long to keep stepping for first tokens of requests
# that were due in it
FIRST_TOKEN_WAIT_S = 60.0


class NoChip(RuntimeError):
    pass


class NoProgram(RuntimeError):
    pass


# ------------------------------------------------------------------ cell --
def load_cell(root: Path, name: str) -> Dict:
    """Everything one cell needs, found by the names ``BENCHMARK.json``
    gives: its entry, configuration and its family's module, traffic mix,
    serving settings and the metrics it reports."""
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    here = root / "bench"

    def load(p):
        with open(p) as f:
            return json.load(f)

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or name in m["workloads"]]

    cj = load(root / config["file"])
    if "family" not in cj:
        raise KeyError(f"{config['file']} names no family")
    return {
        "name": name,
        "chips": entry["chips"],
        "config": cj,
        "family": load_family(here / "families", cj["family"]),
        "traffic": load(here / "traffic" / f"{entry['traffic']}.json"),
        "workload": load(here / "workloads" / f"{name}.json"),
        "end_to_end": mine(bench["end_to_end"]),
        "per_layer": mine(bench["per_layer"]),
        "metrics_dir": here / "metrics",
    }


def load_family(families_dir: Path, name: str):
    """The module of one model family (``bench/families/<name>.py``)."""
    path = families_dir / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no family {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(f"bench_family_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metric(metrics_dir: Path, name: str, run) -> Optional[float]:
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", metrics_dir / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    v = mod.read(run)
    return None if v is None else float(v)


# ---------------------------------------------------------------- device --
def device_report(chips: int, require_chip: bool) -> Dict:
    import jax
    from bench import peaks
    devs = jax.devices()
    d = devs[0]
    rep = {"platform": d.platform, "kind": d.device_kind,
           "count": len(devs)}
    if not require_chip:
        return rep
    if d.platform != "tpu":
        raise NoChip(f"no TPU: JAX's device is {d.platform!r} "
                     f"({d.device_kind}); the benchmark has no CPU branch")
    try:
        peaks.peaks(d.device_kind)
    except peaks.UnknownDevice as e:
        raise NoChip(str(e)) from e
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return rep


def enable_caches(root: Path) -> None:
    """JAX's compilation cache and the program's plan store, at fixed paths
    in the checkout unless the environment names others; every program is
    written, however fast it compiled, so a second run compiles nothing."""
    import jax
    os.environ.setdefault("REPRO_CACHE_DIR", str(root / ".cache" / "repro"))
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".cache" / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# --------------------------------------------------------------- program --
class CompileCounter:
    """Counts JAX compilations: backend compiles and loads from the
    persistent cache."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if name in self.EVENTS:
            self.n += 1


def warm_shapes(sched, eng, prompt_lens: List[int]) -> None:
    """Drive the scheduler through every shape the traffic uses: each
    prompt length's prefill, every prefill group size up to the engine
    batch (the scatter into the slot cache and the row slices take one
    shape per group size), and the decode step over all slots."""
    import numpy as np
    from repro.serve.scheduler import Request
    rid = -1
    rounds = [(prompt_lens[0], g) for g in range(1, eng.scfg.batch + 1)]
    rounds += [(p, 1) for p in prompt_lens[1:]]
    for plen, g in rounds:
        reqs = []
        for _ in range(g):
            reqs.append(Request(rid=rid, tokens=np.full(plen, 1, np.int32),
                                n_new=3, arrival=sched.step))
            rid -= 1
        sched.submit(reqs)
        while sched.pending or sched.queue or sched.active:
            sched.run_step()


@dataclasses.dataclass
class RunView:
    """What the metric readers read (``bench/metrics``)."""
    seconds: float
    setup_s: float
    client: object
    window_steps: list
    counters: Dict
    model: object
    peaks: Optional[Dict]
    trace: Optional[Dict]
    memory_peak_bytes: Optional[int]
    gave_up_at: float


class Server:
    """Set-up: the program's engine and scheduler for one cell, with weights
    drawn from the seed and every shape the cell's traffic uses warmed."""

    def __init__(self, cell: Dict, seed: int, log):
        import jax
        from bench import weights
        from repro.serve.engine import Engine, ServeConfig
        self.cell, self.log = cell, log
        cj, mix, fam = cell["config"], cell["traffic"], cell["family"]
        sv = self.sv = cell["workload"]["serve"]
        seed32 = int(seed) % 2**31
        self.cfg = fam.program_config(cj)
        self.model = fam.work_model(cj)
        self.compiles = CompileCounter()
        t0 = time.perf_counter()
        self.params = jax.block_until_ready(
            weights.make(fam.weight_shapes(cj), seed32))
        t1 = time.perf_counter()
        # the engine's mesh holds the cell's chips and no more devices
        from jax.sharding import AxisType
        mesh = jax.make_mesh((1, cell["chips"]), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2,
                             devices=jax.devices()[:cell["chips"]])
        self.eng = Engine(self.cfg, self.params, ServeConfig(
            batch=sv["engine_batch"], max_len=sv["max_len"],
            temperature=0.0, seed=seed32, cache_dtype="bfloat16",
            kernel_plan="measure"), mesh=mesh)
        # the engine's plan registry (a private name of the program): its
        # misses and fallbacks are read for plan_misses_window and failed
        self.reg = self.eng._reg
        if self.reg is None:
            from bench.harness import ProbeLost
            raise ProbeLost("the engine keeps no plan registry (_reg) under "
                            "kernel_plan='measure'")
        plans = fam.warm_plans(self.cfg, sv)
        if plans:
            self.reg.warmup(plans)
        t2 = time.perf_counter()
        self.sched = self.new_scheduler()
        warm_shapes(self.sched, self.eng, list(mix["prompt_lens"]))
        t3 = time.perf_counter()
        log(f"set-up: weights {t1 - t0:.2f} s, engine and plans "
            f"{t2 - t1:.2f} s, shapes {t3 - t2:.2f} s; "
            f"{self.compiles.n} compilations; registry "
            f"{self.reg.stats.as_dict()}")

    def new_scheduler(self):
        from repro.serve.scheduler import Scheduler
        return Scheduler(self.eng, max_slots=self.sv["max_slots"])

    def reset(self, seed: Optional[int] = None) -> None:
        """A fresh scheduler, and with ``seed`` weights drawn from it for
        the same compiled programs; the old cache and weights are freed
        first."""
        import jax
        from bench import weights
        self.sched = None
        if seed is not None:
            self.params = self.eng.params = None
        gc.collect()
        if seed is not None:
            cj = self.cell["config"]
            self.params = self.eng.params = jax.block_until_ready(
                weights.make(self.cell["family"].weight_shapes(cj),
                             int(seed) % 2**31))
        self.sched = self.new_scheduler()


@dataclasses.dataclass
class Served:
    """What one window left: the client's records and the counters."""
    client: object
    counters: Dict
    setup_s: float
    trace: Optional[Dict]
    memory_peak_bytes: Optional[int]
    gave_up_at: float
    done: list                  # (rid, prompt, served tokens)


def serve(server: Server, seed: int, seconds: float, trace: bool,
          trace_dir: Optional[Path], process_start: float) -> Served:
    """Pre-roll, then the measured window, then first tokens of requests
    due in it; reads memory and the trace before anything else runs."""
    import jax
    import numpy as np
    from bench import trace as trace_mod
    from bench.harness import Client, annotate, engine_counts
    from bench.traffic import generator
    cell, log = server.cell, server.log
    cj, mix, wl = cell["config"], cell["traffic"], cell["workload"]
    eng, reg, sched = server.eng, server.reg, server.sched
    compiles = server.compiles
    clock0 = time.perf_counter() + float(mix.get("preroll_s", 0.0))
    gen = generator.make(mix, wl["load"], seed, cj["vocab_size"], seconds)
    drv = Client(sched, eng, gen, cell["family"], server.model,
                 queue_cap=server.sv["engine_batch"],
                 clock=lambda: time.perf_counter() - clock0)
    snap: Dict = {}

    def on_open():
        snap["setup_s"] = time.perf_counter() - process_start
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        snap["ann"] = annotate("bench.window")
        snap["ann"].__enter__()
        snap.update(engine_counts(eng),
                    misses=reg.stats.misses, fallbacks=reg.stats.fallbacks,
                    compiles=compiles.n)

    try:
        try:
            drv.run(seconds, on_open=on_open)
        finally:
            snap["compiles_end"] = compiles.n
            if "ann" in snap:
                snap["ann"].__exit__(None, None, None)
        end = engine_counts(eng)
        counters = {
            "decode_count": end["decode"][0] - snap["decode"][0],
            "decode_total_s": end["decode"][1] - snap["decode"][1],
            "prefill_count": end["prefill"][0] - snap["prefill"][0],
            "prefill_total_s": end["prefill"][1] - snap["prefill"][1],
            "plan_misses": reg.stats.misses - snap["misses"],
            "fallbacks": reg.stats.fallbacks - snap["fallbacks"],
            "compiles": snap["compiles_end"] - snap["compiles"],
        }
        # the requests due in the window are served before the profiler
        # stops: writing a large trace can take as long as the wait for
        # their first tokens (the reduction reads only ``bench.window``)
        gave_up_at = drv.finish(seconds, FIRST_TOKEN_WAIT_S)
    finally:
        if trace:
            jax.profiler.stop_trace()
    stats = jax.devices()[0].memory_stats() or {}
    late = np.asarray(drv.lateness or [0.0]) * 1e3
    log(f"window closed: {len(drv.stamps)} tokens stamped, {len(drv.reqs)} "
        f"requests sent, {len(drv.deferred)} held back by the queue cap; "
        f"generator lateness p50 {np.percentile(late, 50):.3f} ms, p99 "
        f"{np.percentile(late, 99):.3f} ms, max {late.max():.3f} ms")
    ttft = np.asarray(drv.ttft_s(seconds, gave_up_at) or [np.nan]) * 1e3
    log(f"ttft of requests due in the window: p50 "
        f"{np.percentile(ttft, 50):.3f} ms, p90 {np.percentile(ttft, 90):.3f} "
        "ms (not a metric: too few requests in a window to bound it)")
    log(f"counters: {counters}")
    tr = None
    if trace:
        path = trace_mod.find(str(trace_dir))
        t0 = time.perf_counter()
        tr = trace_mod.reduce(path)
        log(f"trace {path}: {os.path.getsize(path)} bytes, reduced in "
            f"{time.perf_counter() - t0:.1f} s")
    done = []
    for rid, c in sched.completed.items():
        r = drv.reqs.get(rid)
        if r is not None:
            done.append((rid, np.asarray(r.spec.prompt),
                         np.asarray(c.tokens)))
    drv.probe.remove()
    return Served(client=drv, counters=counters, setup_s=snap["setup_s"],
                  trace=tr, memory_peak_bytes=stats.get("peak_bytes_in_use"),
                  gave_up_at=gave_up_at, done=done)


def report(cell: Dict, served: Served, seconds: float, model, pk,
           trace: bool) -> Dict:
    """attempted, failed and the metrics of the cell for this kind of run."""
    drv = served.client
    in_window = [r for r in drv.reqs.values() if 0.0 <= r.spec.due < seconds]
    failed = sum(1 for r in in_window if r.degraded or r.first is None) \
        + served.counters["fallbacks"]
    view = RunView(seconds=seconds, setup_s=served.setup_s, client=drv,
                   window_steps=[s for s in drv.steps
                                 if 0.0 <= s.start < seconds],
                   counters=served.counters, model=model, peaks=pk,
                   trace=served.trace,
                   memory_peak_bytes=served.memory_peak_bytes,
                   gave_up_at=served.gave_up_at)
    metrics = {}
    for m in (cell["per_layer"] if trace else cell["end_to_end"]):
        v = read_metric(cell["metrics_dir"], m["name"], view)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    return {"attempted": len(in_window), "failed": failed,
            "metrics": metrics}


def check(cell: Dict, params, done, seed: int, log, control: bool = False):
    """The comparison with the plain reference (``bench.correctness``);
    with ``control`` also the float8 control's widest gap."""
    from bench import correctness
    wl, cj = cell["workload"], cell["config"]
    cc, max_len = wl["correct"], wl["serve"]["max_len"]
    picked = correctness.sample(done, seed, cc["sample_requests"],
                                cc["sample_tokens"])
    t0 = time.perf_counter()
    ref = cell["family"].reference
    gaps = correctness.served_gaps(ref, params, cj, picked, max_len)
    log(f"reference over {len(picked)} requests, {gaps.size} served tokens "
        f"in {time.perf_counter() - t0:.1f} s")
    chk = correctness.checks(gaps, cc["gap_limit"])
    if not control:
        return chk, None
    ctrl = correctness.control_gaps(ref, params, cj, picked, max_len)
    return chk, correctness.checks(ctrl, cc["gap_limit"])


def run_cell(root: Path, name: str, seed: int, seconds: float, trace: bool,
             *, require_chip: bool = True, cell: Optional[Dict] = None,
             trace_dir: Optional[Path] = None,
             process_start: float = PROCESS_START, log=None) -> Dict:
    """One run of one cell; returns the result object (the JSON line)."""
    log = log or (lambda msg: print(f"[bench] {msg}", file=sys.stderr,
                                    flush=True))
    cell = cell or load_cell(root, name)
    if not (root / "src" / "repro").is_dir():
        raise NoProgram(f"no program under {root / 'src'}: run the benchmark "
                        "from a checkout of the repo")
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    from bench import correctness, peaks as peaks_mod
    device = device_report(cell["chips"], require_chip)
    try:
        pk = peaks_mod.peaks(device["kind"])
    except peaks_mod.UnknownDevice:
        pk = None
    server = Server(cell, seed, log)
    served = serve(server, seed, seconds, trace, trace_dir, process_start)
    out = report(cell, served, seconds, server.model, pk, trace)
    params = server.params
    # the program's state goes before the reference runs
    del server
    served.client = None
    gc.collect()
    chk, _ = check(cell, params, served.done, seed, log)
    result = {"correct": correctness.passed(chk), **out,
              "device": dict(device,
                             memory_peak_bytes=served.memory_peak_bytes)}
    if served.trace is not None:
        tr = served.trace
        result["device"]["busy_s"] = tr["busy_s"]
        result["device"]["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["checks"] = chk
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    trace_dir = ROOT / ".cache" / "trace" / args.workload
    try:
        cell = load_cell(ROOT, args.workload)
        if not (ROOT / "src" / "repro").is_dir():
            raise NoProgram(f"no program under {ROOT / 'src'}")
        enable_caches(ROOT)
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), cell=cell, trace_dir=trace_dir)
    except (NoChip, NoProgram, KeyError, FileNotFoundError) as e:
        print(f"[bench] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    from bench import correctness
    for line in correctness.describe(result["checks"]):
        print(f"[bench] check {line}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
