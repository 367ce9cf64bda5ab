"""Tier-1 contract for ``repro.obs`` — the tracing/metrics/profiling spine.

Covers the properties the rest of the repo leans on: spans nest correctly
(including under exceptions), the Chrome-trace export is valid Perfetto
input, metrics snapshots are pure JSON and round-trip, the cache health
counters fire on corruption/staleness, and StepTimer's percentile stats are
views over the obs histogram (one percentile implementation, not two).
"""
import contextlib
import json
import threading
import time

import pytest

from repro import obs
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer


@pytest.fixture()
def tracer():
    """Fresh enabled tracer installed as the process tracer."""
    tr = Tracer(enabled=True)
    old = obs.set_tracer(tr)
    yield tr
    obs.set_tracer(old)


@pytest.fixture()
def metrics():
    """Fresh metrics registry installed as the process default."""
    reg = MetricsRegistry()
    old = obs.set_default_metrics(reg)
    yield reg
    obs.set_default_metrics(old)


# --------------------------------------------------------------- tracing ----
def test_spans_nest_with_parent_and_depth(tracer):
    with obs.span("outer", cat="t", a=1):
        with obs.span("inner"):
            time.sleep(0.001)

    by_name = {r["name"]: r for r in tracer.spans()}
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["depth"] == 0 and outer["parent"] is None
    assert inner["depth"] == 1 and inner["parent"] == "outer"
    assert outer["args"] == {"a": 1}
    # time containment: the child interval lies inside the parent's
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert inner["dur"] >= 1000.0  # slept 1ms; ts/dur are microseconds


def test_spans_record_and_unwind_on_exception(tracer):
    with pytest.raises(ValueError):
        with obs.span("outer"):
            with obs.span("boom"):
                raise ValueError("x")

    boom = tracer.spans("boom")[0]
    outer = tracer.spans("outer")[0]
    assert boom["args"]["error"] == "ValueError"
    assert outer["args"]["error"] == "ValueError"
    assert boom["parent"] == "outer" and boom["depth"] == 1

    # the thread-local stack fully unwound: a later span is a root again
    with obs.span("after"):
        pass
    after = tracer.spans("after")[0]
    assert after["depth"] == 0 and after["parent"] is None


def test_mid_span_attrs_and_instants(tracer):
    with obs.span("work") as sp:
        sp.set(factor=4)
        obs.instant("tick", n=1)
    rec = tracer.spans("work")[0]
    assert rec["args"]["factor"] == 4
    events = [r for r in tracer.records if r["type"] == "event"]
    assert events and events[0]["name"] == "tick"


def test_disabled_tracer_is_noop_and_shared(tracer):
    tracer.enabled = False
    handle = obs.span("never")
    with handle as sp:
        sp.set(anything=1)  # must not raise on the null handle
    assert obs.span("never2") is handle  # one shared null object
    obs.instant("never3")
    assert tracer.records == []


def test_spans_carry_distinct_tids_across_threads(tracer):
    def work():
        with obs.span("child_thread"):
            pass

    t = threading.Thread(target=work)
    with obs.span("main_thread"):
        t.start()
        t.join()
    tids = {r["name"]: r["tid"] for r in tracer.spans()}
    assert tids["main_thread"] != tids["child_thread"]
    # a thread's first span is a root on its own stack, not a child of main
    child = tracer.spans("child_thread")[0]
    assert child["depth"] == 0 and child["parent"] is None


def test_chrome_trace_export_is_valid(tracer, tmp_path):
    with obs.span("outer", cat="serve", k="v"):
        with obs.span("inner"):
            pass
    obs.instant("hit", kind="cache")

    path = tmp_path / "trace.json"
    obs.write_trace(path, metadata={"run": "test"})
    trace = json.loads(path.read_text())  # must be parseable JSON

    assert trace["displayTimeUnit"] == "ms"
    assert trace["otherData"] == {"run": "test"}
    events = trace["traceEvents"]
    assert len(events) == 3
    for e in events:
        assert {"name", "cat", "ph", "ts", "pid", "tid", "args"} <= set(e)
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        else:
            assert e["ph"] == "i" and e["s"] == "t"
    assert {e["ph"] for e in events} == {"X", "i"}


def test_jsonl_event_log(tracer, tmp_path):
    with obs.span("a"):
        pass
    obs.instant("b")
    path = tmp_path / "events.jsonl"
    tracer.write_jsonl(path)
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["type"] for r in lines] == ["span", "event"]


# --------------------------------------------------------------- metrics ----
def test_metrics_snapshot_roundtrips(metrics):
    obs.count("c.hits", 3)
    obs.gauge("g.frac", 0.5)
    for v in (1.0, 2.0, 3.0):
        obs.observe("h.lat_s", v)

    snap = obs.snapshot()
    assert snap["counters"]["c.hits"] == 3
    assert snap["gauges"]["g.frac"] == 0.5
    h = snap["histograms"]["h.lat_s"]
    assert h["count"] == 3 and h["min"] == 1.0 and h["max"] == 3.0
    assert h["p50"] == 2.0

    # pure JSON: survives a serialize→parse cycle unchanged
    assert json.loads(json.dumps(snap)) == snap

    metrics.reset()
    assert obs.snapshot()["counters"] == {}


def test_histogram_percentiles_and_compaction():
    h = obs.Histogram(max_samples=64)
    for v in range(1, 101):
        h.record(float(v))
    # count/total/min/max stay exact through compaction
    assert h.count == 100 and h.total == sum(range(1, 101))
    assert h.min == 1.0 and h.max == 100.0
    assert len(h.values) <= 64
    # nearest-rank percentiles over the retained sample stay ordered and
    # in-range
    p50, p99 = h.percentile(50), h.percentile(99)
    assert 1.0 <= p50 <= p99 <= 100.0
    assert 30.0 <= p50 <= 70.0


def test_views_absorb_existing_stat_objects(metrics):
    obs.register_view("good", lambda: {"hits": 1})
    obs.register_view("bad", lambda: 1 / 0)
    snap = obs.snapshot()
    assert snap["views"]["good"] == {"hits": 1}
    # a broken view degrades to an error entry, never breaks the snapshot
    assert "error" in snap["views"]["bad"]
    assert json.loads(json.dumps(snap)) == snap


def test_count_emits_instant_when_tracing(tracer, metrics):
    obs.count("cache.hit", key="k")
    assert metrics.counter("cache.hit").value == 1
    events = [r for r in tracer.records if r["type"] == "event"]
    assert events[0]["name"] == "cache.hit"
    assert events[0]["args"] == {"key": "k"}


def test_formatters(metrics):
    obs.count("cache.hit", 2)
    obs.observe("serve.decode_step_s", 0.001)
    text = obs.format_snapshot(obs.snapshot())
    assert "cache.hit" in text and "serve.decode_step_s" in text
    assert "p99" in text

    phases = {"decode": {"compile_s": 0.5, "warm": {
        "calls": 3, "mean_s": 0.001, "p50_s": 0.001, "p99_s": 0.002,
        "best_s": 0.0009}}}
    lines = obs.format_phases(phases)
    assert "decode" in lines and "p99=2.00ms" in lines and "3 steps" in lines


# ----------------------------------------------------- cache health events --
def test_cache_corrupt_counter(metrics, tmp_path):
    from repro.compiler.cache import CompileCache

    path = tmp_path / "cache.json"
    path.write_text("{ this is not json")
    cache = CompileCache(path)
    assert cache.get("k") is None  # degrade contract unchanged
    assert metrics.counter("cache.corrupt").value == 1


def test_cache_stale_jax_version_counter(metrics, tmp_path):
    from repro.compiler.cache import CompileCache, _env_fingerprint

    path = tmp_path / "cache.json"
    cache = CompileCache(path)
    cache.put("fresh", {"factor": 2})       # stamped with the live env
    entries = json.loads(path.read_text())
    entries["entries"]["old"] = {"factor": 4, "env": "jax-0.0.0-older"}
    path.write_text(json.dumps(entries))

    reread = CompileCache(path)
    assert reread.get("fresh")["factor"] == 2
    assert reread.get("fresh")["env"] == _env_fingerprint()
    assert metrics.counter("cache.stale_jax_version").value == 1
    assert metrics.counter("cache.corrupt").value == 0


# ---------------------------------------------------------------- timers ----
def test_steptimer_warm_cold_split_and_percentiles():
    from repro.launch.steps import StepTimer

    timer = StepTimer()
    for _ in range(6):
        timer.run("decode", lambda: time.sleep(0.001))
    st = timer.stats()["decode"]

    # legacy flat keys survive (compat with older BENCH_* consumers)
    assert st["steps"] == 5 and st["compile_s"] > 0
    assert st["steady_mean_s"] is not None
    # explicit warm/cold split + percentiles
    assert st["cold"]["calls"] == 1
    assert st["cold"]["total_s"] == st["compile_s"]
    assert st["warm"]["calls"] == 5
    assert st["warm"]["p50_s"] <= st["warm"]["p99_s"]
    assert st["steady_p50_s"] == st["warm"]["p50_s"]
    assert st["steady_p99_s"] == st["warm"]["p99_s"]
    assert timer.steady["decode"]  # compat view over the histogram samples


# --------------------------------------------------------------- profile ----
def test_profile_without_logdir_is_a_plain_span(tracer):
    with obs.profile("window", tag="x"):
        pass
    rec = tracer.spans("window")[0]
    assert rec["cat"] == "profile"
    assert rec["args"]["profiled"] is False and rec["args"]["tag"] == "x"


def _host_events(logdir):
    """``(name, start_ns, end_ns, stats)`` of every host event in the
    ``jax.profiler`` capture under ``logdir``, in start order."""
    import glob
    from jax.profiler import ProfileData
    path, = glob.glob(str(logdir / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def test_sink_puts_spans_in_the_profiler_trace(tracer, tmp_path):
    """While tracing, spans, step spans and instants are host events of a
    ``jax.profiler`` capture on the CPU, nested as they ran; attributes
    become the events' stats, and an instant's carry ``instant``."""
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.step_span("t.step", 7):
            with obs.span("t.outer", rows=3):
                with obs.span("t.inner"):
                    time.sleep(0.001)
                obs.instant("t.tick", n=2)
    finally:
        jax.profiler.stop_trace()
    evs = {e[0]: e for e in _host_events(tmp_path)
           if e[0].startswith("t.")}
    assert set(evs) == {"t.step", "t.outer", "t.inner", "t.tick"}
    step, outer, inner, tick = (evs[k] for k in
                                ("t.step", "t.outer", "t.inner", "t.tick"))
    assert step[1] <= outer[1] <= inner[1] < inner[2] <= outer[2] <= step[2]
    # an instant is an annotation entered and left at once
    assert inner[2] <= tick[1] <= tick[2] <= outer[2]
    assert tick[2] - tick[1] < inner[2] - inner[1]
    assert step[3]["step_num"] == 7 and outer[3]["rows"] == 3
    assert tick[3] == {"n": 2, "instant": 1}
    assert "instant" not in outer[3] and "instant" not in inner[3]
    # the JSON records are kept as before, the marker left out
    assert [r["name"] for r in tracer.spans()] == ["t.inner", "t.outer",
                                                    "t.step"]
    assert [r["args"] for r in tracer.records if r["type"] == "event"] == \
        [{"n": 2}]


@pytest.mark.parametrize("enabled, jax_loaded",
                         [(False, True), (True, False)])
def test_no_annotation_unless_tracing_with_the_sink(tracer, monkeypatch,
                                                    enabled, jax_loaded):
    """Tracing off returns the shared null handle and makes no
    annotation; tracing on makes none until the process has loaded JAX,
    and never loads it."""
    import sys
    from repro.obs import trace as trace_mod
    made = []

    def fake(*a, **k):
        made.append(a)
        return contextlib.nullcontext()
    monkeypatch.setattr(trace_mod, "_PROFILER", (fake, fake))
    if not jax_loaded:
        monkeypatch.setattr(trace_mod, "_PROFILER", None)
        monkeypatch.setitem(sys.modules, "jax", None)
    tracer.enabled = enabled
    handle = obs.span("x")
    with handle, obs.step_span("y", 1):
        obs.instant("z")
    assert made == []
    if not jax_loaded:
        assert trace_mod._PROFILER is None
    if not enabled:
        assert handle is obs.span("other")
        assert tracer.records == []
    else:
        assert [r["name"] for r in tracer.records] == ["z", "y", "x"]


def test_obs_imports_without_jax(tmp_path):
    """``repro.obs`` needs the standard library alone, and traces without
    JAX."""
    import subprocess
    import sys
    code = ("import sys; sys.modules['jax'] = None\n"
            "from repro import obs\n"
            "from repro.obs import trace\n"
            "obs.enable()\n"
            "with obs.span('a'):\n"
            "    obs.count('c')\n"
            "assert obs.snapshot()['counters']['c'] == 1\n"
            "assert [r['name'] for r in obs.get_tracer().records] == "
            "['c', 'a']\n"
            "if trace._profiler() is None:\n"
            "    print('no jax')\n")
    src = str(__import__("pathlib").Path(__file__).parents[1] / "src")
    run = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env={"PYTHONPATH": src, "PATH": ""})
    assert run.returncode == 0, run.stderr
    assert run.stdout.strip() == "no jax"


def test_watch_compiles_counts_and_marks_compiles(tracer, metrics):
    """``jax.compiles`` counts each compilation once the listener is on;
    while tracing, each also drops a ``jax.compile`` instant with its
    duration."""
    import jax
    import numpy as np
    obs.watch_compiles()
    obs.watch_compiles()            # once per process, however often called
    jax.jit(lambda x: x * 3 + 1)(np.ones(17, np.float32))
    n = metrics.counter("jax.compiles").value
    assert n >= 1
    marks = [r for r in tracer.records if r["name"] == "jax.compile"]
    assert len(marks) == n and all(m["args"]["dur_s"] >= 0 for m in marks)


def test_profile_with_logdir_captures_program_spans(tmp_path):
    """``obs.profile(logdir=...)`` turns tracing on for its capture, and
    back off after."""
    tracer = obs.get_tracer()
    assert not tracer.enabled
    with obs.profile("t.window", logdir=str(tmp_path)) as sp:
        assert sp is not None and tracer.enabled
        with obs.span("t.work"):
            pass
    assert not tracer.enabled
    names = [e[0] for e in _host_events(tmp_path)]
    assert "t.window" in names and "t.work" in names
    tracer.clear()


# ------------------------------------------------- end-to-end serve trace ----
def test_engine_generate_produces_nested_trace(tracer, metrics, tmp_path,
                                               monkeypatch):
    """One Engine.generate() yields warmup/prefill/per-token decode spans
    with monotonic timestamps, TTFT on the generate span, and latency
    histograms in the metrics snapshot."""
    import jax
    import jax.numpy as jnp
    from repro.compiler.registry import PlanRegistry, set_default_registry
    from repro.configs.base import load_arch
    from repro.models import model as model_mod
    from repro.serve.engine import Engine, ServeConfig

    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path / "cache"))
    prev = set_default_registry(PlanRegistry())
    try:
        cfg = load_arch("qwen3-0.6b", smoke=True)
        params = model_mod.init_params(cfg, jax.random.PRNGKey(0),
                                       dtype=jnp.float32)
        eng = Engine(cfg, params, ServeConfig(batch=2, max_len=16))
        prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0,
                                     cfg.vocab_size)
        eng.generate(prompts, 3)
    finally:
        set_default_registry(prev)

    gen = tracer.spans("serve.generate")[0]
    assert tracer.spans("serve.prefill")
    decodes = sorted(tracer.spans("serve.decode"), key=lambda r: r["ts"])
    assert len(decodes) == 3
    for d in decodes:
        assert d["parent"] == "serve.generate" and d["depth"] == 1
        assert gen["ts"] <= d["ts"]
        assert d["ts"] + d["dur"] <= gen["ts"] + gen["dur"]
    assert all(a["ts"] + a["dur"] <= b["ts"]
               for a, b in zip(decodes, decodes[1:]))
    assert gen["args"]["ttft_s"] > 0
    # the step timer splits each call into its launch and its wait
    assert [r["parent"] for r in tracer.spans("engine.init_cache")] == \
        ["serve.prefill"]
    for name in ("engine.dispatch", "engine.wait"):
        parents = [r["parent"] for r in tracer.spans(name)]
        assert parents.count("serve.decode") == 3
        assert parents.count("serve.prefill") == 1

    snap = obs.snapshot()
    assert snap["counters"]["serve.tokens"] == 6
    assert snap["histograms"]["serve.ttft_s"]["count"] == 1
    assert snap["histograms"]["serve.decode_step_s"]["count"] == 3
    # the engine's stats are published as a snapshot view
    assert snap["views"]["serve.engine"]["phases"]["decode"]["steps"] >= 1


def test_scheduler_spans_nest_and_name_their_requests(tracer, metrics):
    """Each scheduler step is a step span holding its admissions (with the
    admitted rids, rows and padding) and its decode (logits copy, then
    sampling); the counters add up to the spans."""
    import dataclasses
    import jax
    from repro.configs.base import load_arch
    from repro.models import model as model_mod
    from repro.serve import scheduler as sched
    from repro.serve.engine import Engine, ServeConfig

    cfg = dataclasses.replace(load_arch("qwen3-0.6b", smoke=True),
                              attention_impl="xla_chunked",
                              kernel_plan="direct")
    params = model_mod.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, ServeConfig(batch=2, max_len=16, warmup=False))
    reqs = sched.synthetic_workload(5, seed=3, prompt_lens=(2, 4),
                                    new_tokens=(2, 3), arrival_rate=0.5,
                                    vocab=cfg.vocab_size)
    eng.serve_stream(reqs, max_slots=3)

    steps = tracer.spans("sched.step")
    assert [r["depth"] for r in steps] == [1] * len(steps)   # serve.stream
    admits = tracer.spans("sched.admit")
    # rids are space-separated text: a profiler stat is comma-separated
    groups = [[int(x) for x in r["args"]["rids"].split()] for r in admits]
    assert sorted(rid for g in groups for rid in g) == list(range(5))
    for r, g in zip(admits, groups):
        assert r["parent"] == "sched.step"
        # a group pads to the engine batch (2) when smaller
        assert r["args"]["rows"] == max(2, len(g))
        assert r["args"]["pad_rows"] == r["args"]["rows"] - len(g)
    inserts = tracer.spans("sched.insert_rows")
    assert [r["args"]["rids"] for r in inserts] == \
        [r["args"]["rids"] for r in admits]
    assert {r["parent"] for r in inserts} == {"sched.admit"}
    assert {r["parent"] for r in tracer.spans("serve.prefill")} == \
        {"sched.admit"}
    decodes = tracer.spans("sched.decode")
    for name in ("serve.decode", "sched.logits_to_host", "sched.sample"):
        assert [r["parent"] for r in tracer.spans(name)] == \
            ["sched.decode"] * len(decodes)
    c = metrics.snapshot(include_views=False)["counters"]
    assert c["sched.decode_steps"] == len(decodes)
    assert c["sched.decode_lanes"] == sum(r["args"]["lanes"]
                                          for r in decodes)
    assert c["sched.prefill_rows"] == sum(r["args"]["rows"] for r in admits)
    assert c["sched.prefill_pad_rows"] == \
        sum(r["args"]["pad_rows"] for r in admits)
    assert c["sched.logits_host_bytes"] == \
        len(decodes) * 3 * cfg.vocab_size * 4
    for gone in ("serve.stream_tokens", "sched.ttft_steps"):
        assert gone not in c
        assert gone not in metrics.snapshot(include_views=False)[
            "histograms"]


def test_scheduler_metrics_on_two_rate_trace(metrics, tmp_path, monkeypatch):
    """Satellite: obs metrics under concurrency.  The same synthetic
    workload streamed at a bursty vs a trickle arrival rate must emit sane
    scheduler metrics: the slot-occupancy gauge never exceeds max_slots
    (and drains to 0), the queue-wait histogram records every request, and
    waits are monotone with arrival rate — the bursty trace queues at
    least as hard as the trickle."""
    import dataclasses
    import jax
    import numpy as np
    from repro.configs.base import load_arch
    from repro.models import model as model_mod
    from repro.serve import scheduler as sched
    from repro.serve.engine import Engine, ServeConfig

    cfg = dataclasses.replace(load_arch("qwen3-0.6b", smoke=True),
                              attention_impl="xla_chunked",
                              kernel_plan="direct")
    params = model_mod.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, ServeConfig(batch=2, max_len=16, warmup=False))

    def run(rate):
        reqs = sched.synthetic_workload(8, seed=4, prompt_lens=(2, 4),
                                        new_tokens=(2, 4), arrival_rate=rate,
                                        vocab=cfg.vocab_size)
        occs = []
        before = metrics.histogram("sched.queue_wait_steps").count
        res = eng.serve_stream(
            reqs, step_hook=lambda s: occs.append(s["occupancy"]))
        h = metrics.histogram("sched.queue_wait_steps")
        waits = [r.queue_wait_steps for r in res]
        return occs, waits, h.count - before

    occ_burst, waits_burst, n_burst = run(1.0)     # all arrive at step 0
    occ_slow, waits_slow, n_slow = run(0.2)

    for occs in (occ_burst, occ_slow):
        assert all(0 <= o <= 2 for o in occs), "occupancy exceeded max_slots"
    assert max(occ_burst) == 2, "the burst never filled the slots"
    # the gauge drained with the stream
    snap = obs.snapshot(include_views=False)
    assert snap["gauges"]["sched.slot_occupancy"] == 0
    assert snap["gauges"]["sched.queue_depth"] == 0
    # one histogram sample per admitted request, none dropped
    assert n_burst == 8 and n_slow == 8
    # monotone with arrival rate: the burst queues at least as hard
    assert np.mean(waits_burst) >= np.mean(waits_slow)
    assert max(waits_burst) >= max(waits_slow)
    assert max(waits_burst) > 0, "the burst never exercised the queue"
    # per-request latency histograms populated alongside
    assert metrics.histogram("serve.request_ttft_s").count == 16
    assert metrics.histogram("serve.request_tpot_s").count == 16
