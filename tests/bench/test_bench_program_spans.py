"""The program's own spans and counters as the benchmark sees them.

Counters: in the smoke rehearsal (CPU, Pallas in interpret mode), the
scheduler's ``sched.*`` counters move with what the harness's probe counted
in the same run.

Reduction: ``bench.program_spans`` on a second small trace recorded on a TPU
v5 lite, ``data/v5e_decode_spans.xplane.pb``.  It was recorded as
``data/v5e_decode.xplane.pb`` was (the qwen3-0.6b configuration cut to 2
layers, 4 slots, an engine batch of 2, prompts of 128 tokens, a 0.12 s
window served by ``bench.run.run_cell`` with tracing on), with the
program's tracer on (``bench.program_spans.traced_run``):
its host planes hold the ``sched.*``, ``serve.*`` and ``engine.*`` spans and
its decode kernel is named ``decode_attention_m<M><mode>``.
"""
from __future__ import annotations

import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import peaks, program_spans, trace
from bench import run as bench_run
from bench.harness import StepRecord
from bench_helpers import smoke_cell

DATA = Path(__file__).parent / "data"
FIXTURE = DATA / "v5e_decode_spans.xplane.pb"
UNNAMED = DATA / "v5e_decode.xplane.pb"


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    """One smoke-size server for both loops (they share configuration and
    prompt lengths), in the private serving environment of
    ``conftest.serving_env``."""
    from repro.compiler.registry import set_default_registry
    from repro.obs import metrics
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_CACHE_DIR",
                  str(tmp_path_factory.mktemp("program_spans")))
        old_metrics = metrics.set_default_metrics(metrics.MetricsRegistry())
        old_reg = set_default_registry(None)
        try:
            yield bench_run.Server(smoke_cell("closed"), 11, lambda _m: None)
        finally:
            set_default_registry(old_reg)
            metrics.set_default_metrics(old_metrics)


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_program_counters_match_the_probe(server, loop):
    from repro import obs
    cell = server.cell = smoke_cell(loop)
    server.reset()
    before = obs.snapshot(include_views=False)["counters"]
    compiles = server.compiles.n
    served = bench_run.serve(server, 11, 1.5, False, None,
                             time.perf_counter())
    after = obs.snapshot(include_views=False)["counters"]

    def moved(name):
        return after.get(name, 0) - before.get(name, 0)

    steps = served.client.steps
    dec = [s for s in steps if s.kind == "decode"]
    pre = [s for s in steps if s.kind == "prefill"]
    assert dec and pre
    assert moved("sched.decode_steps") == len(dec)
    assert moved("sched.decode_lanes") == sum(len(s.real) for s in dec)
    assert moved("sched.prefill_rows") == sum(s.rows for s in pre)
    assert moved("sched.prefill_pad_rows") == \
        sum(s.rows - len(s.real) for s in pre)
    # float32 logits of every slot, once per decode step
    slots = cell["workload"]["serve"]["max_slots"]
    assert moved("sched.logits_host_bytes") == \
        len(dec) * slots * cell["config"]["vocab_size"] * 4
    assert moved("jax.compiles") == server.compiles.n - compiles


@pytest.fixture(scope="module")
def reduced():
    if not FIXTURE.exists():
        pytest.fail(f"missing recorded trace {FIXTURE}")
    return program_spans.reduce(str(FIXTURE))


def test_recorded_span_trace_is_small():
    assert FIXTURE.stat().st_size < 1 << 20


def test_decode_kernel_carries_its_name(reduced):
    ops = reduced["decode_kernel_ops"]
    assert ops and all(op.startswith("%decode_attention_m") for op in ops)
    assert 0 < reduced["decode_kernel_s"] < reduced["window_s"]
    # the same name reaches the harness's own breakdown
    names = [n for n, _ in trace.reduce(str(FIXTURE))["device_ops"]]
    assert any(n.startswith("%decode_attention_m") for n in names)


def test_sampling_spans_are_in_the_window(reduced):
    spans = reduced["spans"]
    for name in ("sched.step", "sched.decode", "serve.decode",
                 "engine.dispatch", "engine.wait", "sched.logits_to_host",
                 "sched.sample"):
        assert spans[name][0] >= 1 and spans[name][1] > 0, name
    steps = spans["sched.decode"][0]
    assert spans["sched.sample"][0] == steps
    assert program_spans.per_decode_step_ms(reduced, "sched.sample") > 0


def test_counters_are_instants_not_spans(reduced):
    """A counter's instant is counted apart, as an instant, and takes no
    idle: only spans can hold a gap."""
    counters = ("sched.decode_steps", "sched.decode_lanes",
                "sched.logits_host_bytes")
    for name in counters:
        assert name not in reduced["spans"]
        assert name not in reduced["idle_by_span"]
        assert reduced["instants"][name] == \
            reduced["spans"]["sched.decode"][0]


def test_attributed_idle_is_the_window_idle(reduced):
    harness = trace.reduce(str(FIXTURE))
    idle = harness["window_s"] - harness["busy_s"]
    assert sum(reduced["idle_by_span"].values()) <= idle * 1.0001
    assert reduced["idle_s"] == pytest.approx(idle, rel=1e-6)
    assert set(reduced["idle_by_span"]) <= {"none"} | set(reduced["spans"])
    by_bench = reduced["idle_by_bench_span"]
    assert set(by_bench) == {name for name, _ in harness["idle_gaps"]}
    for name, secs in harness["idle_gaps"]:
        assert sum(by_bench[name].values()) == pytest.approx(secs)


def test_a_trace_without_program_spans_reads_nothing():
    red = program_spans.reduce(str(UNNAMED))
    assert red["spans"] == {} and red["instants"] == {}
    assert red["decode_kernel_ops"] == []
    assert red["decode_kernel_s"] == 0
    assert list(red["idle_by_span"]) == ["none"]
    assert program_spans.per_decode_step_ms(red, "sched.sample") is None


@pytest.mark.parametrize("path, found", [(FIXTURE, True), (UNNAMED, False)])
def test_decode_roofline_reads_the_named_kernel(path, found):
    """The reader finds the decode kernel by its name, and finds nothing
    in a trace whose kernels are unnamed."""
    cell = smoke_cell()
    fam = cell["family"]
    cj = dict(cell["config"], num_hidden_layers=2, hidden_size=1024,
              num_attention_heads=16, num_key_value_heads=8, head_dim=128)
    m = fam.work_model(cj)
    step = StepRecord("decode", 0.0, 0.001, rows=4, real=[140] * 4,
                      attn=fam.decode_attention(m, [140] * 4))
    run = SimpleNamespace(trace=trace.reduce(str(path)),
                          peaks=peaks.peaks("TPU v5 lite"), model=m,
                          window_steps=[step])
    v = bench_run.read_metric(bench_run.ROOT / "bench" / "metrics",
                              "decode_roofline", run)
    assert (v is not None) == found
    if found:
        assert 0 < v <= 100
