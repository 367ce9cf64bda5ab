"""The trace reduction (``bench.trace``): interval arithmetic, op naming,
and a small trace recorded on a TPU v5 lite through the harness.

The recorded trace (``data/v5e_decode.xplane.pb``, 0.5 MB) is the
qwen3-0.6b configuration cut to 2 layers, with 4 slots, an engine batch of
2 and prompts of 128 tokens, served by ``bench.run.run_cell`` with tracing
on over a 0.12 s window; the window span opened after the step that was
running at its start, so it holds 0.054 s: decode steps with the decode
kernel, and the harness's spans.
"""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace

FIXTURE = Path(__file__).parent / "data" / "v5e_decode.xplane.pb"


def test_union_and_gaps():
    u = trace._union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [[0, 3], [5, 9]]
    assert trace._gaps(u, 0, 10) == [(3, 5), (9, 10)]
    assert trace._gaps([], 2, 4) == [(2, 4)]


def test_gaps_are_named_by_the_innermost_span():
    spans = [(0, 100, "bench.run_step", 0), (10, 40, "bench.decode", 0),
             (40, 90, "bench.host_sampling", 0), (120, 130, "bench.sleep", 0)]
    assert trace._label(spans, [5, 20, 50, 95, 125, 200]) == [
        "bench.run_step", "bench.decode", "bench.host_sampling",
        "bench.run_step", "bench.sleep", "none"]


def test_op_names():
    loop = ("%while.2 = (s32[], bf16[32,1,1024]) while((s32[], "
            "bf16[32,1,1024]) %tuple.51), condition=%c, body=%b")
    kern = ('%run_fn.5 = bf16[32,16,1,128]{3,2,1,0} custom-call(bf16[32] '
            '%pad), custom_call_target="tpu_custom_call"')
    fus = ("%fusion.120 = bf16[32,3072]{1,0} fusion(bf16[28] %g), "
           "kind=kOutput, calls=%fused_computation.9")
    assert trace.is_container(loop)
    assert not trace.is_container(kern) and not trace.is_container(fus)
    assert trace.is_custom_call(kern) and not trace.is_custom_call(fus)
    assert trace.op_name(fus) == "%fusion.120"


@pytest.fixture(scope="module")
def reduced():
    if not FIXTURE.exists():
        pytest.fail(f"missing recorded trace {FIXTURE}")
    return trace.reduce(str(FIXTURE))


def test_recorded_trace_is_small():
    assert FIXTURE.stat().st_size < 1 << 20


def test_recorded_trace_busy_and_idle(reduced):
    assert reduced["chips"] == 1
    assert 0.02 < reduced["window_s"] < 1.0
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    # the attention kernels ran, inside the busy time
    assert 0 < reduced["custom_call_s"] < reduced["busy_s"]


def test_recorded_trace_breakdown(reduced):
    ops, gaps = reduced["device_ops"], reduced["idle_gaps"]
    assert 1 <= len(ops) <= 10 and 1 <= len(gaps) <= 10
    assert any("tpu_custom_call" in name for name, _ in ops)
    assert all(not name.startswith("%while") for name, _ in ops)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    labels = {name for name, _ in gaps}
    assert labels <= {"none"} | {f"bench.{n}" for n in (
        "submit", "sleep", "run_step", "prefill", "decode",
        "host_sampling")}
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(s for _, s in gaps) <= idle * 1.0001
