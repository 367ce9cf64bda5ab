"""The harness end to end on the CPU at the smoke size (Pallas in interpret
mode), past its look for a chip: the result line keeps its schema and
carries no device metric.  The command itself refuses a
CPU, and a directory without the program."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import run as bench_run
from bench.harness import ProbeLost
from bench_helpers import ROOT, smoke_cell

HOST_METRICS = {"setup_s", "output_tok_s", "itl_p95_ms"}


@pytest.fixture
def result(serving_env):
    return bench_run.run_cell(ROOT, "smoke", 2**33 + 5, 2.0, False,
                              require_chip=False, cell=smoke_cell("closed"),
                              process_start=time.perf_counter(),
                              log=lambda _m: None)


def test_result_line_schema(result):
    line = json.loads(json.dumps(result))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"
    # a CPU run reports the host-clock metrics only: no device number
    assert set(line["metrics"]) <= HOST_METRICS
    assert {"setup_s", "output_tok_s", "itl_p95_ms"} <= set(line["metrics"])
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    for c in line["checks"].values():
        assert set(c) == {"value", "limit", "rule"}
    assert line["checks"]["tokens_compared"]["value"] >= 1


def test_open_loop_reports_ttft(serving_env):
    logged = []
    res = bench_run.run_cell(ROOT, "smoke", 3, 2.0, False,
                             require_chip=False, cell=smoke_cell("open"),
                             process_start=time.perf_counter(),
                             log=logged.append)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "itl_p95_ms"}
    ttft = [m for m in logged if m.startswith("ttft of requests due")]
    assert len(ttft) == 1
    p90 = float(ttft[0].split("p90 ")[1].split(" ms")[0])
    assert p90 > 0


def test_the_window_is_drained_before_the_profiler_stops(serving_env,
                                                         monkeypatch,
                                                         tmp_path):
    """Writing a large trace can outlast the wait for first tokens, so the
    requests due in the window are served before the profiler stops."""
    import jax
    from bench import harness, trace as trace_mod
    order = []
    finish = harness.Client.finish

    def recorded_finish(self, *args):
        out = finish(self, *args)
        order.append("finished")
        return out

    monkeypatch.setattr(harness.Client, "finish", recorded_finish)
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *_a, **_k: order.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: order.append("stop"))
    xplane = tmp_path / "t.xplane.pb"
    xplane.write_bytes(b"")
    monkeypatch.setattr(trace_mod, "find", lambda _d: str(xplane))
    monkeypatch.setattr(trace_mod, "reduce", lambda _p: {})
    server = bench_run.Server(smoke_cell("open"), 4, lambda _m: None)
    served = bench_run.serve(server, 4, 1.5, True, tmp_path,
                             time.perf_counter())
    assert order == ["start", "finished", "stop"]
    assert served.trace == {}


def _skip_decode_hook(monkeypatch):
    """The scheduler decodes through the engine's class method, past the
    instance attribute the probe wraps."""
    from repro.serve import scheduler
    decode = scheduler.Scheduler._decode

    def bypass(self):
        hook = self.engine.__dict__.pop("_decode_token", None)
        try:
            decode(self)
        finally:
            if hook is not None:
                self.engine._decode_token = hook
    monkeypatch.setattr(scheduler.Scheduler, "_decode", bypass)


def _silence_decode_histogram(monkeypatch):
    """The engine's decode step no longer records its histogram."""
    from repro.serve import engine
    monkeypatch.setattr(engine.Engine, "_decode_token",
                        lambda self, cache, batch:
                        self._run_step("decode", cache, batch))


@pytest.mark.parametrize("lose, says", [
    (_skip_decode_hook, "no Engine._decode_token call"),
    (_silence_decode_histogram, "serve.decode_step_s counted 0")])
def test_a_lost_program_hook_fails_the_run(serving_env, monkeypatch, lose,
                                           says):
    lose(monkeypatch)
    with pytest.raises(ProbeLost, match=says):
        bench_run.run_cell(ROOT, "smoke", 5, 1.0, False, require_chip=False,
                           cell=smoke_cell("closed"),
                           process_start=time.perf_counter(),
                           log=lambda _m: None)


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen3-0.6b.decode-heavy", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_cli_refuses_a_cpu(tmp_path):
    run = _cli(ROOT, {"REPRO_CACHE_DIR": str(tmp_path / "cache"),
                      "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax")})
    assert run.returncode != 0
    assert "no TPU" in run.stderr
    assert run.stdout.strip() == ""


def test_cli_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    run = _cli(tmp_path)
    assert run.returncode != 0
    assert "no program" in run.stderr
    assert run.stdout.strip() == ""
