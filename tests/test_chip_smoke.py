"""``chip_smoke.py`` off the chip: it must refuse a CPU, and its health
check must turn every fallback rung of the serving path into a failure.

The script itself only runs on a TPU; here its health check
(``plan_faults``) runs against a CPU engine at the smoke size, where the
attention regions emit at the ``carryloop`` tier instead of ``pallas``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

import jax

from repro import compiler
from repro.compiler.registry import PlanRegistry, set_default_registry
from repro.configs.base import load_arch
from repro.models import model as model_mod
from repro.obs import metrics
from repro.serve.engine import Engine, ServeConfig
from repro.testing import faults

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_a_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         cwd=tmp_path, env=env, capture_output=True,
                         text=True, timeout=300)
    assert run.returncode != 0
    assert "no TPU" in run.stderr
    assert '"ok"' not in run.stdout


@pytest.fixture
def serving_env(tmp_path, monkeypatch):
    """Private plan store, fresh metrics and registry, no fault left over."""
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path / "cache"))
    old_metrics = metrics.set_default_metrics(metrics.MetricsRegistry())
    old_reg = set_default_registry(None)
    yield
    faults.clear()
    set_default_registry(old_reg)
    metrics.set_default_metrics(old_metrics)


def _serve() -> Engine:
    compiler.clear_memo()   # memo-served kernels would bypass the fault seams
    set_default_registry(PlanRegistry())
    cfg = dataclasses.replace(load_arch("qwen3-0.6b", smoke=True),
                              attention_impl="pallas")
    eng = Engine(cfg, model_mod.init_params(cfg, jax.random.PRNGKey(0)),
                 ServeConfig(batch=2, max_len=16))
    eng.generate(jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                    cfg.vocab_size), 3)
    return eng


def test_plan_faults_clean_on_a_healthy_run(serving_env):
    assert _load_smoke().plan_faults(_serve(), expect_tier="carryloop") == []


@pytest.mark.parametrize("site,action,kwargs,symptom", [
    pytest.param("registry.exec", "error", {"times": 1}, "fallback",
                 id="kernel-exec-error"),
    pytest.param("emission.exec", "nan", {}, "quarantined",
                 id="nan-kernel"),
    pytest.param("engine.decode", "error", {"after": 1, "times": 1},
                 "degraded", id="decode-step-error"),
    pytest.param("compile.measure", "timeout", {"times": 1},
                 "compile.measure_failed", id="autotune-candidate-timeout"),
])
def test_plan_faults_flags_a_forced_kernel_failure(serving_env, site, action,
                                                   kwargs, symptom):
    smoke = _load_smoke()
    with faults.inject(faults.FaultRule(site, action, **kwargs)):
        eng = _serve()
    found = smoke.plan_faults(eng, expect_tier="carryloop")
    assert any(symptom in f for f in found), found
