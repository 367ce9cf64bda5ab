"""Shared set-up of the benchmark's tests: the repo root on ``sys.path`` (the
benchmark is the ``bench`` package beside ``src``) and a private serving
environment."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@pytest.fixture
def serving_env(tmp_path, monkeypatch):
    """Private plan store, fresh metrics and plan registry, no fault left
    over (the program keeps these per process)."""
    from repro.compiler.registry import set_default_registry
    from repro.obs import metrics
    from repro.testing import faults
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path / "cache"))
    old_metrics = metrics.set_default_metrics(metrics.MetricsRegistry())
    old_reg = set_default_registry(None)
    yield
    faults.clear()
    set_default_registry(old_reg)
    metrics.set_default_metrics(old_metrics)
