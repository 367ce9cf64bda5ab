"""``correct`` comes out false when the timed path is broken underneath:
the harness runs end to end at the smoke size on the CPU, past its look for
a chip, once sound and once with each fault a serving cell can have (a
token altered where it is produced; a decode step that returns its cache
unchanged).  And the control, the reference in float8, fails the limit."""
from __future__ import annotations

import time

import numpy as np
import pytest

from bench import correctness, run as bench_run, weights
from bench_helpers import ROOT, family, smoke_cell, smoke_config

# the smoke cell's limit: the program reads 0 at this size, every fault and
# the control read far above it
LIMIT = 0.02


def _run(seed=11):
    return bench_run.run_cell(ROOT, "smoke", seed, 2.0, False,
                              require_chip=False,
                              cell=smoke_cell("closed", gap_limit=LIMIT),
                              process_start=time.perf_counter(),
                              log=lambda _m: None)


def test_sound_run_is_correct(serving_env):
    res = _run()
    assert res["correct"] is True
    assert res["checks"]["logit_gap"]["value"] <= LIMIT


def test_altered_token_is_not_correct(serving_env, monkeypatch):
    from repro.serve import scheduler
    sample = scheduler.Scheduler._sample_row

    def shifted(self, logits_row, key):
        return (sample(self, logits_row, key) + 1) % len(logits_row)

    monkeypatch.setattr(scheduler.Scheduler, "_sample_row", shifted)
    res = _run()
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > LIMIT


def test_decode_step_returning_its_cache_unchanged_is_not_correct(
        serving_env, monkeypatch):
    from repro.models import model as model_mod
    step = model_mod.decode_step

    def stale(cfg, params, batch, cache):
        logits, new = step(cfg, params, batch, cache)
        return (logits, cache) if batch["tokens"].shape[1] == 1 \
            else (logits, new)

    monkeypatch.setattr(model_mod, "decode_step", stale)
    res = _run()
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > LIMIT


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_fails_the_limit(seed):
    cj = smoke_config("qwen3-0.6b")
    dense = family("dense")
    params = weights.make(dense.weight_shapes(cj), seed)
    rng = np.random.default_rng(seed)
    picked = []
    for rid in range(4):
        prompt = rng.integers(0, cj["vocab_size"], 16).astype(np.int32)
        served = rng.integers(0, cj["vocab_size"], 24).astype(np.int32)
        picked.append((rid, prompt, served))
    gaps = correctness.control_gaps(dense.reference, params, cj, picked,
                                    64)
    chk = correctness.checks(gaps, LIMIT)
    assert not correctness.passed(chk)
