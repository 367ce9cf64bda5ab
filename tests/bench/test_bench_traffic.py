"""The traffic generator: a seed fixes the trace, every seed gets the same
sizes, and the open loop keeps its Poisson rate."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench.traffic import generator
from bench_helpers import ROOT


def _mix(name):
    with open(ROOT / "bench" / "traffic" / f"{name}.json") as f:
        return json.load(f)


def _open(seed, rate=5.0, end=60.0):
    return generator.make(_mix("prompt-heavy"), {"rate_rps": rate}, seed,
                          1000, end)


def _trace(gen):
    return [(s.rid, s.due, len(s.prompt), s.n_new, s.prompt.tobytes())
            for s in gen.due_until(float("inf"))]


def test_same_seed_same_trace():
    assert _trace(_open(2**33 + 17)) == _trace(_open(2**33 + 17))
    assert _trace(_open(1)) != _trace(_open(2))


def test_seeds_share_sizes_in_another_order():
    a, b = _trace(_open(3, end=200.0)), _trace(_open(4, end=200.0))
    n = min(len(a), len(b)) // 40 * 40
    assert n >= 400
    assert sorted(x[2] for x in a[:n]) == sorted(x[2] for x in b[:n])
    assert sorted(x[3] for x in a[:n]) == sorted(x[3] for x in b[:n])
    assert [x[2] for x in a[:n]] != [x[2] for x in b[:n]]


@pytest.mark.parametrize("rate", [0.5, 4.0, 25.0])
def test_poisson_rate_is_honoured(rate):
    mix = _mix("prompt-heavy")
    end = 400.0 / rate
    specs = _open(5, rate=rate, end=end).due_until(float("inf"))
    span = end + mix["preroll_s"]
    assert abs(len(specs) / span - rate) / rate < 0.02
    gaps = np.diff([s.due for s in specs])
    # exponential gaps: the coefficient of variation is about 1
    assert 0.85 < gaps.std() / gaps.mean() < 1.1


def test_prompt_mix_follows_the_weights():
    specs = _open(6, end=400.0).due_until(float("inf"))
    lens = [len(s.prompt) for s in specs[:400]]
    mix = _mix("prompt-heavy")
    w = np.asarray(mix["prompt_weights"], float) / sum(mix["prompt_weights"])
    got = [lens.count(p) / 400 for p in mix["prompt_lens"]]
    np.testing.assert_allclose(got, w)
    lo, hi = mix["output_range"]
    assert all(lo <= s.n_new <= hi for s in specs)


def test_closed_loop_staggers_and_refills_on_completion():
    mix = _mix("decode-heavy")
    gen = generator.make(mix, {"clients": 32}, 7, 1000, 30.0)
    first = gen.due_until(float("inf"))
    assert len(first) == 32 and gen.next_due() is None
    dues = sorted(s.due for s in first)
    assert dues[0] == -mix["preroll_s"]
    np.testing.assert_allclose(np.diff(dues), mix["stagger_s"])
    # first requests ask for spread-out shares of the longest output, so
    # the lanes do not finish together
    assert len({s.n_new for s in first}) == 32
    assert max(s.n_new for s in first) <= mix["output_range"][1]
    gen.complete(first[5], 1.25)
    nxt = gen.due_until(1.25)
    assert len(nxt) == 1 and nxt[0].client == first[5].client
    lo, hi = mix["output_range"]
    assert lo <= nxt[0].n_new <= hi and nxt[0].due == 1.25
