"""Operation and byte counts count live work only; the peak table refuses
an unknown chip."""
from __future__ import annotations

import json

import pytest

from bench import counts, peaks
from bench_helpers import ROOT, family

DENSE = family("dense")


def _model(name):
    with open(ROOT / "bench" / "configs" / f"{name}.json") as f:
        cj = json.load(f)
    return cj, DENSE.work_model(cj)


@pytest.mark.parametrize("name,params_b,kv_kib", [
    ("qwen3-0.6b", 0.596, 112), ("granite-3-2b", 2.533, 80)])
def test_model_sizes(name, params_b, kv_kib):
    cj, m = _model(name)
    total = m.layer_params + m.head_params
    assert abs(total / 1e9 - params_b) < 0.001
    assert m.kv_bytes_per_token == kv_kib * 1024
    # the program's own count agrees (norm scales aside)
    prog = DENSE.program_config(cj).param_count()
    assert abs(prog - total) / total < 1e-3


def test_decode_attention_counts_live_context_not_max_len():
    _, m = _model("qwen3-0.6b")
    a = counts.decode_attention(m, [10, 20])
    b = counts.decode_attention(m, [30])
    assert a["flops"] == b["flops"] == 4 * 16 * 128 * 30 * 28
    kv = 2 * 8 * 128 * 30 * 2 * 28
    assert a["bytes"] == kv + 2 * 16 * 128 * 2 * 2 * 28
    assert counts.decode_attention(m, [])["flops"] == 0


def test_prefill_attention_counts_causal_pairs():
    _, m = _model("qwen3-0.6b")
    w = counts.prefill_attention(m, [4, 4])
    assert w["flops"] == 4 * 16 * 128 * (2 * 10) * 28
    assert counts.prefill_attention(m, [])["flops"] == 0


def test_step_counts_head_once_per_sampled_token():
    _, m = _model("qwen3-0.6b")
    pre = counts.prefill_step(m, [256])
    head = 2 * m.head_params
    att = counts.prefill_attention(m, [256])["flops"]
    assert pre["flops"] == 2 * 256 * m.layer_params + head + att
    dec = counts.decode_step(m, [300, 400])
    assert dec["bytes"] > (m.layer_params + m.head_params) * 2


def test_roofline_takes_the_binding_bound():
    w = {"flops": 197e12, "bytes": 819e9 / 2}
    assert counts.roofline_s(w, 197e12, 819e9) == 1.0
    w = {"flops": 1.0, "bytes": 819e9}
    assert counts.roofline_s(w, 197e12, 819e9) == 1.0


def test_peaks_known_and_unknown():
    pk = peaks.peaks("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12 and pk["hbm_bytes_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v9 imaginary")
