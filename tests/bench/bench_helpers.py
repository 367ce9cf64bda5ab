"""Small configurations of the benchmark's two model families, for tests."""
from __future__ import annotations

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

# the smoke sizes of the program's own qwen3/granite presets
SMOKE = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, intermediate_size=128, vocab_size=256)


def smoke_config(name: str) -> dict:
    """A benchmark configuration file's keys at the smoke size."""
    with open(ROOT / "bench" / "configs" / f"{name}.json") as f:
        cj = json.load(f)
    cj.update(SMOKE, name=f"{name}-smoke")
    if cj.get("head_dim"):
        cj["head_dim"] = 32
    return cj




def smoke_cell(loop: str = "closed", gap_limit: float = 0.02) -> dict:
    """A cell at the smoke size: the qwen3 family, four lanes, an engine
    batch of two, short prompts and outputs; it reports the metrics of the
    real cell of the same loop."""
    like = {"closed": "qwen3-0.6b.decode-heavy",
            "open": "qwen3-0.6b.prompt-heavy"}[loop]
    if loop == "closed":
        mix = dict(loop="closed", prompt_lens=[8, 16], prompt_weights=[1, 1],
                   output_range=[6, 12], block=4, preroll_s=0.3,
                   stagger_s=0.01)
        load = {"clients": 4}
    else:
        mix = dict(loop="open", prompt_lens=[8, 16], prompt_weights=[1, 1],
                   output_range=[4, 8], block=4, preroll_s=0.3)
        load = {"rate_rps": 8.0}
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    return {
        "name": "smoke", "chips": 1, "config": smoke_config("qwen3-0.6b"),
        "traffic": mix,
        "workload": {"config": "smoke", "traffic": loop, "load": load,
                     "serve": {"max_slots": 4, "engine_batch": 2,
                               "max_len": 64},
                     "correct": {"sample_requests": 4, "sample_tokens": 40,
                                 "gap_limit": gap_limit}},
        "end_to_end": _metrics_of(bench["end_to_end"], like),
        "per_layer": _metrics_of(bench["per_layer"], like),
        "metrics_dir": ROOT / "bench" / "metrics",
    }


def _metrics_of(metrics, cell):
    return [m for m in metrics if "workloads" not in m
            or cell in m["workloads"]]
