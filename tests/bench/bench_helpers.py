"""Small configurations of the benchmark's model families, for tests."""
from __future__ import annotations

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def family(name: str = "dense"):
    """The module of one model family, loaded as a cell loads it."""
    from bench import run as bench_run
    return bench_run.load_family(ROOT / "bench" / "families", name)


def smoke_config(name: str) -> dict:
    """A benchmark configuration file's keys at its family's smoke size."""
    with open(ROOT / "bench" / "configs" / f"{name}.json") as f:
        cj = json.load(f)
    return family(cj["family"]).smoke(cj)


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
        "family": family(),
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
