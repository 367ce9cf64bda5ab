"""A model family is found by the ``family`` key of its configuration file
and reached through ``bench/families/<family>.py`` alone: a family added as
new files drives a whole smoke cell through every seam (on the CPU, past
the look for a chip), an unknown family fails with the file it looked for,
and the dense family draws the same weights as before it had a module."""
from __future__ import annotations

import hashlib
import json
import re
import shutil
import time

import jax
import numpy as np
import pytest

from bench import run as bench_run, weights
from bench_helpers import ROOT, family, smoke_config

# a family that hands every seam to the dense family and records each call
PROBE_FAMILY = '''
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "probe_dense", Path(__file__).with_name("dense.py"))
_dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_dense)
CALLS = []


def _recorded(name, fn):
    def call(*args, **kwargs):
        CALLS.append(name)
        return fn(*args, **kwargs)
    return call


for _name in ("program_config", "weight_shapes", "work_model",
              "prefill_step", "prefill_attention", "decode_step",
              "decode_attention", "warm_plans", "smoke"):
    globals()[_name] = _recorded(_name, getattr(_dense, _name))


class reference:
    gaps = staticmethod(_recorded("reference.gaps", _dense.reference.gaps))
'''

SEAMS = {"program_config", "weight_shapes", "work_model", "prefill_step",
         "prefill_attention", "decode_step", "decode_attention",
         "warm_plans", "reference.gaps"}

MIX = dict(loop="closed", prompt_lens=[8, 16], prompt_weights=[1, 1],
           output_range=[6, 12], block=4, preroll_s=0.3, stagger_s=0.01)
WORKLOAD = {"config": "probe-model", "traffic": "probe-closed",
            "load": {"clients": 4},
            "serve": {"max_slots": 4, "engine_batch": 2, "max_len": 64},
            "correct": {"sample_requests": 4, "sample_tokens": 40,
                        "gap_limit": 0.02}}


def _root_with(tmp_path, family_name, family_source=None):
    """A copy of the benchmark with, as new files only, a family (when
    ``family_source`` is given), a smoke-size configuration that names
    ``family_name``, a traffic mix and one cell."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    here = root / "bench"
    if family_source is not None:
        (here / "families" / f"{family_name}.py").write_text(family_source)
    cj = dict(smoke_config("qwen3-0.6b"), name="probe-model",
              family=family_name)
    (here / "configs" / "probe-model.json").write_text(json.dumps(cj))
    (here / "traffic" / "probe-closed.json").write_text(json.dumps(MIX))
    (here / "workloads" / "probe-model.probe-closed.json").write_text(
        json.dumps(WORKLOAD))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "probe-model", "source": "smoke size of qwen3-0.6b",
        "file": "bench/configs/probe-model.json", "reduced": [],
        "why": "a family added as new files"})
    bench["workloads"].append({
        "name": "probe-model.probe-closed", "config": "probe-model",
        "traffic": "probe-closed", "chips": 1, "why": "smoke"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_a_family_added_as_new_files_drives_every_seam(serving_env,
                                                        tmp_path):
    root = _root_with(tmp_path, "dense-probe", PROBE_FAMILY)
    probe = bench_run.load_family(root / "bench" / "families", "dense-probe")
    cj = json.loads((root / "bench" / "configs" / "probe-model.json")
                    .read_text())
    assert probe.smoke(dict(cj, name="probe"))["name"] == "probe-smoke"
    assert probe.CALLS == ["smoke"]
    cell = bench_run.load_cell(root, "probe-model.probe-closed")
    fam = cell["family"]
    assert fam.__file__ == str(root / "bench" / "families" / "dense-probe.py")
    res = bench_run.run_cell(ROOT, cell["name"], 2**32 + 21, 2.0, False,
                             require_chip=False, cell=cell,
                             process_start=time.perf_counter(),
                             log=lambda _m: None)
    assert set(fam.CALLS) == SEAMS
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["tokens_compared"]["value"] >= 1


def test_an_unknown_family_names_the_file_it_looked_for(tmp_path):
    root = _root_with(tmp_path, "no-such-family")
    want = root / "bench" / "families" / "no-such-family.py"
    with pytest.raises(FileNotFoundError, match=re.escape(str(want))):
        bench_run.load_cell(root, "probe-model.probe-closed")


# sha256 (first 16 hex digits) of each leaf's bfloat16 bytes, drawn from
# seed 1234 at the smoke size by ``bench.weights`` before the families
# existed
DENSE_SMOKE_DRAW = {
    "qwen3-0.6b": {
        "['blocks']['attn']['k_norm']['scale']": "5bf3d5fa070b88f2",
        "['blocks']['attn']['q_norm']['scale']": "4fb2a31e97ccb459",
        "['blocks']['attn']['wk']['w']": "7151fc5f77006397",
        "['blocks']['attn']['wo']['w']": "2ecc553af28c7ba0",
        "['blocks']['attn']['wq']['w']": "9c532713e3c15a39",
        "['blocks']['attn']['wv']['w']": "3cb2f79061d1ec1d",
        "['blocks']['mlp']['down']['w']": "e4061a7e10629e20",
        "['blocks']['mlp']['gate']['w']": "8b28a5acab150454",
        "['blocks']['mlp']['up']['w']": "f775db664284f2c6",
        "['blocks']['norm1']['scale']": "866209b3bdcac839",
        "['blocks']['norm2']['scale']": "2e3fa8bd11c04692",
        "['embed']['embedding']": "e8187c5fbf806140",
        "['final_norm']['scale']": "afdb28b6478f0c77",
    },
    "granite-3-2b": {
        "['blocks']['attn']['wk']['w']": "c5c54bbb6fcc64a8",
        "['blocks']['attn']['wo']['w']": "5b65b73e05c9f0d5",
        "['blocks']['attn']['wq']['w']": "7151fc5f77006397",
        "['blocks']['attn']['wv']['w']": "15cfccb87c1f29e8",
        "['blocks']['mlp']['down']['w']": "58a1a33ea39632fe",
        "['blocks']['mlp']['gate']['w']": "16e966b8c09ac197",
        "['blocks']['mlp']['up']['w']": "785763dd3a7c128d",
        "['blocks']['norm1']['scale']": "4e8ee47e7f9d8aa5",
        "['blocks']['norm2']['scale']": "ada0a20e399d99d4",
        "['embed']['embedding']": "0dceab744c0c4d1a",
        "['final_norm']['scale']": "ce71491f710c33c5",
    },
}


@pytest.mark.parametrize("name", sorted(DENSE_SMOKE_DRAW))
def test_dense_weights_match_the_draw_before_families(name):
    cj = smoke_config(name)
    params = weights.make(family("dense").weight_shapes(cj), 1234)
    got = {jax.tree_util.keystr(path): hashlib.sha256(
        np.asarray(leaf).view(np.uint16).tobytes()).hexdigest()[:16]
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == DENSE_SMOKE_DRAW[name]
