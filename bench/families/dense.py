"""The dense grouped-query-attention decoder (Qwen3, Granite): token
embedding, per layer RMSNorm, GQA with a per-head key and value cache,
SwiGLU, final RMSNorm and a tied head.  Every seam is the benchmark's
existing function for this family (``bench.counts``,
``bench.reference.dense``)."""
from __future__ import annotations

from typing import Dict, List

from bench import counts
from bench.counts import (decode_attention, decode_step,  # noqa: F401
                          prefill_attention, prefill_step)
from bench.reference import dense as reference  # noqa: F401

# the smoke sizes of the program's own qwen3/granite presets
SMOKE = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, intermediate_size=128, vocab_size=256)


def program_config(cj: Dict):
    """The program's configuration object for a configuration file."""
    from repro.configs.base import ModelConfig
    h, d = cj["num_attention_heads"], cj["hidden_size"]
    return ModelConfig(
        name=cj["name"], family="dense", n_layers=cj["num_hidden_layers"],
        d_model=d, n_heads=h, n_kv_heads=cj["num_key_value_heads"],
        d_ff=cj["intermediate_size"], vocab_size=cj["vocab_size"],
        head_dim=cj.get("head_dim") or d // h, qk_norm=bool(cj["qk_norm"]),
        tie_embeddings=bool(cj["tie_word_embeddings"]),
        rope_theta=float(cj["rope_theta"]),
        norm_eps=float(cj["rms_norm_eps"]), attention_impl="pallas",
        kernel_plan="measure", dtype="bfloat16")


def weight_shapes(cj: Dict) -> Dict:
    """Per-layer weights stacked over layers under ``blocks``, 2-D weights
    as ``(d_in, d_out)`` under ``w``, the tied embedding as
    ``(vocab, d_model)``: the program's layout."""
    k = reference.dims(cj)
    d, h, hkv, hd = k["d"], k["h"], k["hkv"], k["hd"]
    n, ff, v = (cj["num_hidden_layers"], cj["intermediate_size"],
                cj["vocab_size"])
    attn = {"wq": {"w": (n, d, h * hd)}, "wk": {"w": (n, d, hkv * hd)},
            "wv": {"w": (n, d, hkv * hd)}, "wo": {"w": (n, h * hd, d)}}
    if k["qk_norm"]:
        attn["q_norm"] = {"scale": (n, hd)}
        attn["k_norm"] = {"scale": (n, hd)}
    return {
        "embed": {"embedding": (v, d)},
        "final_norm": {"scale": (d,)},
        "blocks": {
            "norm1": {"scale": (n, d)},
            "attn": attn,
            "norm2": {"scale": (n, d)},
            "mlp": {"gate": {"w": (n, d, ff)}, "up": {"w": (n, d, ff)},
                    "down": {"w": (n, ff, d)}},
        },
    }


def work_model(cj: Dict) -> counts.Model:
    return counts.Model.from_config(cj)


def warm_plans(cfg, serve: Dict) -> List:
    """The decode step runs over all slots: its plan bucket is keyed on the
    slot count, which the engine's warmup (at its batch) does not cover."""
    if serve["max_slots"] == serve["engine_batch"]:
        return []
    return [("decode_attention", dict(
        b=serve["max_slots"], h=cfg.n_heads, hkv=cfg.n_kv_heads,
        t=serve["max_len"], d=cfg.head_dim_, dtype="bfloat16"))]


def smoke(cj: Dict) -> Dict:
    """``cj`` at the smoke size, under the name ``<name>-smoke``."""
    out = dict(cj, **SMOKE, name=f"{cj['name']}-smoke")
    if out.get("head_dim"):
        out["head_dim"] = 32
    return out
