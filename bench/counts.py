"""Operations and bytes the serving work needs, from its shapes.

Counted from what the algorithm needs, whatever implements it: only real
requests' rows and each lane's live context, never padding rows, free
lanes, or cache positions beyond a lane's depth.  A later change that stops
doing needless work therefore raises a share honestly and cannot push it
over 100%.

Conventions: a multiply-add is 2 operations; attention of one query
against ``c`` keys takes ``4 * heads * head_dim * c`` operations (scores
and the weighted sum of values); ``itemsize`` is the served dtype's
(bfloat16, 2 bytes).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Sequence

ITEMSIZE = 2


@dataclass(frozen=True)
class Model:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @classmethod
    def from_config(cls, cfg: Dict) -> "Model":
        h, d = cfg["num_attention_heads"], cfg["hidden_size"]
        return cls(layers=cfg["num_hidden_layers"], d=d, heads=h,
                   kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg.get("head_dim") or d // h,
                   d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"])

    @property
    def layer_params(self) -> int:
        """Weights of the matrix products of all layers (norms left out)."""
        d, hd = self.d, self.head_dim
        attn = d * self.heads * hd * 2 + d * self.kv_heads * hd * 2
        return self.layers * (attn + 3 * d * self.d_ff)

    @property
    def head_params(self) -> int:
        return self.d * self.vocab

    @property
    def kv_bytes_per_token(self) -> int:
        """Key and value of one position over all layers."""
        return self.layers * 2 * self.kv_heads * self.head_dim * ITEMSIZE


# --------------------------------------------------------------- attention --
def decode_attention(m: Model, contexts: Sequence[int]) -> Dict[str, float]:
    """One decode step's attention over all layers: one query per live lane,
    lane ``i`` attending over ``contexts[i]`` keys (its depth, the new token
    included).  Bytes: the live keys and values read, queries read and
    outputs written."""
    c = float(sum(contexts))
    n = len(contexts)
    flops = 4.0 * m.heads * m.head_dim * c * m.layers
    byts = (2.0 * m.kv_heads * m.head_dim * c
            + 2.0 * m.heads * m.head_dim * n) * ITEMSIZE * m.layers
    return {"flops": flops, "bytes": byts, "calls": m.layers}


def prefill_attention(m: Model, lengths: Sequence[int]) -> Dict[str, float]:
    """One prefill group's causal attention over all layers: row ``i`` of
    ``lengths[i]`` tokens, query ``j`` attending over ``j + 1`` keys.
    Bytes: queries, keys and values read once, outputs written once."""
    pairs = float(sum(s * (s + 1) // 2 for s in lengths))
    toks = float(sum(lengths))
    flops = 4.0 * m.heads * m.head_dim * pairs * m.layers
    byts = (2.0 * m.heads + 2.0 * m.kv_heads) * m.head_dim * toks \
        * ITEMSIZE * m.layers
    return {"flops": flops, "bytes": byts, "calls": m.layers}


def roofline_s(work: Dict[str, float], peak_flops: float,
               peak_bytes_s: float) -> float:
    """Least time the chip could take for one kernel call's work."""
    return max(work["flops"] / peak_flops, work["bytes"] / peak_bytes_s)


def attention_roofline_s(m: Model, work: Dict[str, float],
                         peak_flops: float, peak_bytes_s: float) -> float:
    """Roofline time of one step's attention: the step's work splits evenly
    over its per-layer kernel calls, each bounded on its own."""
    per = {k: work[k] / work["calls"] for k in ("flops", "bytes")}
    return work["calls"] * roofline_s(per, peak_flops, peak_bytes_s)


# -------------------------------------------------------------- whole step --
def decode_step(m: Model, contexts: Sequence[int]) -> Dict[str, float]:
    """A decode step's model operations and bytes: every live lane runs all
    layers and the head for one token; the weights and the head's table are
    read once, each lane's live keys and values read and its new ones
    written."""
    n = len(contexts)
    att = decode_attention(m, contexts)
    flops = 2.0 * n * (m.layer_params + m.head_params) + att["flops"]
    byts = (m.layer_params + m.head_params) * ITEMSIZE + att["bytes"] \
        + n * m.kv_bytes_per_token
    return {"flops": flops, "bytes": byts}


def prefill_step(m: Model, lengths: Sequence[int]) -> Dict[str, float]:
    """A prefill group's model operations and bytes over its real rows: all
    layers for every prompt token, the head for each row's last position
    only (the one whose logits are sampled), weights read once and every
    prompt token's keys and values written."""
    toks = float(sum(lengths))
    att = prefill_attention(m, lengths)
    flops = 2.0 * toks * m.layer_params \
        + 2.0 * len(lengths) * m.head_params + att["flops"]
    byts = (m.layer_params + m.head_params) * ITEMSIZE \
        + toks * m.kv_bytes_per_token
    return {"flops": flops, "bytes": byts}
