"""Decides ``correct``: the served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and always holding the one with
the most output tokens, is run once through the float32 reference of the
model's family (its ``reference`` module, ``bench/families``): each prompt
followed by its served tokens.  For every served token the reference gives
the gap by which that token's logit lies below the reference's best logit
at its position.  The program serves greedy tokens, so a sound run serves
near-best tokens and its widest gap is small; a wrong token, a stale cache,
or a lower precision shows as a wide one.  The widest gap over the sample
is held to the cell's ``gap_limit``.

The control (``control_gaps``) puts the reference computed in float8 in the
program's place: at each of the same positions the token the float8
reference ranks first is read in the float32 reference.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

ROWS = 4          # reference rows per block


def sample(done: Sequence[Tuple[int, np.ndarray, np.ndarray]], seed: int,
           max_requests: int, max_tokens: int):
    """Pick ``(rid, prompt, served)`` entries: the one with the most served
    tokens, then others in an order drawn from the seed, until either cap."""
    if not done:
        return []
    done = sorted(done, key=lambda d: d[0])
    longest = max(range(len(done)), key=lambda i: (len(done[i][2]),
                                                   -done[i][0]))
    rng = np.random.default_rng([int(seed) % 2**63, 11])
    order = [longest] + [int(i) for i in rng.permutation(len(done))
                         if i != longest]
    out, toks = [], 0
    for i in order:
        if len(out) >= max_requests or toks >= max_tokens:
            break
        out.append(done[i])
        toks += len(done[i][2])
    return out


def _rows(picked, max_len: int):
    """Input rows (prompt + served[:-1]), query rows (the served token each
    position predicts) and the compared positions, padded to ``max_len``
    and to a whole number of blocks."""
    n = -(-len(picked) // ROWS) * ROWS
    toks = np.zeros((n, max_len), np.int32)
    query = np.zeros((n, max_len, 1), np.int32)
    mask = np.zeros((n, max_len), bool)
    for i, (_rid, prompt, served) in enumerate(picked):
        p, s = len(prompt), len(served)
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        toks[i, :len(seq)] = seq
        query[i, p - 1:p - 1 + s, 0] = served
        mask[i, p - 1:p - 1 + s] = True
    return toks, query, mask


def served_gaps(reference, params, cfg: Dict, picked,
                max_len: int) -> np.ndarray:
    """Gap of every served token in ``picked`` (flattened)."""
    toks, query, mask = _rows(picked, max_len)
    out = []
    for b in range(0, len(toks), ROWS):
        g, _ = reference.gaps(params, cfg, toks[b:b + ROWS],
                              query[b:b + ROWS])
        out.append(np.asarray(g)[..., 0][mask[b:b + ROWS]])
    return np.concatenate(out) if out else np.zeros(0, np.float32)


def control_gaps(reference, params, cfg: Dict, picked,
                 max_len: int) -> np.ndarray:
    """Gap, in the float32 reference, of the token the float8 reference
    ranks first at each served position."""
    toks, query, mask = _rows(picked, max_len)
    out = []
    for b in range(0, len(toks), ROWS):
        t = toks[b:b + ROWS]
        _, top8 = reference.gaps(params, cfg, t, query[b:b + ROWS],
                                 quant="fp8")
        g, _ = reference.gaps(params, cfg, t, np.asarray(top8)[..., None])
        out.append(np.asarray(g)[..., 0][mask[b:b + ROWS]])
    return np.concatenate(out) if out else np.zeros(0, np.float32)


def checks(gaps: np.ndarray, limit, min_tokens: int = 1) -> Dict[str, Dict]:
    """The numbers compared, each beside its limit."""
    widest = float(gaps.max()) if gaps.size else None
    return {
        "logit_gap": {"value": widest, "limit": limit, "rule": "<="},
        "tokens_compared": {"value": int(gaps.size), "limit": min_tokens,
                            "rule": ">="},
    }


def passed(chk: Dict[str, Dict]) -> bool:
    for c in chk.values():
        v, lim = c["value"], c["limit"]
        if v is None or lim is None:
            return False
        if c["rule"] == "<=" and not v <= lim:
            return False
        if c["rule"] == ">=" and not v >= lim:
            return False
    return True


def describe(chk: Dict[str, Dict]) -> List[str]:
    return [f"{name}: {c['value']} (limit {c['rule']} {c['limit']})"
            for name, c in chk.items()]
