"""The one traffic generator: reads a mix's data file and a cell's load.

Times are seconds on the harness's clock, relative to the opening of the
measured window; a pre-roll before it (``preroll_s`` in the mix) brings the
system to a steady state first.  Every seed gets the same multiset of sizes
and gaps in another order, so seeds change which request comes when, not
how much work a run holds:

* sizes come in blocks of ``block`` requests; each block holds prompt
  lengths in exactly the proportions of ``prompt_weights`` and output
  lengths at the ``block`` midpoint quantiles of the uniform distribution
  over ``output_range``, each shuffled;
* an open loop (``"loop": "open"``) draws Poisson arrivals at the cell's
  ``rate_rps``: gaps are the ``block`` midpoint quantiles of the
  exponential distribution, scaled to a mean of exactly ``1 / rate_rps``
  and shuffled within each block;
* a closed loop (``"loop": "closed"``) runs the cell's ``clients``: client
  ``i`` sends its first request at ``-preroll_s + i * stagger_s`` and its
  next one when the previous one's last token arrives.  First requests ask
  for the output still left to a request met at a random moment of a
  steady state (midpoint quantiles of the residual-life distribution of
  ``output_range``, shuffled), so lanes finish at the steady state's rate
  from the start, and not together.

Prompt tokens are uniform over the vocabulary.  Prompt lengths come from a
small set because the program compiles one prefill per prompt length.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Spec:
    rid: int
    prompt: np.ndarray
    n_new: int
    due: float
    client: Optional[int] = None


def load(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _size_blocks(rng, mix: Dict):
    """Endless (prompt_len, n_new) pairs, one shuffled block at a time."""
    k = int(mix["block"])
    lens, w = mix["prompt_lens"], np.asarray(mix["prompt_weights"], float)
    counts = w * k / w.sum()
    if not np.allclose(counts, np.round(counts)):
        raise ValueError(f"block {k} does not split by prompt_weights {w}")
    plens = np.repeat(lens, np.round(counts).astype(int))
    lo, hi = mix["output_range"]
    outs = np.round(lo + (hi - lo) * (np.arange(k) + 0.5) / k).astype(int)
    while True:
        for p, n in zip(rng.permutation(plens), rng.permutation(outs)):
            yield int(p), int(n)


def residual_quantiles(lo: float, hi: float, k: int) -> np.ndarray:
    """Midpoint quantiles of the output left to a request met at a random
    moment, when outputs are uniform over ``[lo, hi]``: the density is
    ``P(L > x) / E[L]``."""
    x = np.linspace(0.0, hi, 4097)
    surv = np.clip((hi - x) / (hi - lo), 0.0, 1.0)
    cdf = np.concatenate([[0.0], np.cumsum((surv[1:] + surv[:-1]) / 2
                                           * np.diff(x))])
    cdf /= cdf[-1]
    return np.interp((np.arange(k) + 0.5) / k, cdf, x)


def _exp_gaps(rng, k: int, rate: float):
    """Endless Poisson gaps at ``rate``, one shuffled block at a time."""
    q = -np.log1p(-(np.arange(k) + 0.5) / k)
    q *= (1.0 / rate) / q.mean()
    while True:
        yield from rng.permutation(q)


class _Base:
    def __init__(self, mix: Dict, seed: int, vocab: int):
        self.rng = np.random.default_rng(seed)
        self.vocab = vocab
        self.sizes = _size_blocks(self.rng, mix)
        self.preroll = float(mix.get("preroll_s", 0.0))
        self.ready: List[Spec] = []
        self.rid = 0

    def _spec(self, due: float, plen: int, n_new: int,
              client: Optional[int] = None) -> Spec:
        prompt = self.rng.integers(0, self.vocab, plen, dtype=np.int32)
        s = Spec(self.rid, prompt, n_new, due, client)
        self.rid += 1
        return s

    def due_until(self, now: float) -> List[Spec]:
        """Requests due at or before ``now``, oldest first."""
        out = []
        while self.ready and self.ready[0].due <= now:
            out.append(self.ready.pop(0))
        return out

    def next_due(self) -> Optional[float]:
        return self.ready[0].due if self.ready else None

    def complete(self, spec: Spec, t: float) -> None:
        """The last token of ``spec`` arrived at ``t``."""


class OpenLoop(_Base):
    """Poisson arrivals at ``rate_rps`` from ``-preroll_s`` to ``end_s``."""

    def __init__(self, mix: Dict, load: Dict, seed: int, vocab: int,
                 end_s: float):
        super().__init__(mix, seed, vocab)
        gaps = _exp_gaps(self.rng, int(mix["block"]), float(load["rate_rps"]))
        t = -self.preroll
        while True:
            t += next(gaps)
            if t >= end_s:
                break
            plen, n_new = next(self.sizes)
            self.ready.append(self._spec(t, plen, n_new))


class ClosedLoop(_Base):
    """``clients`` clients, each with one request in flight at a time."""

    def __init__(self, mix: Dict, load: Dict, seed: int, vocab: int,
                 end_s: float):
        super().__init__(mix, seed, vocab)
        n = int(load["clients"])
        lo, hi = mix["output_range"]
        firsts = self.rng.permutation(np.maximum(
            1, np.ceil(residual_quantiles(lo, hi, n)).astype(int)))
        stagger = float(mix.get("stagger_s", 0.0))
        for i in range(n):
            plen, _ = next(self.sizes)
            self.ready.append(self._spec(-self.preroll + i * stagger, plen,
                                         int(firsts[i]), client=i))
        self.ready.sort(key=lambda s: s.due)

    def complete(self, spec: Spec, t: float) -> None:
        plen, n_new = next(self.sizes)
        self.ready.append(self._spec(t, plen, n_new, client=spec.client))
        self.ready.sort(key=lambda s: s.due)


def make(mix: Dict, load: Dict, seed: int, vocab: int, end_s: float):
    kinds = {"open": OpenLoop, "closed": ClosedLoop}
    return kinds[mix["loop"]](mix, load, seed, vocab, end_s)
