"""Reusable differential-testing harness for the kernel library.

One registry of *cases* — every executable ``autopump.BUILDERS`` entry with
small shapes and deterministic integer-valued float32 data — and one
``run_case`` that compiles a case through a chosen backend and asserts it
against the numpy reference executor (:mod:`repro.core.executor`), replacing
the per-kernel copy-pasted differential tests that used to live in
``tests/test_compiler.py``.

Exactness contract: kernels built from exactly-representable ops on
integer-valued data (add/mul/min/max — vecadd, matmul, stencil,
floyd-warshall, grouped gemm dense *and* ragged) are asserted **bit-exact**
across every backend.  Flash attention, the SSD kernels (scan, the
final-state variant, the single-token decode step) and decode attention
contain ``exp``, whose numpy and XLA CPU implementations differ by 1 ULP on
some inputs, so no backend pair can agree bitwise; those cases assert to a
1-ULP-amplified tolerance (``rtol=atol=5e-6``) instead.  That includes the
flash running-max output ``m``: it is a max over dot products, and XLA's
CPU dot may round a score 1 ULP away from numpy's.

The sweep axes (``BACKENDS × FACTORS × MODES``) intentionally mirror the
acceptance contract: every backend must hold for M ∈ {1, 2, 4} in both
temporal modes on at least two shapes per kernel.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from repro import compiler
from repro.core import executor
from repro.core.autopump import BUILDERS

BACKENDS = ("reference", "jax", "pallas")
FACTORS = (1, 2, 4)
MODES = ("T", "R")


@dataclasses.dataclass(frozen=True)
class Case:
    """One differential case: a builder invocation + data + contract."""

    kernel: str                       # BUILDERS key
    args: Tuple                       # builder positional args
    kwargs: Dict                      # builder keyword args
    input_shapes: Dict[str, Tuple]    # memory name -> shape
    outputs: Tuple[str, ...]          # memory names to compare
    exact: bool = True                # bit-exact vs executor (see module doc)
    gold: Optional[Callable] = None   # inputs -> {output name: array}
    transform: Optional[Callable] = None  # post-process generated inputs
    seed: int = 0

    def inputs(self) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(self.seed)
        data = {name: rng.integers(-3, 4, shape).astype(np.float32)
                for name, shape in self.input_shapes.items()}
        if self.transform is not None:
            data = self.transform(data)
        return data


def _ssd_transform(data):
    # dt > 0, a < 0: the decay recurrence's contract; keep values on a
    # coarse grid so products/sums stay exactly representable
    data["dt"] = np.abs(data["dt"]) * 0.25 + 0.25
    data["a"] = -(np.abs(data["a"]) * 0.25 + 0.25)
    return data


def _decode_transform(positions):
    """Pin the decode positions (int32 cache write offsets)."""
    def transform(data):
        data["pos"] = np.asarray(positions, np.int32)
        return data
    return transform


def _decode_gold(inputs):
    # the kernel's query and output hold the group of query heads that
    # share a KV head: (b, hkv, group, d)
    q, k, v, pos = inputs["q"], inputs["k"], inputs["v"], inputs["pos"]
    sc = np.einsum("bkgd,bktd->bkgt", q * np.float32(q.shape[-1] ** -0.5), k)
    mask = np.arange(k.shape[2])[None, None, None, :] \
        <= pos[:, None, None, None]
    sc = np.where(mask, sc, -1e30)
    m = sc.max(-1, keepdims=True)
    p = np.exp(sc - m)
    o = np.einsum("bkgt,bktd->bkgd", p / p.sum(-1, keepdims=True), v)
    return {"o": o.astype(np.float32)}


def decode_case(b: int, h: int, hkv: int, t: int, d: int,
                bkv: Optional[int], positions: Sequence[int],
                seed: int = 0) -> Case:
    """A decode-attention case: ``h`` query heads over ``hkv`` KV heads of
    ``d``, a cache of ``t`` slots in tiles of ``bkv`` (None: the graph's own
    tile), one write position per batch row."""
    return Case(
        "decode_attention", (b, h, t, d),
        dict(bkv=bkv, hkv=hkv, vector_width=4),
        {"q": (b, hkv, h // hkv, d), "k": (b, hkv, t, d),
         "v": (b, hkv, t, d), "pos": (b,)},
        ("o",), exact=False, transform=_decode_transform(positions),
        gold=_decode_gold, seed=seed)


# decode attention across GQA groups and KV tilings (one tile, the graph's
# own tile, several tiles), each case with a mid-cache and a cache-full row
DECODE_CASES = {
    f"g{group}-t{t}-bkv{bkv or 'auto'}": decode_case(
        2, 2 * group, 2, t, 8, bkv, [t // 2 + 1, t - 1], seed=group)
    for group in (1, 2, 4)
    for t, bkv in ((16, 16), (32, None), (32, 8))
}


def _ssd_decode_gold(inputs):
    st, x, dt, a = (inputs[k] for k in ("state", "x", "dt", "a"))
    hpg = x.shape[1] // inputs["bmat"].shape[1]
    Bh = np.repeat(inputs["bmat"], hpg, axis=1)
    Ch = np.repeat(inputs["cmat"], hpg, axis=1)
    st2 = st * np.exp(a[None] * dt)[..., None, None] \
        + (Bh * dt[..., None])[..., :, None] * x[..., None, :]
    y = np.einsum("bhn,bhnp->bhp", Ch, st2)
    return {"y": y.astype(np.float32), "state_out": st2.astype(np.float32)}


def _flash_gold(inputs, causal=False, scale=None):
    q, k, v = inputs["q"], inputs["k"], inputs["v"]
    group = q.shape[1] // k.shape[1]
    k = np.repeat(k, group, axis=1)
    v = np.repeat(v, group, axis=1)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    logits = np.einsum("bhsd,bhtd->bhst", q, k) * np.float32(scale)
    if causal:
        s, t = q.shape[2], k.shape[2]
        logits = np.where(np.tril(np.ones((s, t), bool)), logits, -1e30)
    m = logits.max(-1, keepdims=True)
    p = np.exp(logits - m)
    o = np.einsum("bhst,bhtd->bhsd", p / p.sum(-1, keepdims=True), v)
    return {"o": o.astype(np.float32)}


def _grouped_gold_dense(inputs):
    return {"o": np.einsum("ecd,edf->ecf", inputs["x"], inputs["w"])}


def _grouped_gold_ragged(sizes):
    def gold(inputs):
        x, w = inputs["x"], inputs["w"]
        offs = np.cumsum([0] + list(sizes))
        return {"o": np.concatenate(
            [x[offs[i]:offs[i + 1]] @ w[i] for i in range(len(sizes))])}
    return gold


def cases(shape_index: int = 0) -> Dict[str, Case]:
    """The registry, at one of two shape points per kernel (0 = tiny tier-1
    shapes, 1 = a second, structurally different shape for each kernel)."""
    if shape_index == 0:
        return {
            "vecadd": Case("vecadd", (64,), dict(vector_width=8),
                           {"x": (64,), "y": (64,)}, ("z",)),
            "matmul": Case("matmul", (32, 32, 32),
                           dict(bm=16, bn=16, bk=16, vector_width=8),
                           {"a": (32, 32), "b": (32, 32)}, ("c",)),
            "stencil": Case("stencil", (10, 8, 8), dict(),
                            {"x": (10, 8, 8)}, ("y",)),
            "floyd_warshall": Case("floyd_warshall", (16,), dict(),
                                   {"dist": (16, 16)}, ("out",)),
            "flash_attention": Case(
                "flash_attention", (1, 2, 32, 32, 8),
                dict(bq=16, bkv=8, causal=True, vector_width=8),
                {"q": (1, 2, 32, 8), "k": (1, 2, 32, 8), "v": (1, 2, 32, 8)},
                ("o", "m", "l"), exact=False,
                gold=lambda i: _flash_gold(i, causal=True)),
            "ssd_scan": Case(
                "ssd_scan", (1, 32, 2, 4, 4), dict(chunk=8, vector_width=8),
                {"x": (1, 32, 2, 4), "dt": (1, 32, 2), "a": (2,),
                 "bmat": (1, 32, 2, 4), "cmat": (1, 32, 2, 4)},
                ("y",), exact=False, transform=_ssd_transform),
            "grouped_gemm": Case(
                "grouped_gemm", (2, 32, 16, 8),
                dict(bc=8, bf=8, bd=8, vector_width=8),
                {"x": (2, 32, 16), "w": (2, 16, 8)}, ("o",),
                gold=_grouped_gold_dense),
            "grouped_gemm_ragged": Case(
                "grouped_gemm", (2, 32, 16, 8),
                dict(bc=8, bf=8, bd=8, group_sizes=(16, 24),
                     vector_width=8),
                {"x": (40, 16), "w": (2, 16, 8)}, ("o",),
                gold=_grouped_gold_ragged((16, 24))),
            # GQA fold; mid-cache and cache-full rows
            "decode_attention": decode_case(2, 4, 2, 32, 8, 8, [17, 31]),
            "ssd_scan_final": Case(
                "ssd_scan", (1, 32, 2, 4, 4),
                dict(chunk=8, vector_width=8, final_state=True),
                {"x": (1, 32, 2, 4), "dt": (1, 32, 2), "a": (2,),
                 "bmat": (1, 32, 2, 4), "cmat": (1, 32, 2, 4)},
                ("y", "state"), exact=False, transform=_ssd_transform),
            "ssd_decode": Case(
                "ssd_decode", (2, 4, 8, 4),
                dict(n_groups=2, vector_width=4),         # grouped B/C
                {"state": (2, 4, 4, 8), "x": (2, 4, 8), "dt": (2, 4),
                 "a": (4,), "bmat": (2, 2, 4), "cmat": (2, 2, 4)},
                ("y", "state_out"), exact=False, transform=_ssd_transform,
                gold=_ssd_decode_gold),
        }
    return {
        "vecadd": Case("vecadd", (128,), dict(vector_width=4),
                       {"x": (128,), "y": (128,)}, ("z",), seed=1),
        "matmul": Case("matmul", (32, 16, 64),
                       dict(bm=8, bn=8, bk=16, vector_width=8),
                       {"a": (32, 64), "b": (64, 16)}, ("c",), seed=1),
        "stencil": Case("stencil", (6, 4, 8), dict(),
                        {"x": (6, 4, 8)}, ("y",), seed=1),
        "floyd_warshall": Case("floyd_warshall", (8,), dict(),
                               {"dist": (8, 8)}, ("out",), seed=1),
        "flash_attention": Case(
            "flash_attention", (2, 4, 16, 32, 4),
            dict(bq=8, bkv=8, hkv=2, vector_width=8),    # GQA fold
            {"q": (2, 4, 16, 4), "k": (2, 2, 32, 4), "v": (2, 2, 32, 4)},
            ("o", "m", "l"), exact=False,
            gold=lambda i: _flash_gold(i), seed=1),
        "ssd_scan": Case(
            "ssd_scan", (2, 16, 4, 8, 2),
            dict(chunk=4, n_groups=2, vector_width=8),   # grouped B/C
            {"x": (2, 16, 4, 8), "dt": (2, 16, 4), "a": (4,),
             "bmat": (2, 16, 2, 2), "cmat": (2, 16, 2, 2)},
            ("y",), exact=False, transform=_ssd_transform, seed=1),
        "grouped_gemm": Case(
            "grouped_gemm", (3, 16, 32, 16),
            dict(bc=16, bf=8, bd=8, vector_width=8),
            {"x": (3, 16, 32), "w": (3, 32, 16)}, ("o",),
            gold=_grouped_gold_dense, seed=1),
        "grouped_gemm_ragged": Case(
            "grouped_gemm", (3, 16, 8, 8),
            dict(bc=8, bf=8, bd=8, group_sizes=(8, 24, 8),
                 vector_width=8),
            {"x": (40, 8), "w": (3, 8, 8)}, ("o",),
            gold=_grouped_gold_ragged((8, 24, 8)), seed=1),
        "decode_attention": decode_case(1, 4, 2, 16, 4, 4, [0],  # fresh
                                        seed=1),
        "ssd_scan_final": Case(
            "ssd_scan", (2, 16, 4, 8, 2),
            dict(chunk=4, n_groups=2, vector_width=8, final_state=True),
            {"x": (2, 16, 4, 8), "dt": (2, 16, 4), "a": (4,),
             "bmat": (2, 16, 2, 2), "cmat": (2, 16, 2, 2)},
            ("y", "state"), exact=False, transform=_ssd_transform, seed=1),
        "ssd_decode": Case(
            "ssd_decode", (1, 4, 8, 4),
            dict(n_groups=4, vector_width=4),     # hpg=1: linear head sym
            {"state": (1, 4, 4, 8), "x": (1, 4, 8), "dt": (1, 4),
             "a": (4,), "bmat": (1, 4, 4), "cmat": (1, 4, 4)},
            ("y", "state_out"), exact=False, transform=_ssd_transform,
            gold=_ssd_decode_gold, seed=1),
    }


def run_case(case: Case, factor: int, mode: str, backend: str,
             cache=False, pallas_mode: str = "auto"):
    """Compile one case and assert it against the reference executor (and
    the independent numpy gold, when the case carries one); returns the
    compiled kernel."""
    g, _est = BUILDERS[case.kernel](*case.args, **case.kwargs)
    kern = compiler.compile(g, factor=factor, mode=mode, backend=backend,
                            pallas_mode=pallas_mode, cache=cache,
                            memoize=False)
    inputs = case.inputs()
    out = kern(inputs)
    gold = executor.run(kern.graph, dict(inputs))
    for name in case.outputs:
        a, b = np.asarray(out[name]), gold[name]
        if case.exact:
            np.testing.assert_array_equal(
                a, b, err_msg=f"{case.kernel}:{name} vs executor "
                              f"(M={factor} {mode} {backend})")
        else:
            np.testing.assert_allclose(
                a, b, rtol=5e-6, atol=5e-6,
                err_msg=f"{case.kernel}:{name} vs executor "
                        f"(M={factor} {mode} {backend})")
    if case.gold is not None:
        want = case.gold(inputs)
        for name, value in want.items():
            np.testing.assert_allclose(
                np.asarray(out[name]), value, rtol=1e-5, atol=1e-5,
                err_msg=f"{case.kernel}:{name} vs semantics "
                        f"(M={factor} {mode} {backend})")
    return kern


def sweep(kernels: Optional[Sequence[str]] = None,
          backends: Sequence[str] = BACKENDS,
          factors: Sequence[int] = FACTORS,
          modes: Sequence[str] = MODES,
          shape_indices: Sequence[int] = (0, 1)) -> int:
    """Run the full cross product (CLI / `make test-diff` entry point);
    returns the number of executed combinations."""
    ran = 0
    for si in shape_indices:
        registry = cases(si)
        for name, case in registry.items():
            if kernels is not None and name not in kernels:
                continue
            for backend in backends:
                for factor in factors:
                    for mode in modes:
                        run_case(case, factor, mode, backend)
                        ran += 1
    return ran


if __name__ == "__main__":
    print(f"differential sweep: {sweep()} combinations ok")
