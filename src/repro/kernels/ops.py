"""Jit'd public wrappers around the kernel library.

Responsibilities: shape padding to block multiples, dtype policy, automatic
pump-factor planning (``pump='auto'`` asks the capacity model, ``'measure'``
times candidates), and the interpret/compile switch: ``interpret=None`` (the
default) runs the Pallas interpreter exactly when no TPU is attached, so a
chip only ever runs compiled kernels unless a caller asks for interpret
mode by name.

Flash attention, the SSD scan and grouped GEMM are **compiled, not
hand-scheduled**: their default path builds the kernel's executable IR graph
(:mod:`repro.core.autopump`) and routes it through
``repro.compiler.compile(backend='pallas')`` — the fused-region emission
derives the BlockSpecs, carry scratch and pump schedule that the hand-wired
Pallas kernels in this package previously encoded by hand.  The hand-wired
kernels remain as a differential reference behind ``impl='pallas'``; a
compiler-route failure raises rather than switching to them.

The decode hot path is compiler-only: :func:`decode_attention` (S=1 against
a preallocated KV cache, position-offset mask from an int32 ``pos`` input),
:func:`ssd_decode` (single-token SSD state update, multi-output tile
emission) and ``ssd_scan(final_state=True)`` (the scan plus its final
inter-chunk state) have no hand-wired counterparts — serving reaches them
through the plan registry's pos-bucketed wrappers.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.ir import PumpSpec
from repro.core.pump_plan import VMEM_BYTES

from . import flash_attention as _fa
from . import grouped_gemm as _gg
from . import floyd_warshall as _fw
from . import matmul as _mm
from . import ssd_scan as _ssd
from . import stencil as _st
from . import vecadd as _va


def _as_spec(pump, kernel: Optional[str] = None, builder_args=(),
             builder_kwargs=None, **plan_kwargs) -> PumpSpec:
    if pump == "auto":
        # compiler-backed planning: the chosen factor is memoized in the
        # persistent compile cache, so repeated serve/benchmark processes
        # skip the capacity-model search entirely.
        from repro.compiler import plan_pump
        return plan_pump(**plan_kwargs)
    if pump == "measure":
        # measured-runtime planning: compile the kernel's IR graph through
        # the fused-region pallas backend with autotune='measure' and reuse
        # the winning factor here; the measured plan persists in the same
        # compile cache, so only the first process ever pays the timing runs.
        spec = _measured_spec(kernel, builder_args, builder_kwargs or {})
        if spec is not None:
            return spec
        from repro.compiler import plan_pump
        return plan_pump(**plan_kwargs)
    if isinstance(pump, int):
        return PumpSpec(factor=pump)
    return pump


def _measured_spec(kernel, builder_args, builder_kwargs):
    if kernel is None:
        return None
    from repro.core.autopump import BUILDERS
    from repro import compiler
    try:
        g, est = BUILDERS[kernel](*builder_args, **builder_kwargs)
        kern = compiler.compile(g, factor="auto", estimate=est,
                                backend="pallas", autotune="measure")
    except compiler.LoweringError as e:
        # expected for non-executable builder shapes (e.g. non-divisible
        # blocks leave fn=None): fall back to the capacity model, visibly
        import warnings
        warnings.warn(f"pump='measure' for {kernel}: graph not executable "
                      f"({e}); falling back to capacity-model planning",
                      stacklevel=3)
        return None
    return kern.spec


def _pump_request(pump):
    """Normalize a ``pump`` argument into ``(factor, mode, autotune)`` for
    ``compiler.compile``: ``'auto'`` → capacity-model factor, ``'measure'``
    → measured-runtime autotune, int/PumpSpec → explicit."""
    if pump == "auto":
        return "auto", "T", None
    if pump == "measure":
        return "auto", "T", "measure"
    if isinstance(pump, PumpSpec):
        return pump.factor, pump.mode, None
    return int(pump), "T", None


def _on_accelerator() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` means interpret mode exactly when no TPU is attached."""
    return not _on_accelerator() if interpret is None else bool(interpret)


def _use_compiler_route(impl: str, interpret: bool) -> bool:
    """The compiler route serves CPU validation (its carryloop/blockloop jit
    tiers) and real TPU emission.  ``interpret=False`` on CPU is an explicit
    request for *compiled* pallas execution, which the hand-wired path
    reports loudly instead of being silently downgraded."""
    return impl == "compiler" and (interpret or _on_accelerator())


@functools.lru_cache(maxsize=256)
def _compile_kernel_cached(kernel: str, builder_args, builder_kwargs_items,
                           pump):
    """Build the kernel's IR graph and compile it through the fused-region
    pallas backend.  The lru layer skips per-call graph reconstruction and
    fingerprint hashing on repeat shapes (the compiler's own memo already
    makes the compile itself O(1))."""
    from repro import compiler
    from repro.core.autopump import BUILDERS
    factor, mode, autotune = _pump_request(pump)
    g, est = BUILDERS[kernel](*builder_args, **dict(builder_kwargs_items))
    return compiler.compile(g, factor=factor, mode=mode, estimate=est,
                            backend="pallas", autotune=autotune)


def _compile_kernel(kernel: str, builder_args, builder_kwargs, pump):
    return _compile_kernel_cached(kernel, tuple(builder_args),
                                  tuple(sorted(builder_kwargs.items())),
                                  pump if isinstance(pump, (PumpSpec, str))
                                  else int(pump))


def _pad_to(x: jax.Array, axis: int, mult: int, value=0.0):
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x, n
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads, constant_values=value), n


# ------------------------------------------------------------------ vecadd --
@functools.partial(jax.jit, static_argnames=("vector_width", "pump_factor",
                                             "pump_mode", "interpret"))
def _vecadd(x, y, vector_width, pump_factor, pump_mode, interpret):
    spec = PumpSpec(factor=pump_factor, mode=pump_mode)
    block = vector_width * (pump_factor if pump_mode == "T" else 1)
    xp, n = _pad_to(x, 0, block)
    yp, _ = _pad_to(y, 0, block)
    return _va.vecadd_pallas(xp, yp, vector_width=vector_width, pump=spec,
                             interpret=interpret)[:n]


def vecadd(x, y, *, vector_width: int = 8, pump: PumpSpec | int | str = 1,
           interpret: Optional[bool] = None):
    """``pump``: factor, PumpSpec, ``'auto'`` (capacity model) or
    ``'measure'`` (timed on the compiled IR graph, cached)."""
    interpret = _resolve_interpret(interpret)
    spec = _as_spec(pump, kernel="vecadd", builder_args=(x.shape[0],),
                    builder_kwargs=dict(vector_width=vector_width),
                    block_bytes_in=2 * vector_width * x.dtype.itemsize,
                    block_bytes_out=vector_width * x.dtype.itemsize,
                    flops_per_block=vector_width)
    return _vecadd(x, y, vector_width, spec.factor, spec.mode, interpret)


# ------------------------------------------------------------------ matmul --
@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "pump_factor",
                                             "pump_mode", "interpret"))
def _matmul(a, b, bm, bn, bk, pump_factor, pump_mode, interpret):
    spec = PumpSpec(factor=pump_factor, mode=pump_mode)
    kw = bk * (pump_factor if pump_mode == "T" else 1)
    ap, m = _pad_to(a, 0, bm)
    ap, k = _pad_to(ap, 1, kw)
    bp, _ = _pad_to(b, 0, kw)
    bp, n = _pad_to(bp, 1, bn)
    out = _mm.matmul_pallas(ap, bp, bm=bm, bn=bn, bk=bk, pump=spec,
                            interpret=interpret)
    return out[:m, :n]


def matmul(a, b, *, bm: int = 128, bn: int = 128, bk: int = 128,
           pump: PumpSpec | int | str = 1, interpret: Optional[bool] = None):
    """``pump``: factor, PumpSpec, ``'auto'`` (capacity model) or
    ``'measure'`` (timed on the compiled IR graph, cached)."""
    interpret = _resolve_interpret(interpret)
    spec = _as_spec(
        pump, kernel="matmul",
        builder_args=(a.shape[0], b.shape[1], a.shape[1]),
        builder_kwargs=dict(bm=bm, bn=bn, bk=bk),
        block_bytes_in=(bm * bk + bk * bn) * a.dtype.itemsize,
        block_bytes_out=0,  # accumulated in VMEM, written once per tile
        flops_per_block=2.0 * bm * bn * bk)
    return _matmul(a, b, bm, bn, bk, spec.factor, spec.mode, interpret)


# ----------------------------------------------------------------- stencil --
@functools.partial(jax.jit, static_argnames=("stages", "kind", "coef",
                                             "pump_factor", "interpret"))
def _stencil(x, stages, kind, coef, pump_factor, interpret):
    return _st.stencil_chain_pallas(x, stages, kind=kind, coef=coef,
                                    pump=pump_factor, interpret=interpret)


def stencil_chain(x, stages: int, *, kind: str = "jacobi", coef: float = 0.1,
                  pump: PumpSpec | int = 1, interpret: Optional[bool] = None):
    interpret = _resolve_interpret(interpret)
    f = pump.factor if isinstance(pump, PumpSpec) else pump
    if (x.shape[0] - 2) % f:
        raise ValueError("interior plane count must divide the pump factor")
    return _stencil(x, stages, kind, coef, f, interpret)


# ---------------------------------------------------------- floyd-warshall --
@functools.partial(jax.jit, static_argnames=("pump_factor", "interpret"))
def _fw_run(d, pump_factor, interpret):
    return _fw.floyd_warshall_pallas(d, pump=pump_factor, interpret=interpret)


def floyd_warshall(dist, *, pump: PumpSpec | int = 1,
                   interpret: Optional[bool] = None):
    interpret = _resolve_interpret(interpret)
    f = pump.factor if isinstance(pump, PumpSpec) else pump
    n = dist.shape[0]
    if n % f:
        raise ValueError(f"n={n} must divide pump factor {f}")
    return _fw_run(dist, f, interpret)


# --------------------------------------------------------- flash attention --
@functools.partial(jax.jit, static_argnames=("causal", "bq", "bkv",
                                             "pump_factor", "interpret"))
def _flash(q, k, v, causal, bq, bkv, pump_factor, interpret):
    spec = PumpSpec(factor=pump_factor)
    b, hq, s, d = q.shape
    kwide = min(bkv, k.shape[2]) * pump_factor
    qp, s0 = _pad_to(q, 2, min(bq, s))
    kp, _ = _pad_to(k, 2, kwide)
    vp, _ = _pad_to(v, 2, kwide)
    # padded KV positions must not contribute: causal masking handles the
    # tail for causal=True; for non-causal we bias keys via -inf on k? We
    # instead require T % bkv == 0 after padding and mask via position ids:
    # simplest robust approach: pad K with -inf-scoring keys by zeroing V and
    # giving K a huge negative last-dim component is fragile; we pad S only.
    out = _fa.flash_attention_pallas(qp, kp, vp, causal=causal,
                                     bq=min(bq, s), bkv=min(bkv, k.shape[2]),
                                     pump=spec, interpret=interpret)
    return out[:, :, :s0, :]


def _flash_compiled(q, k, v, causal, bq, bkv, pump):
    b, hq, s, d = q.shape
    _, hkv, t, _ = k.shape
    bq, bkv = min(bq, s), min(bkv, t)
    if t % bkv:
        raise ValueError(f"T={t} %% bkv={bkv} != 0")
    qp, s0 = _pad_to(q, 2, bq)
    kern = _compile_kernel(
        "flash_attention", (b, hq, qp.shape[2], t, d),
        dict(bq=bq, bkv=bkv, hkv=hkv, causal=causal, dtype=str(q.dtype),
             itemsize=q.dtype.itemsize, stats=False), pump)
    out = kern({"q": qp, "k": k, "v": v})["o"]
    return out[:, :, :s0, :]


def flash_attention(q, k, v, *, causal: bool = False, bq: int = 128,
                    bkv: int = 128, pump: PumpSpec | int | str = 1,
                    interpret: Optional[bool] = None, impl: str = "compiler"):
    """Multi-head attention (GQA folded via a group-indexed table).

    ``impl='compiler'`` (default) compiles the executable IR builder through
    ``repro.compiler`` — BlockSpecs, the online-softmax carry and the pump
    schedule are all derived; ``impl='pallas'`` forces the hand-wired kernel
    (kept as the differential reference).  ``interpret=False`` on CPU keeps
    the hand-wired path's loud failure semantics."""
    interpret = _resolve_interpret(interpret)
    if _use_compiler_route(impl, interpret):
        return _flash_compiled(q, k, v, causal, bq, bkv, pump)
    d = q.shape[-1]
    spec = _as_spec(pump,
                    block_bytes_in=2 * bkv * d * q.dtype.itemsize,
                    block_bytes_out=0,
                    flops_per_block=4.0 * bq * bkv * d)
    if k.shape[2] % (min(bkv, k.shape[2]) * spec.factor):
        raise ValueError("KV length must divide bkv * pump factor")
    return _flash(q, k, v, causal, bq, bkv, spec.factor, interpret)


# ---------------------------------------------------------------- SSD scan --
@functools.partial(jax.jit, static_argnames=("chunk", "pump_factor",
                                             "interpret"))
def _ssd_jit(x, dt, A, B, C, chunk, pump_factor, interpret):
    return _ssd.ssd_scan_pallas(x, dt, A, B, C, chunk=chunk,
                                pump=pump_factor, interpret=interpret)


def _ssd_compiled(x, dt, A, B, C, chunk, pump, final_state=False):
    b, l, h, p = x.shape
    grp, n = B.shape[2], B.shape[3]
    chunk = min(chunk, l)
    if l % chunk:
        raise ValueError(f"L={l} %% chunk={chunk} != 0")
    kern = _compile_kernel(
        "ssd_scan", (b, l, h, p, n),
        dict(chunk=chunk, n_groups=grp, dtype=str(x.dtype),
             itemsize=x.dtype.itemsize, final_state=bool(final_state)), pump)
    out = kern({"x": x, "dt": dt, "a": A, "bmat": B, "cmat": C})
    if final_state:
        return out["y"], out["state"]
    return out["y"]


def ssd_scan(x, dt, A, B, C, *, chunk: int = 16,
             pump: PumpSpec | int | str = 1, interpret: Optional[bool] = None,
             impl: str = "compiler", final_state: bool = False):
    """Mamba-2 SSD chunked scan.  ``impl='compiler'`` (default) compiles the
    carry-graph IR builder; ``impl='pallas'`` forces the hand-wired kernel
    (the differential reference).  ``final_state=True`` also returns the
    final inter-chunk state (B, H, N, P) as a second output — the carry
    state surfaced through ``CarrySpec.final_fn``; compiler-only (the
    hand-wired kernel never exposes its state)."""
    interpret = _resolve_interpret(interpret)
    if _use_compiler_route(impl, interpret):
        return _ssd_compiled(x, dt, A, B, C, chunk, pump, final_state)
    if final_state:
        raise ValueError("ssd_scan(final_state=True) requires the compiler "
                         "route (impl='compiler')")
    b, l, h, p = x.shape
    n = B.shape[-1]
    spec = _as_spec(pump,
                    block_bytes_in=(chunk * (p + 1 + 2 * n)) * 4,
                    block_bytes_out=chunk * p * 4,
                    flops_per_block=2.0 * chunk * chunk * (n + p))
    if l % (chunk * spec.factor):
        raise ValueError(f"L={l} must divide chunk*M={chunk * spec.factor}")
    return _ssd_jit(x, dt, A, B, C, chunk, spec.factor, interpret)


# ------------------------------------------------------- decode attention --
def decode_attention(q, k_cache, v_cache, pos, *, bkv: int | None = None,
                     pump: PumpSpec | int | str = 1, impl: str = "compiler"):
    """Single-position (S=1) attention against a preallocated KV cache.

    q: (B, H, D); caches: (B, Hkv, T, D); ``pos`` is the current write
    position (scalar or (B,) int32) — valid cache slots are 0..pos, masked
    *symbolically* inside the kernel (the position-offset causal mask is an
    index compare derived from the carry step, never a materialized (B, T)
    boolean).  Compiler-only: the decode builder has no hand-wired
    counterpart; serving routes here through the plan registry
    (``PlanRegistry.decode_attention``), which adds pos-bucketing.  ``bkv``
    defaults to the decode graph's own tile for these shapes."""
    if impl != "compiler":
        raise ValueError("decode_attention is compiler-only")
    b, h, d = q.shape
    hkv, t = k_cache.shape[1], k_cache.shape[2]
    kern = _compile_kernel(
        "decode_attention", (b, h, t, d),
        dict(bkv=bkv, hkv=hkv, dtype=str(q.dtype),
             itemsize=q.dtype.itemsize), pump)
    posv = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(pos, jnp.int32)),
                            (b,))
    out = kern({"q": q.reshape(b, hkv, h // hkv, d), "k": k_cache,
                "v": v_cache, "pos": posv})["o"]
    return out.reshape(b, h, d)


# -------------------------------------------------------------- ssd decode --
def ssd_decode(state, x, dt, A, B, C, *, pump: PumpSpec | int | str = 1,
               impl: str = "compiler"):
    """Single-token SSD recurrent step: ``state' = state·exp(A·dt) +
    (B·dt)⊗x``, ``y = C·state'``.  state: (B, H, N, P) fp32; x: (B, H, P);
    dt: (B, H) post-softplus; A: (H,); B/C: (B, G, N).  Returns
    (y, new_state).  Compiler-only (multi-output tile emission)."""
    if impl != "compiler":
        raise ValueError("ssd_decode is compiler-only")
    b, h, n, p = state.shape
    grp = B.shape[1]
    kern = _compile_kernel(
        "ssd_decode", (b, h, p, n),
        dict(n_groups=grp, dtype=str(x.dtype),
             itemsize=x.dtype.itemsize), pump)
    out = kern({"state": state, "x": x, "dt": dt, "a": A,
                "bmat": B, "cmat": C})
    return out["y"], out["state_out"]


# ------------------------------------------------------------ grouped gemm --
def ragged_request_args(e, d, f, padded, bc, bf, bd, dtype, itemsize):
    """Canonical (builder_args, builder_kwargs) for one ragged grouped-GEMM
    request.  The single source of truth for the compile/plan key: the plan
    registry derives warmup keys from it and the execution path below
    compiles under it, so a warmed plan is a guaranteed hit for the real
    call by construction."""
    rows_p = sum(padded)
    dp = -(-d // bd) * bd
    fp = -(-f // bf) * bf
    return ((e, rows_p, dp, fp),
            dict(bc=bc, bf=bf, bd=bd, group_sizes=tuple(padded),
                 dtype=dtype, itemsize=itemsize))


def ragged_grouped_gemm_compiled(x, w, sizes, padded, bc, bf, bd, *,
                                 kernel_fn=None, pump=1):
    """Shared ragged-execution core (megablocks idiom).

    ``x`` is a row-major concatenation of per-expert row groups
    (``sum(sizes)`` rows); each group is zero-padded up to ``padded[i]``
    (a multiple of the row tile ``bc``; 0 skips the expert entirely), the
    ragged IR builder compiles with group-indexed table access, and the real
    rows are sliced back out.  Callers that already hold the padded layout
    (``sizes == padded``, e.g. the MoE serving path, which scatters tokens
    into it once for all three expert GEMMs) skip the per-group
    segmentation and re-slicing entirely.  ``kernel_fn(builder_args,
    builder_kwargs)`` lets the plan registry own the compile (stats +
    measured plans); the default routes through this module's compile
    cache.
    """
    e, d, f = w.shape
    rows_p = sum(padded)
    if rows_p == 0:
        return jnp.zeros((0, f), x.dtype)
    prepadded = list(sizes) == list(padded)
    if prepadded:
        xp = x
    else:
        parts, off = [], 0
        for sz, psz in zip(sizes, padded):
            seg = x[off:off + sz]
            off += sz
            if psz > sz:
                seg = jnp.pad(seg, ((0, psz - sz), (0, 0)))
            if psz:
                parts.append(seg)
        xp = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    xp, _ = _pad_to(xp, 1, bd)
    wp, _ = _pad_to(w, 1, bd)
    wp, _ = _pad_to(wp, 2, bf)
    builder_args, builder_kwargs = ragged_request_args(
        e, d, f, padded, bc, bf, bd, str(x.dtype), x.dtype.itemsize)
    if kernel_fn is None:
        kern = _compile_kernel("grouped_gemm", builder_args, builder_kwargs,
                               pump)
    else:
        kern = kernel_fn(builder_args, builder_kwargs)
    out = kern({"x": xp, "w": wp})["o"][:, :f]
    if prepadded:
        return out
    outs, off = [], 0
    for sz, psz in zip(sizes, padded):
        if sz:
            outs.append(out[off:off + sz])
        off += psz
    if not outs:
        return jnp.zeros((0, f), x.dtype)
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("bc", "bf", "bd", "pump_factor",
                                             "pump_mode", "interpret"))
def _grouped(x, w, bc, bf, bd, pump_factor, pump_mode, interpret):
    spec = PumpSpec(factor=pump_factor, mode=pump_mode)
    dw = bd * (pump_factor if pump_mode == "T" else 1)
    xp, c0 = _pad_to(x, 1, bc)
    xp, d0 = _pad_to(xp, 2, dw)
    wp, _ = _pad_to(w, 1, dw)
    wp, f0 = _pad_to(wp, 2, bf)
    out = _gg.grouped_gemm_pallas(xp, wp, bc=bc, bf=bf, bd=bd, pump=spec,
                                  interpret=interpret)
    return out[:, :c0, :f0]


def _grouped_compiled(x, w, bc, bf, bd, pump):
    e, c, d = x.shape
    f = w.shape[2]
    bc, bf, bd = min(bc, c), min(bf, f), min(bd, d)
    xp_, c0 = _pad_to(x, 1, bc)
    xp_, _ = _pad_to(xp_, 2, bd)
    wp, _ = _pad_to(w, 1, bd)
    wp, f0 = _pad_to(wp, 2, bf)
    kern = _compile_kernel(
        "grouped_gemm", (e, xp_.shape[1], xp_.shape[2], wp.shape[2]),
        dict(bc=bc, bf=bf, bd=bd, dtype=str(x.dtype),
             itemsize=x.dtype.itemsize), pump)
    out = kern({"x": xp_, "w": wp})["o"]
    return out[:, :c0, :f0]


def grouped_gemm(x, w, *, bc: int = 128, bf: int = 128, bd: int = 128,
                 pump: PumpSpec | int | str = 1,
                 interpret: Optional[bool] = None, impl: str = "compiler",
                 group_sizes=None):
    """Per-expert batched GEMM (MoE hot-spot).

    Dense form (``group_sizes=None``): x (E,C,D) @ w (E,D,F).
    Ragged form: ``group_sizes`` is a static sequence of per-expert row
    counts; x is the (sum(group_sizes), D) row-major concatenation of the
    expert groups and the result keeps that layout — tokens pad only to the
    ``bc`` row tile instead of a dense worst-case capacity, and empty
    experts emit no tiles at all.  The ragged form is compiler-only
    (group-indexed table BlockSpecs have no hand-wired counterpart).

    ``impl='compiler'`` (default) compiles the IR builder (expert axis as
    the outermost grid symbol, contraction accumulated over the reduction
    symbol); ``impl='pallas'`` forces the hand-wired kernel."""
    if group_sizes is not None:
        if impl != "compiler":
            raise ValueError("ragged grouped_gemm (group_sizes=...) is "
                             "compiler-only; the hand-wired kernel has no "
                             "ragged form")
        sizes = [int(sz) for sz in group_sizes]
        e, d, f = w.shape
        if x.ndim != 2 or x.shape[0] != sum(sizes):
            raise ValueError(f"ragged x has {x.shape[0]} rows, group_sizes "
                             f"sum to {sum(sizes)}")
        if len(sizes) != e:
            raise ValueError(f"{len(sizes)} group sizes for {e} experts")
        bc_e = min(bc, max(max(sizes, default=1), 1))
        padded = [-(-sz // bc_e) * bc_e if sz else 0 for sz in sizes]
        return ragged_grouped_gemm_compiled(
            x, w, sizes, padded, bc_e, min(bf, f), min(bd, d),
            pump=pump if isinstance(pump, (PumpSpec, str)) else int(pump))
    interpret = _resolve_interpret(interpret)
    if _use_compiler_route(impl, interpret):
        return _grouped_compiled(x, w, bc, bf, bd, pump)
    spec = _as_spec(pump,
                    block_bytes_in=(bc * bd + bd * bf) * x.dtype.itemsize,
                    block_bytes_out=0,
                    flops_per_block=2.0 * bc * bf * bd)
    return _grouped(x, w, bc, bf, bd, spec.factor, spec.mode, interpret)
