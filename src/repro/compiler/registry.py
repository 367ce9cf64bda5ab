"""Process-level plan registry: shape-bucketed measured execution plans.

The compile cache (:mod:`.cache`) makes a *repeat* compile O(1), but the
serving layers never see that win when every decode step arrives with a
slightly different shape — each (batch, seq) pair is a distinct graph and a
cold ``autotune='measure'`` search.  The registry closes that gap:

* **Shape bucketing** — batch and sequence dims are padded up to a small
  ladder of buckets (powers of two by default), so the unbounded space of
  serve-time shapes collapses onto a handful of graphs.  Padding is value-
  preserving by construction: attention pads KV only under a causal mask
  (padded keys sit at positions no real query may attend), the SSD scan pads
  timesteps with ``dt=0`` (an identity step for the carried state), and the
  grouped GEMM pads rows with zeros whose outputs are sliced away.
* **Measured plans** — every bucket compiles through
  ``compiler.compile(autotune='measure', backend='pallas')``: the pump
  factor M is chosen from measured runtimes, persisted in the compile cache,
  and replayed (no re-measurement) by every later process.
* **Warm lookup** — an in-process ``{request → CompiledKernel}`` map serves
  steady-state decode in O(1); :meth:`PlanRegistry.warmup` pre-measures the
  whole bucket grid at launch so the first real request is already a hit.

``models/*`` route their kernel hot paths here when
``ModelConfig.kernel_plan == 'measure'`` (the default); the direct
``kernels.ops`` path stays available behind ``kernel_plan='direct'`` as the
differential reference.  A corrupted persistent cache degrades to a cold
compile (the :class:`~repro.compiler.cache.CompileCache` contract); a
lowering failure degrades to the direct ops path with a visible warning.
"""
from __future__ import annotations

import dataclasses
import math
import time
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp

from repro import obs
from repro.testing import faults


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length() if n > 1 else 1


def _fit_block(block: int, n: int) -> int:
    """Largest block size ≤ ``block`` that divides ``n`` (n ≥ 1)."""
    cand = min(block, n)
    if n % cand:
        cand = math.gcd(n, cand)
    return max(cand, 1)


@dataclasses.dataclass(frozen=True)
class BucketPolicy:
    """How call shapes are rounded up to plan buckets.

    ``seq_min``/``batch_min`` floor the respective ladders; buckets are the
    powers of two above the floor, so a growing decode context touches
    O(log T) plans instead of O(T).  ``row_block`` is the ragged grouped-GEMM
    row tile: each expert's token group pads to a power-of-two multiple of
    it (0 stays 0 — empty experts contribute no tiles at all).
    """
    seq_min: int = 16
    batch_min: int = 1
    row_block: int = 16

    def bucket_seq(self, n: int, multiple: int = 1) -> int:
        b = max(self.seq_min, _next_pow2(max(n, 1)))
        if multiple > 1 and b % multiple:
            b = -(-b // multiple) * multiple
        return b

    def bucket_batch(self, n: int) -> int:
        return max(self.batch_min, _next_pow2(max(n, 1)))

    def bucket_pos(self, pos) -> int:
        """Decode pos bucket: the seq bucket covering slots ``0..pos``.

        Accepts a scalar or a per-slot ``(B,)`` vector of in-flight
        positions (continuous batching) — a ragged batch buckets on its
        *furthest* row, so every lane's prefix fits one shared plan and
        shorter lanes just mask more."""
        import numpy as _np
        return self.bucket_seq(int(_np.max(_np.asarray(pos))) + 1)

    def bucket_group(self, n: int) -> int:
        """Ragged group-size bucket: 0, or a pow2 multiple of row_block."""
        if n <= 0:
            return 0
        tiles = -(-n // self.row_block)
        return self.row_block * _next_pow2(tiles)

    def seq_grid(self, max_len: int, multiple: int = 1) -> List[int]:
        """All seq buckets from the floor up to ``bucket_seq(max_len)``."""
        top = self.bucket_seq(max_len, multiple)
        out, b = [], self.bucket_seq(1, multiple)
        while b < top:
            out.append(b)
            b = self.bucket_seq(b + 1, multiple)
        out.append(top)
        return out


# the S=1 serving fast path: plans keyed by these kernels are counted under
# the "decode" phase so a cold decode bucket is visible at a glance in the
# registry-stats printout (everything else is "prefill" — prefill, scoring
# and benchmark forward plans)
DECODE_KERNELS = frozenset({"decode_attention", "ssd_decode"})


def _phase_of(kernel: str) -> str:
    return "decode" if kernel in DECODE_KERNELS else "prefill"


@dataclasses.dataclass
class RegistryStats:
    """Hit/miss/fallback accounting, split by serving phase.

    Counts mirror into the process-wide obs metrics registry
    (``registry.{phase}.{hit|miss}``, ``registry.fallback.{phase}``) so the
    unified snapshot carries them; the dataclass itself stays the per-
    instance view (tests and benchmarks diff instances around a window, so
    the local counters are not replaced by the global ones).  The active
    default registry additionally publishes ``as_dict()`` as the
    ``plan_registry`` snapshot view.
    """
    hits: int = 0
    misses: int = 0
    measure_s: float = 0.0    # cold measured-autotune compiles
    compile_s: float = 0.0    # replayed / non-measured compiles
    fallbacks: int = 0        # lookups that fell back to the direct path
    # per-phase split of hits/misses/fallbacks (see DECODE_KERNELS)
    phase: Dict[str, Dict[str, int]] = dataclasses.field(
        default_factory=lambda: {
            "prefill": {"hits": 0, "misses": 0, "fallbacks": 0},
            "decode": {"hits": 0, "misses": 0, "fallbacks": 0}})

    def count(self, kernel: str, hit: bool) -> None:
        ph = _phase_of(kernel)
        bucket = self.phase[ph]
        if hit:
            self.hits += 1
            bucket["hits"] += 1
            obs.count(f"registry.{ph}.hit", kernel=kernel)
        else:
            self.misses += 1
            bucket["misses"] += 1
            obs.count(f"registry.{ph}.miss", kernel=kernel)

    def fallback(self, kernel: str, why: str = "") -> None:
        """A lookup that fell back to the direct path — split per phase so
        a decode-path fallback (the highest-frequency path) is visible at a
        glance instead of buried in a global total."""
        ph = _phase_of(kernel)
        self.fallbacks += 1
        self.phase[ph]["fallbacks"] += 1
        obs.count(f"registry.fallback.{ph}", kernel=kernel, why=why)

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def as_dict(self) -> Dict[str, Any]:
        return {"hits": self.hits, "misses": self.misses,
                "hit_rate": round(self.hit_rate, 4),
                "measure_s": round(self.measure_s, 4),
                "compile_s": round(self.compile_s, 4),
                "fallbacks": self.fallbacks,
                "prefill": dict(self.phase["prefill"]),
                "decode": dict(self.phase["decode"])}


class PlanRegistry:
    """Shape-bucketed front for ``compiler.compile`` on the serving path.

    ``pump`` is ``'measure'`` (measured-runtime autotune, the default),
    ``'auto'`` (capacity model) or an explicit int factor.  ``cache`` is a
    :class:`~repro.compiler.cache.CompileCache`, ``None`` for the default
    persistent cache or ``False`` to disable disk persistence.
    """

    def __init__(self, policy: Optional[BucketPolicy] = None, *,
                 pump="measure", ragged_pump="auto", backend: str = "pallas",
                 cache=None, spot_check: str = "finite"):
        self.policy = policy or BucketPolicy()
        self.pump = pump
        # ragged grouped-GEMM plans are keyed on the per-expert padded-size
        # tuple, which shifts with routing: a measured autotune (seconds of
        # timing runs) on every fresh tuple would land mid-request, so the
        # ragged path defaults to capacity-model planning ('auto', a
        # milliseconds-cold compile).  Set ragged_pump='measure' only when
        # the routing patterns are known and pre-warmed.
        self.ragged_pump = ragged_pump
        self.backend = backend
        self._cache = cache
        # post-compile validation level: 'finite' runs every fresh kernel
        # once on small deterministic inputs and rejects non-finite output
        # (a NaN kernel must be caught at plan time, not inside the jit'd
        # decode step where values can't be branched on); 'diff' adds a
        # differential check against the numpy reference executor; 'off'
        # disables validation.
        self.spot_check = spot_check
        self._plans: Dict[Tuple, Any] = {}
        # wrapper-level fast path: raw call signature -> (plan, padded
        # dims).  The canonical plan key is derived through bucket math +
        # a sorted-kwargs tuple build on every lookup; at steady state
        # that per-call Python cost is the *whole* overhead of the
        # registry path vs a direct kernel call (the measured ~3%
        # prefill_flash gap), so warm wrapper calls memoize the full
        # resolution and skip straight to pad + execute.
        self._lookup: Dict[Tuple, Any] = {}
        # in-trace cold misses on a 'measure' policy are served from the
        # capacity-model plan space (see kernel()); memoized per key so a
        # long trace pays the warn + re-lookup recursion once, not per call
        self._trace_memo: Dict[Tuple, Any] = {}
        self.stats = RegistryStats()

    def _store(self):
        """The persistent CompileCache backing this registry (quarantine
        ledger access), or None when disk caching is disabled."""
        if self._cache is None:
            from .cache import default_cache
            return default_cache()
        return self._cache or None

    # ------------------------------------------------------------- lookup --
    def _request(self, pump=None) -> Tuple[Any, str, Optional[str]]:
        pump = self.pump if pump is None else pump
        if pump == "measure":
            return "auto", "T", "measure"
        if pump == "auto":
            return "auto", "T", None
        return int(pump), "T", None

    def kernel(self, kernel: str, builder_args: Tuple,
               builder_kwargs: Dict[str, Any], pump=None):
        """Compiled kernel for one canonical (bucketed) request — the only
        place the registry talks to the compiler.  ``pump`` overrides the
        registry-wide policy for this request (the ragged path uses it)."""
        pump = self.pump if pump is None else pump
        key = (kernel, tuple(builder_args),
               tuple(sorted(builder_kwargs.items())), pump, self.backend)
        if key in self._plans:
            self.stats.count(kernel, hit=True)
            return self._plans[key]
        from repro import compiler
        if pump == "measure" and not compiler._trace_state_clean():
            # a cold miss inside a jit trace must not run the measured
            # autotune (in-trace timings are garbage and catastrophically
            # slow): serve this lookup from the capacity-model plan space
            # instead, and leave the measure slot empty so warmup()/an
            # eager call can still fill it with a real measured plan.
            # Memoized per key: only the first in-trace miss pays the
            # warning + recursive re-lookup.
            hit = self._trace_memo.get(key)
            if hit is not None:
                self.stats.count(kernel, hit=True)
                return hit
            warnings.warn(
                f"plan registry: cold miss for {kernel}{tuple(builder_args)}"
                " inside a jax trace — using capacity-model planning; call "
                "warmup() at launch to pre-measure this bucket",
                stacklevel=3)
            kern = self.kernel(kernel, builder_args, builder_kwargs,
                               pump="auto")
            self._trace_memo[key] = kern
            return kern
        self.stats.count(kernel, hit=False)
        from repro.core.autopump import BUILDERS
        factor, mode, autotune = self._request(pump)
        with obs.span("registry.compile", cat="serve", kernel=kernel,
                      args=list(builder_args), pump=str(pump)) as sp:
            g, est = BUILDERS[kernel](*builder_args, **builder_kwargs)
            t0 = time.perf_counter()
            # compile through the degradation ladder: a pallas-backend
            # failure (or an open quarantine window on the pallas rung)
            # degrades to the per-node jax lowering instead of raising —
            # the wrapper-level plain-jnp fallback stays the last rung
            kern = compiler.compile_degraded(
                g, factor=factor, mode=mode, estimate=est,
                backend=self.backend, autotune=autotune, cache=self._cache)
            bad = self._spot_check_reason(kern)
            if bad is not None:
                # poisoned kernel (compiles fine, computes garbage): purge
                # the memo so the retry cannot be served the same artifact,
                # quarantine the rung that produced it, degrade once
                obs.count("registry.spotcheck_failed", kernel=kernel,
                          backend=kern.backend, reason=bad)
                ckey = kern.report.cache_key
                if ckey:
                    compiler.forget(ckey)
                store = self._store()
                if store is not None and ckey:
                    store.record_failure(f"{ckey}:{kern.backend}", bad)
                kern = compiler.compile_degraded(
                    g, factor=factor, mode=mode, estimate=est,
                    backend=self.backend, autotune=autotune,
                    cache=self._cache)
                bad2 = self._spot_check_reason(kern)
                if bad2 is not None:
                    raise RuntimeError(
                        f"plan registry: {kernel} failed the {bad!r} "
                        f"spot-check and its degraded recompile failed "
                        f"{bad2!r} — refusing to install the plan")
                kern.report.warn(
                    f"spot-check rejected the first compile ({bad}); "
                    f"serving the degraded recompile (backend="
                    f"{kern.backend})")
            dt = time.perf_counter() - t0
            tuned = kern.report.autotune
            if tuned and not tuned.get("replayed"):
                self.stats.measure_s += dt   # paid the timing runs
                obs.count("registry.measure", kernel=kernel)
            else:
                self.stats.compile_s += dt   # replayed plan / plain compile
                obs.count("registry.replay" if tuned
                          else "registry.plan_compile", kernel=kernel)
            sp.set(factor=kern.spec.factor,
                   measured=bool(tuned and not tuned.get("replayed")))
        if faults.active():
            # chaos seam: simulate a plan that fails/corrupts on the serving
            # path after installation (zero-cost in production: never taken)
            kern = dataclasses.replace(
                kern, fn=faults.wrap("registry.exec", kern.fn, kernel=kernel))
        self._plans[key] = kern
        return kern

    def _spot_check_reason(self, kern) -> Optional[str]:
        """Validate a freshly compiled kernel eagerly; returns the failure
        reason (``exec:*`` / ``nonfinite`` / ``diff:*``) or None.  Skipped
        inside jax traces (can't branch on values there — exactly why the
        check exists at plan time) and when validation is off."""
        from repro import compiler
        if (self.spot_check == "off" or kern.fn is None
                or not compiler._trace_state_clean()):
            return None
        import numpy as np
        inputs = _probe_inputs(kern.graph)
        try:
            out = kern.fn(inputs)
        except Exception as e:  # noqa: BLE001 — any exec failure poisons it
            return f"exec:{type(e).__name__}"
        vals = out.items() if isinstance(out, dict) else [("out", out)]
        for name, a in vals:
            arr = np.asarray(a)
            if arr.dtype.kind == "f" and not np.isfinite(arr).all():
                return "nonfinite"
        if self.spot_check == "diff":
            from repro.core import executor
            ref = executor.run(kern.graph, dict(inputs))
            for name, a in (out.items() if isinstance(out, dict) else []):
                if name in inputs or name not in ref:
                    continue
                got, want = np.asarray(a, np.float64), \
                    np.asarray(ref[name], np.float64)
                if got.shape == want.shape and \
                        not np.allclose(got, want, rtol=1e-2, atol=1e-3):
                    return f"diff:{name}"
        return None

    def plans(self) -> List[Dict[str, Any]]:
        """Summaries of every resident plan (benchmark/report surface)."""
        out = []
        for (kernel, args, kwargs, pump, backend), kern in self._plans.items():
            tuned = kern.report.autotune or {}
            # the grid of the region that names the kernel (its first)
            regions = list((kern.report.emission or {}).values())
            out.append({
                "kernel": kernel, "args": list(args),
                "bkv": dict(kwargs).get("bkv"),
                "grid": regions[0]["grid"] if regions else None,
                "factor": kern.spec.factor, "mode": kern.spec.mode,
                "pump": pump, "backend": backend,
                "measured": tuned.get("policy") == "measure",
                "replayed": bool(tuned.get("replayed")),
                "served_from": kern.report.served_from,
            })
        return out

    def reset(self) -> None:
        self._plans.clear()
        self._lookup.clear()
        self._trace_memo.clear()
        self.stats = RegistryStats()

    # ----------------------------------------------------------- requests --
    # Canonical (builder_args, builder_kwargs, padded dims) per kernel.
    # Wrappers and warmup() share these so a warmed bucket is a guaranteed
    # hit for the real call.
    def flash_request(self, *, b: int, h: int, hkv: int, s: int, t: int,
                      d: int, causal: bool, dtype: str, bq: int = 128,
                      bkv: int = 128):
        bb = self.policy.bucket_batch(b)
        sb = self.policy.bucket_seq(s)
        bq_e = _fit_block(bq, sb)
        # KV padding is masked out only under causality (padded keys sit at
        # positions ≥ every real query); non-causal keeps the exact length.
        tb = self.policy.bucket_seq(t) if causal else t
        bkv_e = _fit_block(bkv, tb)     # always divides tb
        itemsize = jnp.dtype(dtype).itemsize
        args = (bb, h, sb, tb, d)
        kwargs = dict(bq=bq_e, bkv=bkv_e, hkv=hkv, causal=causal,
                      dtype=dtype, itemsize=itemsize, stats=False)
        return args, kwargs, (bb, sb, tb)

    def ssd_request(self, *, b: int, l: int, h: int, p: int, n: int,
                    chunk: int, n_groups: int, dtype: str,
                    final_state: bool = False):
        bb = self.policy.bucket_batch(b)
        lb = self.policy.bucket_seq(l)
        chunk_e = _fit_block(chunk, lb)
        itemsize = jnp.dtype(dtype).itemsize
        args = (bb, lb, h, p, n)
        kwargs = dict(chunk=chunk_e, n_groups=n_groups, dtype=dtype,
                      itemsize=itemsize, final_state=bool(final_state))
        return args, kwargs, (bb, lb)

    def decode_request(self, *, b: int, h: int, hkv: int, t: int, d: int,
                       dtype: str, bkv: Optional[int] = None):
        """S=1 decode attention bucket: ``t`` is the attended cache prefix
        (pos + 1 when the position is concrete, the full preallocated cache
        length under a jit trace) and buckets on the same pow2 ladder as
        prefill sequence dims — a growing decode context touches O(log T)
        plans, keyed separately from prefill by the kernel name.  The KV
        tile is the decode graph's own choice for the bucket's shapes
        (:func:`~repro.core.autopump.decode_kv_tile`) unless ``bkv`` is
        given."""
        from repro.core.autopump import decode_kv_tile
        bb = self.policy.bucket_batch(b)
        tb = self.policy.bucket_seq(t)
        itemsize = jnp.dtype(dtype).itemsize
        bkv_e = decode_kv_tile(tb, d, h // hkv, itemsize) if bkv is None \
            else _fit_block(bkv, tb)
        args = (bb, h, tb, d)
        kwargs = dict(bkv=bkv_e, hkv=hkv, dtype=dtype, itemsize=itemsize)
        return args, kwargs, (bb, tb)

    def ssd_decode_request(self, *, b: int, h: int, p: int, n: int,
                           n_groups: int, dtype: str):
        bb = self.policy.bucket_batch(b)
        args = (bb, h, p, n)
        kwargs = dict(n_groups=n_groups, dtype=dtype,
                      itemsize=jnp.dtype(dtype).itemsize)
        return args, kwargs, (bb,)

    def grouped_request(self, *, e: int, d: int, f: int,
                        group_sizes: Sequence[int], dtype: str,
                        bf: int = 128, bd: int = 128):
        bc = self.policy.row_block
        padded = tuple(self.policy.bucket_group(int(sz))
                       for sz in group_sizes)
        bd_e, bf_e = _fit_block(bd, d), _fit_block(bf, f)
        # the execution path (ops.ragged_grouped_gemm_compiled) compiles
        # under the same canonical request — one source of truth, so a
        # warmed key always matches the real call's key
        from repro.kernels.ops import ragged_request_args
        args, kwargs = ragged_request_args(
            e, d, f, padded, bc, bf_e, bd_e, dtype,
            jnp.dtype(dtype).itemsize)
        return args, kwargs, padded

    # ------------------------------------------------------------ wrappers --
    def flash_attention(self, q, k, v, *, causal: bool = False,
                        bq: int = 128, bkv: int = 128):
        """Bucketed flash attention.  q: (B, H, S, D); k/v: (B, Hkv, T, D)."""
        b, h, s, d = q.shape
        hkv, t = k.shape[1], k.shape[2]
        lk = (b, h, hkv, s, t, d, causal, str(q.dtype), bq, bkv)
        hit = self._lookup.get(lk)
        if hit is not None:
            # warm fast path: signature -> installed plan, no bucket math
            kern, bb, sb, tb = hit
            self.stats.count("flash_attention", hit=True)
        else:
            try:
                args, kwargs, (bb, sb, tb) = self.flash_request(
                    b=b, h=h, hkv=hkv, s=s, t=t, d=d, causal=causal,
                    dtype=str(q.dtype), bq=bq, bkv=bkv)
                kern = self.kernel("flash_attention", args, kwargs)
            except Exception as e:  # noqa: BLE001 — serving must not die
                self.stats.fallback("flash_attention", why=str(e))
                warnings.warn(f"plan registry: flash_attention fell back to "
                              f"the direct ops path ({e})", stacklevel=2)
                from repro.kernels.ops import flash_attention as _flash
                return _flash(q, k, v, causal=causal, bq=bq, bkv=bkv)
            from repro import compiler
            if compiler._trace_state_clean():
                # never memoize a traced resolution: an in-trace measure
                # miss serves a capacity plan, and freezing that into the
                # fast path would keep eager calls off the measured plan
                # warmup later installs
                self._lookup[lk] = (kern, bb, sb, tb)
        qp = _pad_axes(q, {0: bb, 2: sb})
        kp = _pad_axes(k, {0: bb, 2: tb})
        vp = _pad_axes(v, {0: bb, 2: tb})
        try:
            out = kern({"q": qp, "k": kp, "v": vp})["o"]
        except Exception as e:  # noqa: BLE001 — exec failure: degrade a rung
            self.stats.fallback("flash_attention", why=f"exec: {e}")
            warnings.warn(f"plan registry: flash_attention kernel execution "
                          f"fell back to the direct ops path ({e})",
                          stacklevel=2)
            from repro.kernels.ops import flash_attention as _flash
            return _flash(q, k, v, causal=causal, bq=bq, bkv=bkv)
        if (bb, sb) == (b, s):
            return out          # exact bucket: skip the slice dispatch
        return out[:b, :, :s, :]

    def ssd_scan(self, x, dt, A, B, C, *, chunk: int = 16,
                 final_state: bool = False):
        """Bucketed SSD scan.  x: (B, L, H, P); dt zero-padding is an
        identity step for the carried state, so L-padding is exact — which
        also makes the ``final_state=True`` form exact: padded steps leave
        the carried state untouched, so the padded sweep's final state *is*
        the real final state.  Returns y, or ``(y, state)`` with
        ``final_state=True`` (state: (B, H, N, P) fp32 — the cached-prefill
        route)."""
        b, l, h, p = x.shape
        grp, n = B.shape[2], B.shape[3]
        try:
            args, kwargs, (bb, lb) = self.ssd_request(
                b=b, l=l, h=h, p=p, n=n, chunk=chunk, n_groups=grp,
                dtype=str(x.dtype), final_state=final_state)
            kern = self.kernel("ssd_scan", args, kwargs)
        except Exception as e:  # noqa: BLE001
            self.stats.fallback("ssd_scan", why=str(e))
            if final_state:
                # ops.ssd_scan(final_state=True) is compiler-only and would
                # re-raise on the same failure; degrade to the sequential
                # jnp recurrence, which does produce the final state
                warnings.warn(f"plan registry: ssd_scan fell back to the "
                              f"plain jnp scan ({e})", stacklevel=2)
                return _ssd_scan_reference(x, dt, A, B, C)
            warnings.warn(f"plan registry: ssd_scan fell back to the direct "
                          f"ops path ({e})", stacklevel=2)
            from repro.kernels.ops import ssd_scan as _ssd
            return _ssd(x, dt, A, B, C, chunk=chunk)
        xp = _pad_axes(x, {0: bb, 1: lb})
        dtp = _pad_axes(dt, {0: bb, 1: lb})
        bp = _pad_axes(B, {0: bb, 1: lb})
        cp = _pad_axes(C, {0: bb, 1: lb})
        try:
            out = kern({"x": xp, "dt": dtp, "a": A, "bmat": bp, "cmat": cp})
        except Exception as e:  # noqa: BLE001 — exec failure: degrade a rung
            self.stats.fallback("ssd_scan", why=f"exec: {e}")
            warnings.warn(f"plan registry: ssd_scan kernel execution fell "
                          f"back to the plain jnp scan ({e})", stacklevel=2)
            y, st = _ssd_scan_reference(x, dt, A, B, C)
            return (y, st) if final_state else y
        y = out["y"]
        if final_state:
            st = out["state"]
            if (bb, lb) == (b, l):
                return y, st
            return y[:b, :l], st[:b]
        if (bb, lb) == (b, l):
            return y            # exact bucket: skip the slice dispatch
        return y[:b, :l]

    def decode_attention(self, q, k_cache, v_cache, pos, *,
                         bkv: Optional[int] = None):
        """Kernelized S=1 decode: one query row per head against the
        preallocated KV cache.  q: (B, H, D); caches: (B, Hkv, T, D);
        ``pos`` is the current write position (scalar or (B,) int32 — valid
        cache slots are 0..pos, enforced by the kernel's symbolic position
        mask).

        With a *concrete* ``pos`` (eager serving / benchmarks) the cache is
        sliced to the pos bucket before the call, so a decode step costs
        O(bucket(pos)), not O(max_len); a traced ``pos`` (the jit'd engine
        decode step) keys one plan on the full preallocated length and lets
        the mask do the work."""
        import jax
        b, h, d = q.shape
        hkv, t = k_cache.shape[1], k_cache.shape[2]
        try:
            if jnp.ndim(pos):
                # per-slot (B,) positions: a ragged in-flight batch from the
                # continuous-batching scheduler.  Counted so the serving
                # telemetry shows how much decode traffic is ragged.
                obs.count("registry.decode.ragged_pos")
            concrete = not isinstance(pos, jax.core.Tracer)
            # per-row (B,) positions bucket on the furthest row
            # (BucketPolicy.bucket_pos): every row's own mask still cuts
            # its prefix, shorter rows just mask more
            t_req = min(self.policy.bucket_pos(pos), t) if concrete else t
            args, kwargs, (bb, tb) = self.decode_request(
                b=b, h=h, hkv=hkv, t=t_req, d=d, dtype=str(q.dtype), bkv=bkv)
            kern = self.kernel("decode_attention", args, kwargs)
        except Exception as e:  # noqa: BLE001 — serving must not die
            self.stats.fallback("decode_attention", why=str(e))
            warnings.warn(f"plan registry: decode_attention fell back to "
                          f"the plain jnp path ({e})", stacklevel=2)
            return _decode_reference(q, k_cache, v_cache, pos)
        t_keep = min(tb, t)     # bucket ≥ pos+1, so no valid slot is cut
        # query head i reads KV head i // group: the kernel's (B, Hkv, group,
        # D) query and output are free reshapes of (B, H, D)
        qp = _pad_axes(q.reshape(b, hkv, h // hkv, d), {0: bb})
        kp = _pad_axes(k_cache[:, :, :t_keep], {0: bb, 2: tb})
        vp = _pad_axes(v_cache[:, :, :t_keep], {0: bb, 2: tb})
        pp = _pad_axes(_pos_vec(pos, b), {0: bb})
        try:
            out = kern({"q": qp, "k": kp, "v": vp, "pos": pp})["o"]
            out = out.reshape(bb, h, d)
        except Exception as e:  # noqa: BLE001 — exec failure: degrade a rung
            self.stats.fallback("decode_attention", why=f"exec: {e}")
            warnings.warn(f"plan registry: decode_attention kernel execution "
                          f"fell back to the plain jnp path ({e})",
                          stacklevel=2)
            return _decode_reference(q, k_cache, v_cache, pos)
        if bb == b:
            return out
        return out[:b]

    def ssd_decode(self, state, x, dt, A, B, C):
        """Kernelized single-token SSD state update.  state: (B, H, N, P)
        fp32; x: (B, H, P); dt: (B, H) (post-softplus); A: (H,); B/C:
        (B, G, N).  Returns (y, new_state).  Batch padding is exact: padded
        rows carry dt = 0 (identity state step) and are sliced away."""
        b, h, n, p = state.shape
        grp = B.shape[1]
        try:
            args, kwargs, (bb,) = self.ssd_decode_request(
                b=b, h=h, p=p, n=n, n_groups=grp, dtype=str(x.dtype))
            kern = self.kernel("ssd_decode", args, kwargs)
        except Exception as e:  # noqa: BLE001
            self.stats.fallback("ssd_decode", why=str(e))
            warnings.warn(f"plan registry: ssd_decode fell back to the "
                          f"plain jnp path ({e})", stacklevel=2)
            return _ssd_decode_reference(state, x, dt, A, B, C)
        try:
            out = kern({"state": _pad_axes(state, {0: bb}),
                        "x": _pad_axes(x, {0: bb}),
                        "dt": _pad_axes(dt, {0: bb}), "a": A,
                        "bmat": _pad_axes(B, {0: bb}),
                        "cmat": _pad_axes(C, {0: bb})})
        except Exception as e:  # noqa: BLE001 — exec failure: degrade a rung
            self.stats.fallback("ssd_decode", why=f"exec: {e}")
            warnings.warn(f"plan registry: ssd_decode kernel execution fell "
                          f"back to the plain jnp path ({e})", stacklevel=2)
            return _ssd_decode_reference(state, x, dt, A, B, C)
        y, st = out["y"], out["state_out"]
        if bb == b:
            return y, st
        return y[:b], st[:b]

    def grouped_gemm(self, x, w, *, group_sizes: Sequence[int],
                     bf: int = 128, bd: int = 128):
        """Bucketed ragged grouped GEMM.  x: (sum(group_sizes), D) rows
        grouped by expert; w: (E, D, F).  Empty groups emit no tiles."""
        sizes = [int(sz) for sz in group_sizes]
        e, d, f = w.shape
        try:
            args, kwargs, padded = self.grouped_request(
                e=e, d=d, f=f, group_sizes=sizes, dtype=str(x.dtype),
                bf=bf, bd=bd)
            from repro.kernels.ops import ragged_grouped_gemm_compiled
            return ragged_grouped_gemm_compiled(
                x, w, sizes, padded, kwargs["bc"], kwargs["bf"],
                kwargs["bd"],
                kernel_fn=lambda a, kw: self.kernel("grouped_gemm", a, kw,
                                                    pump=self.ragged_pump))
        except Exception as err:  # noqa: BLE001 — serving must not die
            self.stats.fallback("grouped_gemm", why=str(err))
            warnings.warn(f"plan registry: grouped_gemm fell back to "
                          f"per-group matmul ({err})", stacklevel=2)
            # compiler-free reference: one matmul per non-empty group
            outs, off = [], 0
            for ei, sz in enumerate(sizes):
                if sz:
                    outs.append(x[off:off + sz] @ w[ei])
                off += sz
            if not outs:
                return jnp.zeros((0, f), x.dtype)
            return outs[0] if len(outs) == 1 else jnp.concatenate(outs)

    # ----------------------------------------------------------- artifact --
    def preload_artifact(self, path) -> Dict[str, Any]:
        """Warm-start from a published plan artifact (:mod:`repro.tune`):
        verify each manifest entry, install the verified plans into this
        registry's backing store, and let the subsequent :meth:`warmup`
        *replay* them — zero autotune measurements on the replica.

        Degrades per entry, never whole-artifact: a ``corrupt`` (hash
        mismatch), ``stale`` (other jax build), ``missing`` (no manifest
        row) or ``invalid`` entry is rejected (``artifact.rejected``) and
        recorded in the store's quarantine ledger under ``<key>:artifact``
        — a suffix :func:`repro.compiler.compile` never gates on, so the
        local re-measure through the existing degradation ladder proceeds
        and only the artifact provenance is marked bad.  An unreadable or
        wrong-schema artifact degrades to an empty preload (full local
        warmup), counted ``artifact.load_failed``."""
        from repro.tune import artifact as artifact_mod
        report: Dict[str, Any] = {"path": str(path), "total": 0,
                                  "verified": 0, "rejected": 0,
                                  "missing": 0, "reasons": {}}
        try:
            doc = artifact_mod.load(path)
        except Exception as e:  # noqa: BLE001 — unreadable artifact:
            # the replica simply tunes locally, as if no artifact existed
            obs.count("artifact.load_failed", path=str(path),
                      error=type(e).__name__)
            report["error"] = repr(e)
            return report
        store = self._store()
        entries = doc["entries"]
        manifest = doc["manifest"]
        report["total"] = len(entries)
        report["missing"] = len(doc.get("missing", []))
        verified: Dict[str, dict] = {}
        for key, plan in entries.items():
            try:
                reason = artifact_mod.verify_entry(key, plan,
                                                   manifest.get(key))
            except Exception as e:  # noqa: BLE001 — injected/exotic
                # verification failure: treat as a rejected entry
                reason = f"verify-error:{type(e).__name__}"
            if reason is None:
                verified[key] = plan
                obs.count("artifact.verified", key=key)
            else:
                report["rejected"] += 1
                report["reasons"][reason] = \
                    report["reasons"].get(reason, 0) + 1
                obs.count("artifact.rejected", key=key, reason=reason)
                if store is not None:
                    store.record_failure(f"{key}:artifact",
                                         f"artifact:{reason}")
        report["verified"] = len(verified)
        if store is not None and verified:
            store.put_many(verified)
        return report

    # ------------------------------------------------------------- warmup --
    def warmup(self, requests) -> List[Dict[str, Any]]:
        """Pre-measure the bucket grid: ``requests`` is an iterable of
        ``(kernel, shape_kwargs)`` descriptors (see
        ``models.transformer.plan_requests``).  Returns one record per
        request: the chosen factor, whether the plan was freshly measured or
        replayed from the persistent cache, and the wall time paid."""
        canon = {"flash_attention": self.flash_request,
                 "ssd_scan": self.ssd_request,
                 "grouped_gemm": self.grouped_request,
                 "decode_attention": self.decode_request,
                 "ssd_decode": self.ssd_decode_request}
        requests = list(requests)
        report = []
        surfaced: List[str] = []
        failed = 0
        with obs.span("registry.warmup", cat="serve",
                      requests=len(requests)) as wspan:
            for kernel, spec in requests:
                t0 = time.perf_counter()
                # per-request isolation: one unplannable bucket (bad shape,
                # exhausted ladder, injected fault) yields a failure record,
                # not an aborted grid — warmup always returns a partial-but-
                # usable report and the surviving buckets still serve hits
                try:
                    args, kwargs, _pads = canon[kernel](**spec)
                    # ragged requests must warm under the same pump policy
                    # the serving wrapper will look them up with
                    pump = self.ragged_pump if kernel == "grouped_gemm" \
                        else None
                    kern = self.kernel(kernel, args, kwargs, pump=pump)
                except Exception as e:  # noqa: BLE001
                    failed += 1
                    obs.count("registry.warmup_failed", kernel=kernel,
                              error=type(e).__name__)
                    report.append({
                        "kernel": kernel, "args": list(spec.values()),
                        "factor": None, "measured": False, "replayed": False,
                        "time_s": round(time.perf_counter() - t0, 4),
                        "tiers": [], "degraded": [], "error": repr(e),
                    })
                    continue
                for msg in kern.report.warnings:
                    if msg not in surfaced:
                        surfaced.append(msg)
                tuned = kern.report.autotune or {}
                emission = kern.report.emission or {}
                # the winner's measured kernel time (µs) rides along —
                # fresh *and* replayed plans carry timings_us, so the
                # engine can seed the scheduler's step-time model from
                # real plan speed (Engine.measured_step_time_ms)
                winner_us = tuned.get("timings_us", {}).get(
                    str(tuned.get("winner")))
                rec = {
                    "kernel": kernel, "args": list(args),
                    "factor": kern.spec.factor,
                    "measured": tuned.get("policy") == "measure",
                    "replayed": bool(tuned.get("replayed")),
                    "winner_us": winner_us,
                    "time_s": round(time.perf_counter() - t0, 4),
                    # per-region emission tiers + the degradation reason
                    # strings, so a warmup record alone answers "did this
                    # bucket emit at the fast tier, and if not, why"
                    "tiers": sorted({v["tier"] for v in emission.values()}),
                    "degraded": sorted({w for v in emission.values()
                                        for w in v.get("why", [])}),
                }
                report.append(rec)
            wspan.set(failed=failed)
        # compile warnings are deduplicated across the whole sweep: the same
        # degradation note recurs for every bucket of a kernel, and launch
        # output should name each unique condition once, not once per compile
        for msg in surfaced:
            warnings.warn(f"plan warmup: {msg}", stacklevel=2)
        return report


def _probe_inputs(g) -> Dict[str, Any]:
    """Small deterministic non-zero operands for the plan spot-check: a
    fixed repeating pattern in [-0.75, 0.75] per external input memory
    (zeros would make the differential check vacuous; integer inputs —
    decode positions — land at 0, which is always a valid position)."""
    import numpy as np
    from repro.core.ir import NodeKind
    out = {}
    for n in g.nodes.values():
        if n.kind != NodeKind.MEMORY or g.in_edges(n.name):
            continue
        size = max(int(np.prod(n.shape)) if n.shape else 1, 1)
        vals = (((np.arange(size) % 7) - 3) / 4.0).reshape(n.shape or ())
        out[n.name] = vals.astype(n.dtype)
    return out


def _pad_axes(arr, targets: Dict[int, int]):
    """Zero-pad ``arr`` up to ``targets[axis]`` on each listed axis."""
    pads = [(0, 0)] * arr.ndim
    dirty = False
    for axis, tgt in targets.items():
        cur = arr.shape[axis]
        if tgt > cur:
            pads[axis] = (0, tgt - cur)
            dirty = True
    return jnp.pad(arr, pads) if dirty else arr


def _pos_vec(pos, b: int):
    """Normalize a scalar/per-row decode position into an int32 (b,)."""
    p = jnp.asarray(pos, jnp.int32)
    return jnp.broadcast_to(jnp.atleast_1d(p), (b,))


def _decode_reference(q, k_cache, v_cache, pos):
    """Plain-jnp decode attention (the registry's loud-failure fallback —
    the same math as ``models.attention.decode_attention``, inlined here to
    keep ``repro.compiler`` free of model-layer imports)."""
    b, h, d = q.shape
    hkv, t = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32) * (d ** -0.5)
    s = jnp.einsum("bkgd,bktd->bkgt", qg, k_cache.astype(jnp.float32))
    mask = jnp.arange(t)[None, :] <= _pos_vec(pos, b)[:, None]
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    import jax
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,bktd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, h, v_cache.shape[-1]).astype(q.dtype)


def _ssd_scan_reference(x, dt, A, B, C):
    """Sequential jnp SSD recurrence with the final state (fallback for the
    ``final_state=True`` registry route — the chunked dual form in the
    kernel computes exactly this per-timestep recurrence)."""
    import jax
    b, l, h, p = x.shape
    n = B.shape[-1]
    hpg = h // B.shape[2]
    Bh = jnp.repeat(B, hpg, axis=2).astype(jnp.float32)      # (b, l, h, n)
    Ch = jnp.repeat(C, hpg, axis=2).astype(jnp.float32)
    Af = A.astype(jnp.float32)

    def step(state, inp):
        xt, dtt, bt, ct = inp          # (b,h,p), (b,h), (b,h,n), (b,h,n)
        decay = jnp.exp(Af[None] * dtt)
        state = state * decay[..., None, None] \
            + (bt * dtt[..., None])[..., :, None] * xt[..., None, :]
        return state, jnp.einsum("bhn,bhnp->bhp", ct, state)

    init = jnp.zeros((b, h, n, p), jnp.float32)
    state, ys = jax.lax.scan(
        step, init,
        (jnp.moveaxis(x.astype(jnp.float32), 1, 0),
         jnp.moveaxis(dt.astype(jnp.float32), 1, 0),
         jnp.moveaxis(Bh, 1, 0), jnp.moveaxis(Ch, 1, 0)))
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), state


def _ssd_decode_reference(state, x, dt, A, B, C):
    """Plain-jnp single-token SSD step (fallback / differential reference)."""
    h = x.shape[1]
    hpg = h // B.shape[1]
    Bh = jnp.repeat(B, hpg, axis=1).astype(jnp.float32)
    Ch = jnp.repeat(C, hpg, axis=1).astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    st = state.astype(jnp.float32)
    decay = jnp.exp(A.astype(jnp.float32)[None] * dtf)
    st2 = st * decay[..., None, None] \
        + (Bh * dtf[..., None])[..., :, None] \
        * x.astype(jnp.float32)[..., None, :]
    y = jnp.einsum("bhn,bhnp->bhp", Ch, st2)
    return y.astype(x.dtype), st2


# --------------------------------------------------------------- singleton --
_DEFAULT: Optional[PlanRegistry] = None

# publish the *active* default registry's stats into every metrics snapshot
# (a view, not a copy: RegistryStats stays the single implementation and the
# snapshot always reflects whichever instance is currently installed)
obs.register_view(
    "plan_registry",
    lambda: _DEFAULT.stats.as_dict() if _DEFAULT is not None else None)


def default_registry() -> PlanRegistry:
    """Process-wide registry the model layers share."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = PlanRegistry()
    return _DEFAULT


def set_default_registry(reg: Optional[PlanRegistry]) -> Optional[PlanRegistry]:
    """Swap the process-wide registry (tests/benchmarks); returns the old."""
    global _DEFAULT
    old, _DEFAULT = _DEFAULT, reg
    return old
