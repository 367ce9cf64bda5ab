"""``repro.compiler`` — pass pipeline, lowering backends, persistent cache.

The back half of the paper's §3 workflow: where ``repro.core`` defines the
IR and the two rewrite rules, this package *drives* them as registered passes
(:mod:`.passes`, :mod:`.pipeline`), compiles the transformed graph to an
executable jax callable (per-node :mod:`.lowering` or the fused-region
Pallas emission in :mod:`.pallas_backend`), and memoizes both the autotune
decision and the compiled kernel across calls and processes (:mod:`.cache`).

    from repro import compiler
    kern = compiler.compile(graph, factor=2, mode="T", backend="pallas")
    out = kern({"x": x, "y": y})          # == repro.core.executor.run(...)
    kern.report.summary()                 # pass provenance + cache state

``compile`` is served in O(1) for repeated requests: an in-process memo
returns the compiled kernel outright, and the JSON disk cache replays the
pipeline plan (chosen pump factor — including a measured-runtime autotune
winner from ``autotune='measure'``) in fresh processes.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from typing import Dict, Optional, Tuple

import numpy as np

from repro import obs
from repro.core.ir import Graph, NodeKind, PumpSpec
from repro.core.pump_plan import VMEM_BYTES, plan_kernel_pump
from repro.testing import faults

from .cache import (CompileCache, QuarantinePolicy, default_cache,
                    graph_fingerprint, request_key)
from .lowering import CompiledKernel, LoweringError, lower
from .pallas_backend import lower_pallas, partition_regions
from .passes import (PASS_REGISTRY, FifoDepthPass, FusionReport, GraphPass,
                     MultipumpPass, StreamFusionPass, StreamingPass,
                     make_pass, register_pass)
from .pipeline import PassRecord, Pipeline, PipelineReport
from .registry import (BucketPolicy, PlanRegistry, default_registry,
                       set_default_registry)

# The formal degradation ladder (docs/robustness.md).  The first three rungs
# are emission tiers *inside* the pallas backend — lower_pallas already picks
# per region and falls through pallas → blockloop/carryloop → gather when a
# region can't be planned.  The cross-layer rungs are what this module and
# the plan registry own: a pallas-backend failure degrades to the per-node
# jax lowering (compile_degraded), and a jax failure degrades to the plain-
# jnp direct functions the registry wrappers / engine carry.  Every step
# down is counted (``degrade.compile`` / ``registry.fallback`` /
# ``engine.degraded``) with the reason, never silent.
DEGRADATION_LADDER = ("pallas", "blockloop", "gather", "jax", "direct")


class PlanQuarantined(RuntimeError):
    """Raised by :func:`compile` when the request's plan key is inside its
    quarantine backoff window — the caller must degrade a rung instead of
    re-paying a known-bad compile."""

    def __init__(self, msg: str, *, qkey: str = "", entry: dict = None):
        super().__init__(msg)
        self.qkey = qkey
        self.entry = entry or {}


class AutotuneError(RuntimeError):
    """Every autotune candidate failed to build or measure."""

    def __init__(self, msg: str, *, failures: dict = None):
        super().__init__(msg)
        self.failures = failures or {}


# memo value: (kernel, plan) — the plan is re-used to write-through to a
# caller-supplied persistent cache that hasn't seen this request yet
_KERNEL_MEMO: Dict[Tuple, Tuple[CompiledKernel, dict]] = {}
_MEMO_HITS: Dict[Tuple, int] = {}


def clear_memo() -> None:
    """Drop all in-process compiled kernels (test isolation hook)."""
    _KERNEL_MEMO.clear()
    _MEMO_HITS.clear()


def forget(cache_key: str) -> int:
    """Purge every in-process memo entry compiled under ``cache_key`` (all
    backends).  The memo is populated *before* post-compile validation can
    run — a kernel that later flunks the registry's spot-check must not be
    memo-served on the retry, so validation failures call this."""
    stale = [mk for mk in _KERNEL_MEMO if mk[0] == cache_key]
    for mk in stale:
        _KERNEL_MEMO.pop(mk, None)
        _MEMO_HITS.pop(mk, None)
    return len(stale)


def _cell_sig(value) -> str:
    """Value-identifying signature of one closure cell.  repr() is not
    value-identifying for large arrays (elided middle), so array buffers are
    hashed.  Everything else falls back to repr: reprs that embed the object
    id (the common case for callables) miss safely across rebuilds; a custom
    object with a value-blind repr could still alias — documented limit."""
    tobytes = getattr(value, "tobytes", None)
    if callable(tobytes):
        h = hashlib.sha256(tobytes()).hexdigest()[:16]
        return f"<array {getattr(value, 'shape', ())} " \
               f"{getattr(value, 'dtype', '?')} {h}>"
    return repr(value)


def _fn_signature(g: Graph) -> Tuple:
    """Behavioral identity of compute bodies — structural fingerprints ignore
    fn objects, so the in-process memo adds this to avoid serving a kernel
    whose graph matches structurally but computes something else.  Covers the
    code location *and* the captured state (closure cells, defaults): two
    instantiations of the same lambda with different captured values must not
    collide.  A repr that isn't value-identifying only causes a safe memo
    miss."""
    sig = []
    for c in sorted(g.computes(), key=lambda n: n.name):
        carry = c.meta.get("carry")
        fns = [("fn", c.fn), ("tile_fn", c.meta.get("tile_fn"))]
        if carry is not None:
            sig.append((c.name, "carry", carry.signature()))
            fns += [("carry_step", carry.step_fn),
                    ("carry_final", carry.final_fn)]
        for label, fn in fns:
            if fn is None:
                sig.append((c.name, label, None))
                continue
            code = getattr(fn, "__code__", None)
            try:
                cells = tuple(
                    _cell_sig(cell.cell_contents)
                    for cell in getattr(fn, "__closure__", None) or ())
            except ValueError:  # unresolved cell: fall back to object id
                cells = (f"<cell id={id(fn)}>",)
            sig.append((c.name, label, getattr(fn, "__module__", ""),
                        getattr(fn, "__qualname__", repr(fn)),
                        getattr(code, "co_firstlineno", -1),
                        repr(getattr(fn, "__defaults__", None)), cells))
    return tuple(sig)


def _estimate_sig(estimate) -> Optional[Tuple]:
    if estimate is None:
        return None
    return (estimate.block_bytes_in, estimate.block_bytes_out,
            estimate.flops_per_block, estimate.fixed_overhead_s)


def measure_request_key(graph: Graph, estimate=None, *, factor="auto",
                        mode: str = "T", autotune="measure") -> str:
    """The persistent-cache key :func:`compile` assigns this request under
    the plan registry's measured-autotune path (``factor='auto'``,
    ``autotune='measure'``, default budgets).  The offline tuner
    (:mod:`repro.tune`) uses it to enumerate and dedupe work, and to key
    published artifact entries so a replica's replay compile hits them
    without re-deriving anything."""
    return request_key(graph, factor=factor, mode=mode,
                       vmem_budget=VMEM_BYTES, max_factor=16,
                       estimate=_estimate_sig(estimate), autotune=autotune)


def _valid_plan(plan) -> bool:
    """A usable cached plan must at least replay an integer pump factor —
    anything else (truncated write, hand-edited JSON, schema drift) is
    treated as a miss so a corrupted cache degrades to a cold compile
    instead of crashing the build."""
    if not isinstance(plan, dict):
        return False
    try:
        int(plan["factor"])
    except (KeyError, TypeError, ValueError):
        return False
    return True


AUTOTUNE_CANDIDATES = (1, 2, 4, 8)
# relative runtime band within which measured candidates count as tied
AUTOTUNE_TIE_BAND = 0.05


def _build(graph: Graph, *, factor, mode, vmem_budget, max_factor, estimate,
           backend, jit, pallas_mode) -> CompiledKernel:
    """One pipeline run + lowering (no caching layers)."""
    pipe = Pipeline.default(factor=factor, mode=mode,
                            vmem_budget=vmem_budget, max_factor=max_factor,
                            estimate=estimate)
    out_graph, report = pipe.run(graph)
    spec = PumpSpec(factor=report.factor, mode=mode, vmem_budget=vmem_budget)

    warn = report.warn
    fn = None
    if backend == "jax":
        fn = lower(out_graph, jit=jit, warn=warn)
    elif backend == "pallas":
        report.emission = {}
        fn = lower_pallas(out_graph, jit=jit, pallas_mode=pallas_mode,
                          warn=warn, emission=report.emission)
    elif backend == "reference":
        from repro.core import executor

        def fn(inputs, _g=out_graph):
            return executor.run(_g, dict(inputs))

    return CompiledKernel(graph=out_graph, spec=spec, report=report, fn=fn,
                          backend=backend)


def _trace_state_clean() -> bool:
    """True when no jax trace is active.  Measured autotune must not run
    inside a trace: the candidate executions there are re-traced per call
    (orders of magnitude slower) and the recorded timings are meaningless,
    yet would be persisted as a cross-process plan."""
    from jax import core
    return core.trace_ctx.is_top_level()


def _measure_inputs(graph: Graph) -> Dict[str, np.ndarray]:
    """Synthetic operands for autotune timing: zeros for every memory that
    nothing in the graph writes (the external inputs)."""
    return {n.name: np.zeros(n.shape, dtype=n.dtype)
            for n in graph.nodes.values()
            if n.kind == NodeKind.MEMORY and not graph.in_edges(n.name)}


# wall-clock budget for measuring ONE autotune candidate (compile + repeats).
# A candidate that blows through it keeps whatever timings it banked so far —
# a slow-but-finite candidate still competes; the budget bounds warmup tail
# latency, it does not disqualify.
AUTOTUNE_CANDIDATE_BUDGET_S = 10.0


def _time_kernel(fn, inputs, repeats: int = 5,
                 budget_s: Optional[float] = None) -> float:
    """Best-of-N wall time in µs (first call compiles and is discarded).
    Five repeats: the candidate factors on the carry kernels sit within a
    few percent of each other on CPU, and best-of-3 let scheduler noise
    flip the persisted winner between otherwise identical processes.
    ``budget_s`` caps the total wall clock spent here; once exceeded the
    best timing banked so far is returned early (at least one timed run
    always happens)."""
    import jax
    t_start = time.perf_counter()
    jax.block_until_ready(fn(inputs))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(inputs))
        best = min(best, (time.perf_counter() - t0) * 1e6)
        if budget_s is not None and time.perf_counter() - t_start > budget_s:
            obs.count("compile.measure_budget_hit")
            break
    return best


def compile(graph: Graph, *, factor="auto", mode: str = "T",
            vmem_budget: int = VMEM_BYTES, max_factor: int = 16,
            estimate=None, backend: str = "jax", jit: bool = True,
            pallas_mode: str = "auto", autotune=None,
            cache=None, memoize: bool = True) -> CompiledKernel:
    """Run the pass pipeline on ``graph`` and lower the result.

    ``factor`` is an explicit pump factor M (1 = stream-only) or ``'auto'``
    to let the multipump pass autotune it (from ``estimate`` when given).
    ``backend`` is ``'jax'`` (per-node jit lowering), ``'pallas'`` (fused-
    region Pallas emission; see :mod:`.pallas_backend` and ``pallas_mode``),
    ``'reference'`` (numpy executor, the differential-testing oracle) or
    ``'none'`` (plan only).  ``autotune='measure'`` times the candidate pump
    factors ``{1, 2, 4, 8}`` on the lowered executable and keeps the winner;
    the measured plan persists in the cache, so a repeat compile replays it
    without re-measuring.  ``cache`` is a :class:`CompileCache`, ``None``
    for the default persistent cache, or ``False`` to disable disk caching;
    ``memoize=False`` also bypasses the in-process kernel memo.
    """
    if backend not in ("jax", "pallas", "reference", "none"):
        raise ValueError(f"unknown backend {backend!r}")
    if autotune not in (None, "measure"):
        raise ValueError(f"unknown autotune policy {autotune!r}")
    if autotune == "measure" and backend not in ("jax", "pallas"):
        raise ValueError("autotune='measure' needs an executable backend "
                         "('jax' or 'pallas')")
    if cache is None:
        cache = default_cache()
    elif cache is False:
        cache = None

    # the plan (chosen factor) is backend/jit-independent, so those stay out
    # of the persistent key — autopump's backend='none' plans are reused by
    # jax-backend compiles of the same graph; the memo key adds them because
    # the memoized artifact (the compiled callable) is backend-specific.
    # autotune IS part of the key: a measured winner and a capacity-model
    # guess for the same request must not collide.
    key = request_key(graph, factor=factor, mode=mode,
                      vmem_budget=vmem_budget, max_factor=max_factor,
                      estimate=_estimate_sig(estimate), autotune=autotune)
    if cache is not None:
        # quarantine gate: a (plan, backend) pair that recently failed
        # compile or validation is not retried inside its backoff window —
        # the caller degrades a rung instead (compile_degraded does this
        # automatically).  The backend is part of the quarantine key because
        # a NaN pallas kernel does not indict the jax lowering of the same
        # plan.
        qkey = f"{key}:{backend}"
        q = cache.quarantined(qkey)
        if q is not None:
            obs.count("cache.quarantine_skip", graph=graph.name,
                      backend=backend, reason=q.get("reason", ""))
            raise PlanQuarantined(
                f"plan {key[:12]}… backend={backend} is quarantined "
                f"({q.get('reason', 'unknown')}, fail #{q.get('fails', 0)}) — "
                f"backoff window open", qkey=qkey, entry=q)
    memo_key = (key, backend, jit, pallas_mode, _fn_signature(graph))
    if memoize and memo_key in _KERNEL_MEMO:
        kern, plan = _KERNEL_MEMO[memo_key]
        if cache is not None and key not in cache:
            cache.put(key, plan)   # write-through to a fresh persistent cache
        _MEMO_HITS[memo_key] = _MEMO_HITS.get(memo_key, 0) + 1
        obs.count("compile.memo_hit", graph=graph.name, backend=backend)
        # fresh report view per hit: the original compile's provenance
        # record must not be rewritten retroactively
        report = dataclasses.replace(kern.report, served_from="memory",
                                     cache_hits=_MEMO_HITS[memo_key])
        return dataclasses.replace(kern, report=report)
    with obs.span("compiler.compile", cat="compile", graph=graph.name,
                  backend=backend, autotune=autotune or "none",
                  factor=str(factor), mode=mode) as _cspan:
        try:
            return _compile_cold(graph, factor=factor, mode=mode,
                                 vmem_budget=vmem_budget,
                                 max_factor=max_factor,
                                 estimate=estimate, backend=backend, jit=jit,
                                 pallas_mode=pallas_mode, autotune=autotune,
                                 cache=cache, memoize=memoize, key=key,
                                 memo_key=memo_key, cspan=_cspan)
        except Exception as e:
            # stamp the request identity so degradation handlers can
            # quarantine / forget the exact failing plan without recomputing
            # the key (best-effort: some exotic exceptions reject attrs)
            try:
                e.compile_cache_key = key
                e.compile_backend = backend
            except Exception:
                pass
            raise


def _compile_cold(graph: Graph, *, factor, mode, vmem_budget, max_factor,
                  estimate, backend, jit, pallas_mode, autotune, cache,
                  memoize, key, memo_key, cspan) -> CompiledKernel:
    """The non-memo-hit path of :func:`compile` (span-bracketed)."""

    build = lambda f: _build(graph, factor=f, mode=mode,   # noqa: E731
                             vmem_budget=vmem_budget, max_factor=max_factor,
                             estimate=estimate, backend=backend, jit=jit,
                             pallas_mode=pallas_mode)

    persist = True
    plan = cache.get(key) if cache is not None else None
    if plan is not None and not _valid_plan(plan):
        obs.count("cache.corrupt", key=key, graph=graph.name)
        plan = None         # corrupted entry: fall back to a cold compile
    if plan is not None:
        # replay the cached decision: no autotune search, no factor probing,
        # no re-measurement
        obs.count("compile.replay", graph=graph.name, backend=backend,
                  factor=int(plan["factor"]))
        kern = _build(graph, factor=int(plan["factor"]), mode=mode,
                      vmem_budget=vmem_budget, max_factor=max_factor,
                      estimate=None, backend=backend, jit=jit,
                      pallas_mode=pallas_mode)
        served = "disk"
        if plan.get("autotune"):
            kern.report.autotune = dict(plan["autotune"], replayed=True)
    elif autotune == "measure" and not _trace_state_clean():
        # replaying a measured plan under a trace is fine (no timing runs,
        # handled above); *measuring* is not — compile with the requested
        # factor policy instead, and do NOT persist or memoize the result
        # under the measure key, so an eager context (registry warmup) can
        # still produce the real measured plan later
        obs.count("compile.measure_in_trace", graph=graph.name)
        kern = build(factor)
        served = None
        persist = False
        kern.report.warn(
            "autotune='measure' requested inside an active jax trace: "
            "in-trace timings are meaningless — compiled without "
            "measurement; measure from an eager context (e.g. plan-registry "
            "warmup) to persist a real measured plan")
    elif autotune == "measure":
        obs.count("compile.measure", graph=graph.name, backend=backend)
        inputs = _measure_inputs(graph)
        timings: Dict[int, float] = {}
        kernels: Dict[int, CompiledKernel] = {}
        failures: Dict[int, str] = {}
        with obs.span("compiler.autotune", cat="compile", graph=graph.name,
                      backend=backend) as aspan:
            for cand in AUTOTUNE_CANDIDATES:
                if cand > max_factor:
                    continue
                with obs.span("compiler.autotune.candidate", cat="compile",
                              graph=graph.name, factor=cand) as csp:
                    # one candidate failing (bad lowering at that factor, a
                    # measurement timeout) must not sink the search — the
                    # surviving candidates still yield a valid winner
                    try:
                        faults.check("compile.measure", graph=graph.name,
                                     factor=cand)
                        k = build(cand)
                        achieved = k.spec.factor  # legality may clamp it
                        if achieved in timings:
                            csp.set(achieved=achieved, skipped="duplicate")
                            continue
                        t = _time_kernel(k.fn, inputs,
                                         budget_s=AUTOTUNE_CANDIDATE_BUDGET_S)
                        kernels[achieved] = k
                        timings[achieved] = t
                        csp.set(achieved=achieved, best_us=round(t, 1))
                    except Exception as e:
                        failures[cand] = repr(e)
                        obs.count("compile.measure_failed", graph=graph.name,
                                  factor=str(cand), error=type(e).__name__)
                        csp.set(failed=type(e).__name__)
            aspan.set(failed_candidates=len(failures))
        if not timings:
            raise AutotuneError(
                f"autotune='measure' on {graph.name!r}: every candidate "
                f"failed — {failures}", failures=failures)
        # statistical ties go to the smallest factor: candidates within the
        # noise band of the best are indistinguishable by measurement, and
        # persisting an arbitrary exotic winner costs VMEM/beats for nothing
        # (and flips between otherwise identical processes).  Genuine
        # multi-pump wins exceed the band and are kept.
        best_t = min(timings.values())
        winner = min(f for f, t in timings.items()
                     if t <= best_t * (1.0 + AUTOTUNE_TIE_BAND))
        kern = kernels[winner]
        served = None
        kern.report.autotune = {
            "policy": "measure", "winner": winner, "backend": backend,
            "timings_us": {str(f): round(t, 1) for f, t in timings.items()},
            "replayed": False,
        }
        if failures:
            kern.report.autotune["failed"] = {str(f): err for f, err
                                              in failures.items()}
            kern.report.warn(
                f"autotune: {len(failures)} candidate(s) failed "
                f"measurement and were excluded from the search")
    else:
        obs.count("compile.build", graph=graph.name, backend=backend)
        kern = build(factor)
        served = None

    report = kern.report
    report.cache_key = key
    report.served_from = served
    report.cache_hits = 1 if served else 0
    cspan.set(served=served or "build", achieved_factor=kern.spec.factor)

    if plan is None:
        plan = {"factor": kern.spec.factor, "mode": mode,
                "graph": graph.name,
                "passes": [[r.name, r.applied] for r in report.records]}
        if report.autotune:
            plan["autotune"] = {k: v for k, v in report.autotune.items()
                                if k != "replayed"}
        if cache is not None and persist:
            cache.put(key, plan)
    if memoize and persist:
        _KERNEL_MEMO[memo_key] = (kern, plan)
    return kern


def compile_degraded(graph: Graph, *, backend: str = "pallas",
                     autotune=None, cache=None,
                     **kw) -> CompiledKernel:
    """:func:`compile`, walking the cross-backend rungs of
    :data:`DEGRADATION_LADDER` instead of raising.

    Tries the requested backend first; on failure (or an open quarantine
    window) records the failing rung in the quarantine ledger, counts
    ``degrade.compile`` with the reason, and steps down: pallas → per-node
    jax lowering → jax without measured autotune.  The intra-pallas tiers
    (blockloop/gather) degrade inside :func:`~.pallas_backend.lower_pallas`
    before any of this triggers.  Raises only when every rung fails — the
    caller's last rung (the registry wrappers' / engine's plain-jnp direct
    functions) is below this function.
    """
    store = default_cache() if cache is None else (cache or None)
    rungs = [(backend, autotune)]
    if backend != "jax":
        rungs.append(("jax", autotune))
    if autotune is not None:
        rungs.append(("jax", None))
    last = None
    degraded_from = None
    for b, at in rungs:
        try:
            kern = compile(graph, backend=b, autotune=at, cache=cache, **kw)
        except PlanQuarantined as e:
            # already quarantined — skip the rung without re-recording
            last = e
            degraded_from = (b, "quarantined")
            obs.count("degrade.compile", graph=graph.name, frm=b,
                      reason="quarantined")
            continue
        except Exception as e:
            last = e
            reason = type(e).__name__
            degraded_from = (b, reason)
            obs.count("degrade.compile", graph=graph.name, frm=b,
                      reason=reason)
            qkey = getattr(e, "compile_cache_key", None)
            if store is not None and qkey:
                store.record_failure(f"{qkey}:{b}", reason)
            continue
        if degraded_from is not None:
            frm, why = degraded_from
            kern.report.warn(
                f"degraded compile: backend={frm} failed ({why}); "
                f"served by backend={b}"
                + ("" if at == autotune else " without measured autotune"))
        return kern
    raise last


def plan_pump(block_bytes_in: int, block_bytes_out: int,
              flops_per_block: float, mode: str = "T", max_factor: int = 16,
              vmem_budget: int = VMEM_BYTES, axis: int = 0,
              cache=None) -> PumpSpec:
    """Persistently-cached pump-factor planning for the kernel layer.

    Same contract as :func:`repro.core.pump_plan.plan_kernel_pump`, but the
    chosen factor is stored in the compile cache so every benchmark/serve
    process after the first skips the capacity-model search.
    """
    if cache is None:
        cache = default_cache()
    elif cache is False:
        cache = None
    key = None
    if cache is not None:
        import hashlib
        import json
        key = "pump:" + hashlib.sha256(json.dumps(
            [block_bytes_in, block_bytes_out, flops_per_block, mode,
             max_factor, vmem_budget, axis], sort_keys=True).encode()
        ).hexdigest()
        entry = cache.get(key)
        if entry is not None:
            return PumpSpec(factor=int(entry["factor"]), mode=mode, axis=axis,
                            vmem_budget=vmem_budget)
    spec = plan_kernel_pump(block_bytes_in, block_bytes_out, flops_per_block,
                            mode=mode, max_factor=max_factor,
                            vmem_budget=vmem_budget, axis=axis)
    if cache is not None:
        cache.put(key, {"factor": spec.factor})
    return spec


__all__ = [
    "compile", "compile_degraded", "plan_pump", "clear_memo", "forget",
    "AUTOTUNE_CANDIDATES", "AUTOTUNE_CANDIDATE_BUDGET_S",
    "DEGRADATION_LADDER", "PlanQuarantined", "AutotuneError",
    "Pipeline", "PipelineReport", "PassRecord",
    "GraphPass", "PASS_REGISTRY", "register_pass", "make_pass",
    "StreamingPass", "StreamFusionPass", "MultipumpPass", "FifoDepthPass",
    "FusionReport",
    "CompileCache", "QuarantinePolicy", "default_cache",
    "graph_fingerprint", "request_key", "measure_request_key",
    "CompiledKernel", "LoweringError", "lower",
    "lower_pallas", "partition_regions",
    "BucketPolicy", "PlanRegistry", "default_registry",
    "set_default_registry",
]
