"""Serving engine: batched prefill + decode over measured execution plans.

Continuous-batching-lite: a request pool is packed into fixed (batch,
max_len) slots; prefill fills each slot's cache, then decode steps advance
all active slots together.  Kernel-scale temporal vectorization shows up in
the attention path (chunked/pumped KV reads); engine-scale, the decode loop
is the fast domain and cache DMA the slow one.

Two serving-time disciplines live here:

* **Plan warmup** — when the model routes kernels through the plan registry
  (``cfg.kernel_plan == 'measure'``), the engine pre-measures the bucket
  grid at construction (:meth:`Engine.warmup`), so the first real token hits
  a warm measured plan instead of paying an autotune search mid-request.
* **Timing separation** — prefill/decode run through
  :class:`repro.launch.steps.StepTimer`: the first call of each phase
  (tracing + XLA compile + any cold plan measurement) is recorded as compile
  time, steady-state step time accumulates separately, and
  :meth:`Engine.stats` reports both — warmup cost never pollutes the
  steady-state numbers.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ModelConfig
from repro.launch import mesh as mesh_mod
from repro.launch.steps import StepTimer
from repro.models import model as model_mod
from repro.testing import faults


@dataclasses.dataclass
class ServeConfig:
    batch: int = 4
    max_len: int = 256
    temperature: float = 0.0      # 0 = greedy
    seed: int = 0
    cache_dtype: str = "float32"
    # pre-measure the plan-registry bucket grid at engine construction
    # (no-op when the model's kernel paths don't route through the registry)
    warmup: bool = True
    # override cfg.kernel_plan for this engine ('measure' | 'direct' | None)
    kernel_plan: Optional[str] = None
    # path to a published plan artifact (repro.tune): warmup verifies and
    # installs its entries first, so every artifact-covered bucket replays
    # with zero autotune measurements (docs/robustness.md "Artifact
    # lifecycle").  None = tune locally at warmup, the classic path.
    plan_artifact: Optional[str] = None
    # host-side non-finite check on each step's logits, degrading the step
    # to the plain-jnp fallback instead of emitting garbage tokens.  Costs a
    # device sync per token, so it is opt-in; chaos runs get it implicitly
    # whenever fault rules are installed.
    nan_guard: bool = False


def _jit_step(name: str, cfg: ModelConfig):
    """``(params, cache, batch) -> (logits, cache)`` for ``cfg``, jitted
    under ``name``: the compiled module (``jit_<name>``) and its ops carry
    that name in a profiler trace."""
    def step(params, cache, batch):
        return model_mod.decode_step(cfg, params, batch, cache)
    step.__name__ = step.__qualname__ = name
    return jax.jit(step)


class Engine:
    def __init__(self, cfg: ModelConfig, params, scfg: ServeConfig,
                 mesh=None):
        if scfg.kernel_plan and scfg.kernel_plan != cfg.kernel_plan:
            cfg = dataclasses.replace(cfg, kernel_plan=scfg.kernel_plan)
        if not cfg.fresh_prefill_kernel:
            # this engine's prefill always builds a fresh cache (pos == 0),
            # which is exactly the contract the flag requires — enable the
            # kernel prefill route so serving hits the measured plans
            cfg = dataclasses.replace(cfg, fresh_prefill_kernel=True)
        self.cfg, self.params, self.scfg = cfg, params, scfg
        self.mesh = mesh or mesh_mod.make_host_mesh()
        cdt = jnp.dtype(scfg.cache_dtype)
        # one compiled step serves prefill (s > 1) and decode (s == 1)
        self._model_step = _jit_step("model_step", cfg)
        self._cache_factory = lambda batch=None: model_mod.init_cache(
            cfg, batch or scfg.batch, scfg.max_len, cdt)
        # the bottom rung of the degradation ladder: a fully compiler-free
        # config (plain-jnp attention/ssm, no plan registry) the engine can
        # re-run any failing step through.  Built lazily — fault-free
        # serving never pays the extra trace/compile.
        self._direct_cfg = dataclasses.replace(
            cfg, kernel_plan="direct", attention_impl="xla_chunked",
            ssm_impl="xla")
        # continuation prefill (chunked prefill / preemption resume): same
        # model, but s > 1 steps into a cache already holding pos > 0
        # tokens — attention must mask over the whole written prefix and
        # the SSM path seeds from cached state, so the flash fresh-prefill
        # route is off and prefill_continuation on.  Traced lazily: plain
        # whole-prompt serving never pays the extra compile.
        self._cont_cfg = dataclasses.replace(
            cfg, prefill_continuation=True, fresh_prefill_kernel=False)
        self._cont_fn: Optional[Any] = None
        self._fallback_fn: Optional[Any] = None
        self._fallback_cont_fn: Optional[Any] = None
        self.degraded_requests = 0
        self._req_degraded = False
        self.timer = StepTimer()
        self.warmup_s = 0.0
        self.warmup_report: List[Dict[str, Any]] = []
        self.artifact_report: Optional[Dict[str, Any]] = None
        # capture the registry once: stats()/warmup() must keep talking to
        # the instance this engine's model layers were warmed against, even
        # if the process default is swapped later (tests/benchmarks do)
        self._reg = None
        if cfg.kernel_plan == "measure":
            from repro.compiler.registry import default_registry
            self._reg = default_registry()
        # publish this engine's timing stats into the unified metrics
        # snapshot (a view over StepTimer, not a copy; the most recently
        # constructed engine owns the slot)
        obs.register_view("serve.engine", self.stats)
        obs.watch_compiles()
        # resolved once: the decode loop records per-token latency straight
        # into the histogram object, skipping the name lookup per step
        self._step_hist = obs.default_metrics().histogram(
            "serve.decode_step_s")
        if scfg.warmup:
            self.warmup()

    # ----------------------------------------------------------- warmup ----
    def _registry(self):
        return self._reg

    def warmup(self) -> List[Dict[str, Any]]:
        """Pre-measure the plan-registry bucket grid for this model/shape.

        Enumerates ``models.transformer.plan_requests`` (one request per
        kernel × sequence bucket up to ``max_len``) and compiles each through
        the registry — cold requests pay the measured autotune here, at
        launch; repeat processes replay winners from the persistent compile
        cache.  Time spent is reported as ``warmup_s``, never as step time.
        """
        reg = self._registry()
        if reg is None:
            return []
        from repro.models import transformer
        leaves = jax.tree.leaves(self.params)
        dtype = str(jnp.result_type(leaves[0].dtype if leaves
                                    else jnp.float32,
                                    self.cfg.activation_dtype))
        t0 = time.perf_counter()
        with obs.span("serve.warmup", cat="serve", batch=self.scfg.batch,
                      max_len=self.scfg.max_len) as sp:
            if self.scfg.plan_artifact:
                # warm start: verified artifact entries land in the plan
                # store first, so the grid below *replays* them — zero
                # measurements for every verified bucket; rejected/missing
                # entries fall through to the local measured path
                self.artifact_report = reg.preload_artifact(
                    self.scfg.plan_artifact)
                sp.set(artifact_verified=self.artifact_report["verified"],
                       artifact_rejected=self.artifact_report["rejected"])
            # cached=True: only the plans this cached serving loop can execute
            reqs = transformer.plan_requests(self.cfg, self.scfg.batch,
                                             self.scfg.max_len, dtype=dtype,
                                             cached=True)
            self.warmup_report = reg.warmup(reqs)
            # warmup is per-request isolated (PlanRegistry.warmup): a failed
            # bucket is a record with an "error" string, not an abort — and
            # the span says how many so launch telemetry shows partial warmup
            sp.set(plans=len(self.warmup_report),
                   failed=sum(1 for r in self.warmup_report if "error" in r))
        self.warmup_s += time.perf_counter() - t0
        return self.warmup_report

    # ------------------------------------------------- step-time estimate --
    def measured_step_time_ms(self) -> Optional[float]:
        """Measured decode-step estimate (ms) for the scheduler's virtual
        clock, or None when nothing has been measured yet.

        Preference order: the p50 of real decode steps this engine has
        already served (the ``serve.decode_step_s`` histogram — at least
        three samples, so one cold compile outlier cannot be the estimate),
        else a floor estimate from the measured plan timings the warmup /
        artifact carried (winner kernel time per decode kernel × the layer
        count that runs it).  The plan-derived floor excludes XLA glue
        around the kernels, so it under-estimates — still far closer to
        real plan speed than a constant, which is the point: deadline-aware
        shedding should reflect what the measured plans can actually do."""
        if self._step_hist.count >= 3:
            p50 = self._step_hist.percentile(50)
            if p50:
                return p50 * 1e3
        # plan-derived floor: worst (largest-bucket) winner per decode
        # kernel, scaled by how many layers run it
        best: Dict[str, float] = {}
        for rec in self.warmup_report:
            us = rec.get("winner_us")
            kern = rec.get("kernel")
            if us and kern in ("decode_attention", "ssd_decode"):
                best[kern] = max(best.get(kern, 0.0), float(us))
        if not best:
            return None
        cfg = self.cfg
        if cfg.family == "hybrid" and cfg.hybrid_attn_every:
            n_attn = cfg.n_layers // cfg.hybrid_attn_every
        elif cfg.family == "ssm":
            n_attn = 0
        else:
            n_attn = cfg.n_layers
        n_ssm = cfg.n_layers - n_attn if cfg.family in ("ssm", "hybrid") \
            else 0
        ms = (best.get("decode_attention", 0.0) * n_attn
              + best.get("ssd_decode", 0.0) * n_ssm) / 1e3
        return ms or None

    # ------------------------------------------------------------ serving --
    def _fallback(self):
        """The plain-jnp bottom-rung step fn (lazily traced/compiled)."""
        if self._fallback_fn is None:
            obs.count("engine.fallback_build")
            self._fallback_fn = _jit_step("fallback_step", self._direct_cfg)
        return self._fallback_fn

    def _cont(self):
        """Continuation-prefill step fn (lazily traced/compiled)."""
        if self._cont_fn is None:
            self._cont_fn = _jit_step("continuation_step", self._cont_cfg)
        return self._cont_fn

    def _fallback_cont(self):
        """Bottom-rung continuation prefill: plain-jnp paths with the
        continuation masking/state-seeding kept on."""
        if self._fallback_cont_fn is None:
            obs.count("engine.fallback_build", phase="prefill_chunk")
            cfg = dataclasses.replace(
                self._direct_cfg, prefill_continuation=True,
                fresh_prefill_kernel=False)
            self._fallback_cont_fn = _jit_step(
                "fallback_continuation_step", cfg)
        return self._fallback_cont_fn

    def _nan_guarded(self) -> bool:
        return self.scfg.nan_guard or faults.active()

    def _run_step(self, phase: str, cache, batch):
        """One guarded model step: the planned path, degrading to the
        plain-jnp fallback on any failure — an exception out of the compiled
        step, an injected ``engine.decode``/``engine.prefill``/
        ``engine.prefill_chunk`` fault, or (guard on) non-finite logits.
        The fallback recomputes from the *pre-step* cache, so a poisoned
        kernel cannot leak NaNs into the carried KV/SSD state.  Raises only
        if the bottom rung itself fails."""
        cont = phase == "prefill_chunk"
        try:
            faults.check(f"engine.{phase}")
            step_fn = self._cont() if cont else self._model_step
            with jax.set_mesh(self.mesh):
                logits, new_cache = self.timer.run(
                    phase, step_fn, self.params, cache, batch)
            if self._nan_guarded() and \
                    not bool(jnp.isfinite(logits[:, -1]).all()):
                raise FloatingPointError(
                    f"non-finite logits from the planned {phase} step")
            return logits, new_cache
        except Exception as e:  # noqa: BLE001 — serving must not die
            obs.count("engine.degraded", phase=phase,
                      reason=type(e).__name__)
            self._req_degraded = True
            fb = self._fallback_cont() if cont else self._fallback()
            with jax.set_mesh(self.mesh):
                return self.timer.run(phase, fb, self.params, cache, batch)

    def prefill(self, tokens: jax.Array, enc_out=None):
        """tokens: (B, S_prompt) — returns (cache, last_logits)."""
        batch = {"tokens": tokens}
        if enc_out is not None:
            batch["enc_out"] = enc_out
        with obs.span("serve.prefill", cat="serve",
                      batch=int(tokens.shape[0]),
                      prompt_len=int(tokens.shape[1])):
            with obs.span("engine.init_cache", cat="engine"):
                cache = self._cache_factory(int(tokens.shape[0]))
            logits, cache = self._run_step("prefill", cache, batch)
        return cache, logits[:, -1]

    def prefill_chunk(self, cache, tokens: jax.Array, enc_out=None):
        """Continuation prefill: advance ``cache`` (scalar-pos, possibly
        already holding tokens) by one chunk of ``tokens`` (B, S_chunk).
        Returns ``(cache, last_logits)``.  At pos == 0 this computes the
        same answer as :meth:`prefill` (without the flash fresh-cache
        route); at pos > 0 the chunk attends over the whole written prefix
        and the SSM scan is seeded from the cached recurrent state — the
        mechanism under the scheduler's chunked prefill and
        preemption-resume paths."""
        batch = {"tokens": tokens}
        if enc_out is not None:
            batch["enc_out"] = enc_out
        with obs.span("serve.prefill_chunk", cat="serve",
                      batch=int(tokens.shape[0]),
                      chunk_len=int(tokens.shape[1])):
            obs.count("engine.prefill_chunk")
            logits, cache = self._run_step("prefill_chunk", cache, batch)
        return cache, logits[:, -1]

    def _sample(self, logits, key):
        if self.scfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)
        return jax.random.categorical(key, logits / self.scfg.temperature)

    def _decode_token(self, cache, batch):
        """One instrumented decode step — the serving hot path.

        The tracer-off path is kept deliberately lean (one enabled check,
        one perf_counter pair, one cached-histogram append, the step
        timer's two null spans).  Serving qwen3-0.6b over 32 lanes on a TPU
        v5e, the decode step read 88.4-90.1 ms with tracing off against
        88.6-88.9 ms without the scheduler's and timer's spans, and
        88.8-89.7 ms with tracing on under a profiler capture (``PERF.md``).
        """
        t0 = time.perf_counter()
        tr = obs.get_tracer()
        if tr.enabled:
            with tr.span("serve.decode", cat="serve"):
                logits, cache = self._run_step("decode", cache, batch)
        else:
            logits, cache = self._run_step("decode", cache, batch)
        self._step_hist.record(time.perf_counter() - t0)
        return logits, cache

    def generate(self, prompt_tokens: jax.Array, n_new: int,
                 enc_out=None, return_logits: bool = False):
        """Greedy/temperature generation.  Returns (B, n_new) tokens, or
        with ``return_logits=True`` a ``(tokens, logits)`` pair where
        ``logits`` is the fp32 (n_new, B, V) stack of the distributions each
        returned token was sampled from (the chaos suite's parity surface).

        Completion is the contract: any step failure degrades through
        :meth:`_run_step` to the plain-jnp rung rather than raising, and a
        request that needed any degraded step is counted in
        ``degraded_requests`` / the ``serve.generate`` span."""
        t_start = time.perf_counter()
        self._req_degraded = False
        with obs.span("serve.generate", cat="serve",
                      batch=int(prompt_tokens.shape[0]),
                      prompt_len=int(prompt_tokens.shape[1]),
                      n_new=n_new) as gspan:
            cache, last = self.prefill(prompt_tokens, enc_out)
            key = jax.random.PRNGKey(self.scfg.seed)
            toks = []
            lgs = [last.astype(jnp.float32)]
            cur = self._sample(last, key)[:, None]
            # time-to-first-token: prefill + first sample, host-visible
            ttft = time.perf_counter() - t_start
            obs.observe("serve.ttft_s", ttft)
            gspan.set(ttft_s=round(ttft, 6))
            for i in range(n_new):
                toks.append(cur)
                batch = {"tokens": cur.astype(jnp.int32)}
                if enc_out is not None:
                    batch["enc_out"] = enc_out
                logits, cache = self._decode_token(cache, batch)
                lgs.append(logits[:, -1].astype(jnp.float32))
                key, sub = jax.random.split(key)
                cur = self._sample(logits[:, -1], sub)[:, None]
            obs.count("serve.tokens",
                      n_new * int(prompt_tokens.shape[0]))
            if self._req_degraded:
                self.degraded_requests += 1
                obs.count("serve.degraded_request")
                gspan.set(degraded=True)
        out = jnp.concatenate(toks, axis=1)
        if return_logits:
            return out, jnp.stack(lgs[:n_new])
        return out

    # -------------------------------------------------- continuous batching --
    def serve_stream(self, requests, *, max_slots: Optional[int] = None,
                     collect_logits: bool = False, step_hook=None,
                     prefill_chunk_tokens: Optional[int] = None,
                     preempt_policy: Optional[str] = None,
                     max_queue: Optional[int] = None,
                     deadline_aware: bool = False,
                     step_time_ms: Optional[float] = None,
                     return_shed: bool = False):
        """Serve a *stream* of requests through the continuous-batching
        scheduler (:mod:`repro.serve.scheduler`): ``max_slots`` decode
        lanes over one per-slot-pos cache, FIFO admission of arrivals into
        freed lanes, grouped prefill + batched decode per step.

        ``requests`` is a sequence of :class:`scheduler.Request` (virtual
        arrival steps — use :func:`scheduler.synthetic_workload` for seeded
        traces).  Returns ``[CompletedRequest]`` sorted by rid; each
        request's tokens are identical to running it alone through
        :meth:`generate` (per-request PRNG key chains).  ``max_slots``
        defaults to the engine batch — the decode-plan buckets were warmed
        at that batch, so the default keeps the stream on warm plans.

        Overload controls (see ``docs/serving.md`` § Overload behavior):
        ``prefill_chunk_tokens`` bounds per-step prefill work (long prompts
        admit over several steps), ``preempt_policy`` enables slot
        preemption (``'longest_remaining'`` | ``'lowest_priority'``),
        ``max_queue`` bounds the admission queue (overflow is shed with
        reason ``queue_full``), and ``deadline_aware=True`` sheds requests
        whose ``deadline_ms`` is provably unmeetable.  With
        ``return_shed=True`` the result is ``(completed, shed)``.

        ``step_time_ms`` maps wall-clock deadlines onto the scheduler's
        virtual step clock.  ``None`` (the default) seeds it from measured
        timings (:meth:`measured_step_time_ms` — served-step p50, else the
        warmup/artifact plan timings), falling back to the 1.0 ms constant
        only when nothing has been measured — so ``deadline_unmeetable``
        sheds reflect real plan speed, not a guess.
        """
        from . import scheduler as sched_mod
        if step_time_ms is None:
            measured = self.measured_step_time_ms()
            step_time_ms = measured if measured else 1.0
            obs.count("sched.step_time_seeded",
                      source="measured" if measured else "constant",
                      step_time_ms=round(step_time_ms, 4))
        sched = sched_mod.Scheduler(self, max_slots=max_slots,
                                    collect_logits=collect_logits,
                                    step_hook=step_hook,
                                    prefill_chunk_tokens=prefill_chunk_tokens,
                                    preempt_policy=preempt_policy,
                                    max_queue=max_queue,
                                    deadline_aware=deadline_aware,
                                    step_time_ms=step_time_ms)
        completed = sched.run(requests)
        if return_shed:
            return completed, sorted(sched.shed.values(),
                                     key=lambda s: s.rid)
        return completed

    # ------------------------------------------------------------ reports --
    def stats(self) -> Dict[str, Any]:
        """Timing split: plan warmup vs per-phase compile vs steady-state
        step time, plus plan-registry hit/miss counters when active."""
        reg = self._registry()
        return {
            "warmup_s": round(self.warmup_s, 4),
            "plans_warmed": len(self.warmup_report),
            "warmup_failed": sum(1 for r in self.warmup_report
                                 if "error" in r),
            # fresh measurements paid at warmup: the warm-start assertion
            # surface — an artifact-loaded replica must show 0 here
            "warmup_measured": sum(1 for r in self.warmup_report
                                   if r.get("measured")
                                   and not r.get("replayed")),
            "artifact": self.artifact_report,
            "degraded_requests": self.degraded_requests,
            "phases": self.timer.stats(),
            "registry": reg.stats.as_dict() if reg is not None else None,
        }
