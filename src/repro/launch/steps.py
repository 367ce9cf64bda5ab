"""Step builders: jit'd train / prefill / decode steps with shardings.

These are the functions the dry-run lowers and the trainer executes.  All
take abstract ShapeDtypeStructs just as well as real arrays (nothing inside
allocates), so ``build_*`` + ``.lower(...)`` is the whole multi-pod story.

The trainer's *temporal pump* (paper Mode T at pod scale) lives here:
``train_step`` with ``pump_factor=M`` consumes a batch of M microbatches,
runs M sequential grad computations (fast domain — the issuer is a
lax.scan), and applies ONE optimizer update + gradient synchronization per
wide transaction (the packed gradient).  XLA/GSPMD materializes the gradient
all-reduce at the point of use — once per M microbatches instead of per
microbatch — which is exactly the collective-term reduction measured in
EXPERIMENTS.md §Perf.

:class:`StepTimer` is the timing discipline for every step consumer (the
serve engine, launchers, benchmarks): compile/measure cost is attributed to
a phase's first call and steady-state step time is accumulated separately,
so warmup never pollutes the numbers serving decisions are made on.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs, optim
from repro.models import model as model_mod
from repro.configs.base import ModelConfig, ShapeConfig

from . import sharding as shard_mod


# ------------------------------------------------------------- step timing --
class StepTimer:
    """Separates compile/measure time from steady-state step time.

    The first call of each named phase pays tracing + XLA compilation (and,
    on the registry path, any cold plan measurement) and is recorded as that
    phase's cold time (``compile_s``); every later call lands in a warm
    :class:`repro.obs.metrics.Histogram` — the percentile math (p50/p90/p99)
    lives there, not in a parallel implementation here.  Serving reports
    must never average warmup into steady-state step time — the
    measured-pump wins are a steady-state property, and a one-off compile
    can be 1000× a decode step.

        timer = StepTimer()
        logits, cache = timer.run("decode", decode_fn, params, cache, batch)
        timer.stats()["decode"]          # flat legacy keys + cold/warm split
        timer.stats()["decode"]["warm"]  # {"calls", "mean_s", "p50_s", ...}
    """

    def __init__(self):
        self.compile_s: Dict[str, float] = {}
        self._warm: Dict[str, obs.Histogram] = {}

    def run(self, phase: str, fn, *args):
        """Call ``fn(*args)`` and wait for its result.  The call and the
        wait are spans of their own (``engine.dispatch``,
        ``engine.wait``): device idle inside the first is host launch
        cost."""
        t0 = time.perf_counter()
        with obs.span("engine.dispatch", cat="engine", phase=phase):
            out = fn(*args)
        with obs.span("engine.wait", cat="engine", phase=phase):
            out = jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        if phase not in self.compile_s:
            self.compile_s[phase] = dt
        else:
            hist = self._warm.get(phase)
            if hist is None:
                hist = self._warm[phase] = obs.Histogram()
            hist.record(dt)
        return out

    @property
    def steady(self) -> Dict[str, list]:
        """Raw warm samples per phase (compat view over the histograms)."""
        return {phase: h.values for phase, h in self._warm.items()}

    def stats(self) -> Dict[str, Dict[str, Any]]:
        out = {}
        for phase, comp in self.compile_s.items():
            hist = self._warm.get(phase)
            n = hist.count if hist else 0

            def _r(v):
                return round(v, 6) if v is not None else None

            out[phase] = {
                # flat legacy keys (benchmarks/tests consume these)
                "compile_s": round(comp, 6),
                "steady_mean_s": _r(hist.mean) if hist else None,
                # best observed step: the number benchmarks compare against
                # (min drops scheduler tails on a shared box, mirroring the
                # paired best-of-N protocol in benchmarks/serve_report.py)
                "steady_best_s": _r(hist.min) if hist else None,
                "steady_p50_s": _r(hist.percentile(50)) if hist else None,
                "steady_p99_s": _r(hist.percentile(99)) if hist else None,
                "steps": n,
                # explicit warm-vs-cold split: cold = first call (trace +
                # XLA compile + cold plan measurement), warm = steady state
                "cold": {"calls": 1, "total_s": round(comp, 6)},
                "warm": {
                    "calls": n,
                    "total_s": _r(hist.total) if hist else 0.0,
                    "mean_s": _r(hist.mean) if hist else None,
                    "best_s": _r(hist.min) if hist else None,
                    "p50_s": _r(hist.percentile(50)) if hist else None,
                    "p90_s": _r(hist.percentile(90)) if hist else None,
                    "p99_s": _r(hist.percentile(99)) if hist else None,
                },
            }
        return out


# ----------------------------------------------------------- abstract trees --
def abstract_params(cfg: ModelConfig, param_dtype=jnp.bfloat16):
    return jax.eval_shape(
        lambda k: model_mod.init_params(cfg, k, dtype=param_dtype),
        jax.random.PRNGKey(0))


def abstract_opt_state(optcfg: optim.AdamWConfig, params):
    return jax.eval_shape(lambda p: optim.init(optcfg, p), params)


def abstract_batch(cfg: ModelConfig, shape: ShapeConfig,
                   pump_factor: int = 1) -> Dict[str, Any]:
    """ShapeDtypeStructs for one global training batch.

    With pump_factor=M the leading batch dim is split into M microbatches:
    (M, B/M, S).  The wide transaction stays (B, S) tokens; M is the
    temporal packing inside it.
    """
    b, s = shape.global_batch, shape.seq_len
    if pump_factor > 1:
        assert b % pump_factor == 0
        lead = (pump_factor, b // pump_factor)
    else:
        lead = (b,)
    tok = jax.ShapeDtypeStruct(lead + (s,), jnp.int32)
    batch = {"tokens": tok, "labels": tok}
    if cfg.family == "encdec":
        batch["frames"] = jax.ShapeDtypeStruct(
            lead + (cfg.encoder_seq, cfg.d_model), jnp.float32)
    if cfg.family == "vlm":
        batch["patches"] = jax.ShapeDtypeStruct(
            lead + (cfg.n_vision_tokens, cfg.d_vision), jnp.float32)
    return batch


def abstract_decode_batch(cfg: ModelConfig, shape: ShapeConfig):
    b = shape.global_batch
    batch = {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}
    if cfg.family == "encdec":
        batch["enc_out"] = jax.ShapeDtypeStruct(
            (b, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
    return batch


def abstract_cache(cfg: ModelConfig, shape: ShapeConfig,
                   cache_dtype=jnp.bfloat16):
    return jax.eval_shape(
        lambda: model_mod.init_cache(cfg, shape.global_batch, shape.seq_len,
                                     cache_dtype))


# -------------------------------------------------------------- train step --
def make_train_step(cfg: ModelConfig, optcfg: optim.AdamWConfig,
                    pump_factor: int = 1):
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def single_loss(params, batch):
        return model_mod.loss_fn(cfg, params, batch)

    def train_step(params, opt_state, batch):
        if pump_factor > 1:
            # temporal vectorization of the gradient stream: M dependent
            # accumulation iterations per one optimizer/collective step
            def micro(carry, mb):
                loss_acc, grad_acc = carry
                loss, grads = jax.value_and_grad(single_loss)(params, mb)
                grad_acc = jax.tree.map(jnp.add, grad_acc, grads)
                return (loss_acc + loss, grad_acc), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(
                micro, (jnp.zeros(()), zeros), batch)
            inv = 1.0 / pump_factor
            loss = loss * inv
            grads = jax.tree.map(lambda g: g * inv, grads)
        else:
            loss, grads = jax.value_and_grad(single_loss)(params, batch)
        new_params, new_opt, metrics = optim.update(optcfg, grads, opt_state,
                                                    params)
        metrics["loss"] = loss
        return new_params, new_opt, metrics

    return train_step


def train_shardings(cfg: ModelConfig, optcfg, mesh, shape: ShapeConfig,
                    param_dtype=jnp.bfloat16, pump_factor: int = 1):
    """(in_shardings, out_shardings, abstract args) for make_train_step."""
    params = abstract_params(cfg, param_dtype)
    opt_state = abstract_opt_state(optcfg, params)
    batch = abstract_batch(cfg, shape, pump_factor)

    p_shard = shard_mod.shardings(params, mesh)
    pspecs = shard_mod.fit_specs(shard_mod.param_specs(params), params, mesh)
    # ZeRO across pods: optimizer state (master/m/v) additionally shards the
    # FSDP axis over ("pod", "data") — params stay pod-replicated (cheap
    # all-gather within pod), while the 8×-larger optimizer state is divided
    # across ALL chips.  deepseek-v3: 21 GB → 15.7 GB/chip (EXPERIMENTS §Dry-run).
    ospecs = pspecs
    if "pod" in mesh.axis_names:
        def widen(sp):
            return P(*[("pod", e) if e == "data"
                       else (("pod",) + e if isinstance(e, tuple)
                             and "data" in e else e) for e in sp])
        ospecs = jax.tree.map(widen, pspecs,
                              is_leaf=lambda x: isinstance(x, P))
        ospecs = shard_mod.fit_specs(ospecs, params, mesh)
    o_shard = optim.AdamWState(
        step=NamedSharding(mesh, P()),
        master=shard_mod.shardings(opt_state.master, mesh, ospecs),
        m=shard_mod.shardings(opt_state.m, mesh, ospecs),
        v=shard_mod.shardings(opt_state.v, mesh, ospecs),
    )
    bsp = shard_mod.batch_spec(mesh)
    bax = bsp[0] if len(bsp) else None
    bdim = 1 if pump_factor > 1 else 0   # microbatch axis leads when pumped

    def bspec(l):
        spec = [None] * l.ndim
        if l.ndim > bdim:
            spec[bdim] = bax
        return NamedSharding(mesh, shard_mod._fit(P(*spec), l.shape, mesh))

    b_shard = jax.tree.map(bspec, batch)
    metrics_shard = {"loss": NamedSharding(mesh, P()),
                     "grad_norm": NamedSharding(mesh, P()),
                     "lr": NamedSharding(mesh, P())}
    in_sh = (p_shard, o_shard, b_shard)
    out_sh = (p_shard, o_shard, metrics_shard)
    return in_sh, out_sh, (params, opt_state, batch)


# ------------------------------------------------------------ prefill step --
def make_prefill_step(cfg: ModelConfig, last_only: bool = True):
    """Forward pass over a full prompt (inference-prefill).  Serving only
    needs the final position's logits (§Perf C1); pass last_only=False for
    scoring workloads that need the whole sequence."""

    def prefill_step(params, batch):
        logits, _ = model_mod.forward(cfg, params, batch,
                                      last_only=last_only)
        return logits

    return prefill_step


# ------------------------------------------------------------- decode step --
def make_decode_step(cfg: ModelConfig):
    """(params, cache, batch) -> (next_token_logits, new_cache)."""

    def decode_step(params, cache, batch):
        logits, new_cache = model_mod.decode_step(cfg, params, batch, cache)
        return logits, new_cache

    return decode_step


def serve_shardings(cfg: ModelConfig, mesh, shape: ShapeConfig,
                    param_dtype=jnp.bfloat16, fsdp: bool = False):
    """Decode-path shardings.  ``fsdp=False`` (default) keeps weights
    TP-resident (sharded over "model" only): per-token FSDP all-gathers
    were 53 MB/layer/token on qwen2.5 decode — §Perf E2.  Training keeps
    FSDP; prefill amortizes the gathers over the whole prompt."""
    params = abstract_params(cfg, param_dtype)
    cache = abstract_cache(cfg, shape)
    batch = abstract_decode_batch(cfg, shape)
    pspecs = shard_mod.param_specs(params)
    if not fsdp and cfg.family != "moe":
        # MoE keeps FSDP for decode: only top-k of E experts touch a token,
        # so gathering the (small) active slices beats holding every
        # expert's weights 16-way resident (§Perf E3).
        pspecs = shard_mod.strip_axis(pspecs, "data")
    p_shard = shard_mod.shardings(params, mesh, pspecs)
    c_shard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           shard_mod.cache_specs(cache, mesh),
                           is_leaf=lambda x: isinstance(x, P))
    b_shard = jax.tree.map(
        lambda l: NamedSharding(mesh, shard_mod._fit(
            shard_mod.batch_spec(mesh) if l.ndim else P(), l.shape, mesh)),
        batch)
    return p_shard, c_shard, b_shard, (params, cache, batch)
