"""One-chip smoke run of the serving path through the compiled Pallas kernels.

    python chip_smoke.py [--seed 0]

Everything runs in this one process on one TPU; there is no CPU branch.
Phases:

  (a) device report: platform, device kind, device count;
  (b) the paper's kernels (vecadd, matmul, stencil, floyd-warshall) through
      ``compiler.compile(backend='pallas')`` at M in {1, 2, 4}, each checked
      against numpy, with the emission tier of every region;
  (c) qwen3-0.6b at its published width, bf16 parameters drawn from
      ``--seed``, ``BATCH`` prompts of ``PROMPT_LEN`` tokens: ``Engine``
      warmup (measured pump plans) then ``generate`` of ``NEW_TOKENS`` with
      ``attention_impl='pallas'``, ``kernel_plan='measure'``;
  (d) the same prompts through the plain-jnp path (``kernel_plan='direct'``,
      ``attention_impl='xla_chunked'``) on the same chip: prefill and first
      decode logits compared within ``PARITY_REL_L2``, greedy tokens
      compared; as a control, the plain path's bf16 prefill logits against
      the same weights run in f32.

The run fails (exit 1, no ok line) on any wrong result and on any sign that
the serving path left the compiled kernels: a plan-registry fallback, a
degraded engine step, a failed warmup bucket, a quarantined plan, a failed
autotune candidate, a cold plan lookup after warmup, or an attention region
emitted at a tier other than ``pallas``.  Times and memory printed on the
way are one chip run's readings, not benchmark results.  The last line of
standard output is ``{"ok": true, "device": {...}}``.

JAX's compilation cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else
to ``.cache/jax`` in this checkout; the plan store goes where
``REPRO_CACHE_DIR`` says, else to ``.cache/repro``.  A second run in the
same checkout replays both.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

# The serving phase's size: four prompts of 128 tokens, 32 tokens generated.
BATCH, PROMPT_LEN, NEW_TOKENS = 4, 128, 32
# Prefill and first-decode logits of the kernel path against the plain-jnp
# path, per batch row: ||a - b||_2 / ||b||_2.  Both paths keep bf16 weights
# and bf16 activations between layers; they differ in how attention is
# computed (the Pallas kernels against XLA's einsums).  Measured on a v5e
# (PERF.md): the gap is 0 after one layer and grows with depth to 1.5e-2
# after 28, as random-weight layers amplify the first bf16 roundings that
# differ; the plain bf16 path itself parts from the same weights run in f32
# by more, 1.85e-2 (the control line below).  A causal mask off by one
# position reads 5.9e-1 at prefill; a decode mask that drops the new
# token's key reads 7.1e-2 at the first decode.  5% sits between the
# healthy 1.7e-2 and the smaller fault.
PARITY_REL_L2 = 0.05
ATTENTION_KERNELS = ("flash_attention", "decode_attention")


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class SmokeFailure(RuntimeError):
    pass


# ------------------------------------------------------------------ (a) --
def device_report() -> dict:
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SmokeFailure(f"no TPU: JAX found no device ({e})") from e
    d = devs[0]
    if d.platform != "tpu":
        raise SmokeFailure(
            f"no TPU: JAX's device is {d.platform!r} ({d.device_kind}); "
            "this smoke run needs a TPU and has no CPU branch")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ------------------------------------------------------------------ (b) --
def _ints(rng, shape):
    """Small integers as float32: every sum and product below is exact."""
    return rng.integers(-3, 4, shape, dtype=np.int8).astype(np.float32)


def _stencil_ref(x, coef=0.25):
    y = np.zeros_like(x)
    y[1:-1] = coef * (x[:-2] + x[2:]) + (1.0 - 2.0 * coef) * x[1:-1]
    return y


def _floyd_ref(d):
    d = d.copy()
    for k in range(d.shape[0]):
        np.minimum(d, d[:, k:k + 1] + d[k:k + 1, :], out=d)
    return d


def paper_cases(rng):
    """(name, builder args, builder kwargs, inputs, output memory, numpy
    result, exact, must reach the pallas tier).  Sizes move tens of MiB; a
    must-reach kernel is one whose v5e compile is covered by
    ``tests/test_tpu_compile.py``."""
    n = 1 << 24                                     # 3 x 64 MiB
    x, y = _ints(rng, (n,)), _ints(rng, (n,))
    yield ("vecadd", (n,), dict(vector_width=1 << 16),
           {"x": x, "y": y}, "z", x + y, True, True)
    m = 2048                                        # 3 x 16 MiB
    a, b = _ints(rng, (m, m)), _ints(rng, (m, m))
    yield ("matmul", (m, m, m), {},
           {"a": a, "b": b}, "c", a @ b, True, True)
    s = _ints(rng, (258, 256, 256))                 # 2 x 64 MiB
    yield ("stencil", s.shape, {}, {"x": s}, "y", _stencil_ref(s), False,
           False)
    d = np.abs(_ints(rng, (512, 512)))              # 512 sweeps of 1 MiB
    yield ("floyd_warshall", (512,), {}, {"dist": d}, "out", _floyd_ref(d),
           True, False)


def run_paper_kernels(seed: int, factors=(1, 2, 4)) -> None:
    import jax
    from repro import compiler
    from repro.core.autopump import BUILDERS

    rng = np.random.default_rng(seed)
    for name, args, kwargs, inputs, out, want, exact, needs_pallas \
            in paper_cases(rng):
        dev_inputs = {k: jax.device_put(v) for k, v in inputs.items()}
        for m in factors:
            g, _est = BUILDERS[name](*args, **kwargs)
            t0 = time.perf_counter()
            kern = compiler.compile(g, factor=m, backend="pallas",
                                    cache=False)
            got = jax.block_until_ready(kern(dev_inputs)[out])
            t_first = time.perf_counter() - t0
            t0 = time.perf_counter()
            jax.block_until_ready(kern(dev_inputs)[out])
            t_run = time.perf_counter() - t0
            tiers = sorted({r["tier"]
                            for r in kern.report.emission.values()})
            got = np.asarray(got)
            ok = np.array_equal(got, want) if exact else \
                np.allclose(got, want, rtol=1e-6, atol=1e-6)
            log(f"(b) {name} M={m} (realized {kern.spec.factor}) "
                f"tiers={tiers} correct={ok} | one chip run: compile+first "
                f"call {t_first:.3f}s, second call {t_run * 1e3:.3f}ms")
            if not ok:
                raise SmokeFailure(f"{name} M={m}: wrong result "
                                   f"(max |diff| "
                                   f"{np.max(np.abs(got - want))})")
            if needs_pallas and tiers != ["pallas"]:
                raise SmokeFailure(f"{name} M={m}: emitted at {tiers}, "
                                   "expected pallas")
            if not needs_pallas and tiers != ["pallas"]:
                log(f"(b) {name}: this graph does not reach the pallas "
                    f"tier ({tiers})")


# ----------------------------------------------------------- (c) + (d) --
def plan_faults(eng, expect_tier: str = "pallas") -> list:
    """Everything that says the engine's serving path left the compiled
    kernels, as readable strings (empty on a healthy run)."""
    from repro import obs
    from repro.compiler import default_cache

    st = eng.stats()
    faults = []
    reg = st["registry"] or {}
    if reg.get("fallbacks"):
        faults.append(f"{reg['fallbacks']} plan-registry fallback(s) "
                      f"(prefill {reg['prefill']}, decode {reg['decode']})")
    if st["degraded_requests"]:
        faults.append(f"{st['degraded_requests']} degraded request(s)")
    if st["warmup_failed"]:
        faults.append(f"{st['warmup_failed']} failed warmup bucket(s)")
    counters = obs.snapshot(include_views=False)["counters"]
    for name in ("engine.degraded", "compile.measure_failed",
                 "compile.measure_in_trace", "degrade.compile",
                 "registry.spotcheck_failed"):
        if counters.get(name):
            faults.append(f"counter {name} = {counters[name]}")
    quarantined = default_cache().quarantine_entries()
    if quarantined:
        faults.append(f"{len(quarantined)} quarantined plan(s): "
                      + ", ".join(sorted(q["reason"]
                                         for q in quarantined.values())))
    for rec in eng.warmup_report:
        if rec["kernel"] in ATTENTION_KERNELS \
                and rec.get("tiers") != [expect_tier]:
            faults.append(f"{rec['kernel']}{tuple(rec['args'])} emitted at "
                          f"{rec.get('tiers')} ({rec.get('error', '')})")
    return faults


def _rel_l2(a, b) -> np.ndarray:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return np.linalg.norm(a - b, axis=-1) / np.linalg.norm(b, axis=-1)


def _kernel_calls(cfg, params, batch, cache) -> int:
    """Pallas custom calls in the lowered decode step: at least one when the
    decode attention is the compiled kernel (the layers are one scanned
    body, so one call site serves all of them).  Interpret mode lowers to
    plain HLO loops and counts zero."""
    import jax
    from repro.models import model as model_mod
    text = jax.jit(lambda p, c, b: model_mod.decode_step(cfg, p, b, c)) \
        .lower(params, cache, batch).as_text()
    return text.count("tpu_custom_call")


def run_serving(seed: int) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp
    from repro.configs.base import load_arch
    from repro.models import model as model_mod
    from repro.serve.engine import Engine, ServeConfig

    cfg = dataclasses.replace(load_arch("qwen3-0.6b"),
                              attention_impl="pallas", kernel_plan="measure")
    params = model_mod.init_params(cfg, jax.random.PRNGKey(seed),
                                   dtype=jnp.bfloat16)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    batch = BATCH
    scfg = ServeConfig(batch=batch, max_len=PROMPT_LEN + NEW_TOKENS + 1,
                       cache_dtype="bfloat16", seed=seed)
    prompts = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                 (batch, PROMPT_LEN), 0, cfg.vocab_size)
    log(f"(c) {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{n_params / 1e6:.1f}M bf16 params from seed {seed}; batch {batch}, "
        f"prompt {PROMPT_LEN}, {NEW_TOKENS} new tokens")

    eng = Engine(cfg, params, scfg)
    st = eng.stats()
    fresh = st["warmup_measured"]
    log(f"(c) warmup: {st['warmup_s']:.2f}s for {st['plans_warmed']} "
        f"plans ({fresh} measured, {st['plans_warmed'] - fresh} replayed) "
        "| one chip run")
    for rec in eng.warmup_report:
        log(f"(c)   plan {rec['kernel']}{tuple(rec['args'])}: "
            f"M={rec['factor']} tiers={rec.get('tiers')} "
            f"{'replayed' if rec.get('replayed') else 'measured'} "
            f"{rec['time_s']:.2f}s")
    misses_warm = st["registry"]["misses"]

    tokens, logits = eng.generate(prompts, NEW_TOKENS, return_logits=True)
    tokens, logits = np.asarray(tokens), np.asarray(logits)
    st = eng.stats()
    dec, pre = st["phases"]["decode"], st["phases"]["prefill"]
    log(f"(c) generated {tokens.shape}; compile: prefill "
        f"{pre['compile_s']:.2f}s, decode {dec['compile_s']:.2f}s; steady "
        f"decode {dec['steady_p50_s'] * 1e3:.3f} ms/step p50 over "
        f"{dec['steps']} steps (block_until_ready) | one chip run")
    mem = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in mem:
        log(f"(c) peak HBM in use: {mem['peak_bytes_in_use'] / 2**30:.3f} "
            "GiB | one chip run")
    reg = st["registry"]
    log(f"(c) plan registry: prefill {reg['prefill']} decode "
        f"{reg['decode']} fallbacks={reg['fallbacks']}")

    faults = plan_faults(eng)
    if reg["misses"] != misses_warm:
        faults.append(f"{reg['misses'] - misses_warm} cold plan lookup(s) "
                      "after warmup")
    if not np.isfinite(logits).all():
        faults.append("non-finite logits")
    n_calls = _kernel_calls(eng.cfg, params,
                            {"tokens": jnp.asarray(tokens[:, :1], jnp.int32)},
                            model_mod.init_cache(eng.cfg, batch,
                                                 scfg.max_len, jnp.bfloat16))
    log(f"(c) lowered decode step holds {n_calls} tpu_custom_call(s)")
    if n_calls < 1:
        faults.append("the lowered decode step holds no Pallas kernel")
    if faults:
        raise SmokeFailure("serving path left the compiled kernels: "
                           + "; ".join(faults))

    # (d) the plain-jnp path on the same chip, same prompts
    ref_cfg = dataclasses.replace(cfg, attention_impl="xla_chunked",
                                  kernel_plan="direct")
    ref = Engine(ref_cfg, params, scfg)
    cache, ref_prefill = ref.prefill(prompts)
    # teacher-forced: the reference decodes the kernel path's first token,
    # so both first-decode distributions condition on the same context
    _cache, ref_decode = ref.prefill_chunk(
        cache, jnp.asarray(tokens[:, :1], jnp.int32))
    ref_tokens = np.asarray(ref.generate(prompts, NEW_TOKENS))
    err_pre = _rel_l2(logits[0], ref_prefill)
    err_dec = _rel_l2(logits[1], ref_decode)
    agree = float(np.mean(tokens == ref_tokens))
    first = float(np.mean(np.argmax(logits[0], -1)
                          == np.argmax(np.asarray(ref_prefill), -1)))
    log(f"(d) logits vs plain jnp, per-row ||a-b||/||b||: prefill max "
        f"{err_pre.max():.3e}, first decode max {err_dec.max():.3e} "
        f"(limit {PARITY_REL_L2}); prefill argmax agreement {first:.2f}; "
        f"greedy-token agreement over {NEW_TOKENS} tokens {agree:.3f}")
    # control: how far bf16 alone moves the plain path from the same
    # weights run in f32 (f32 products on the MXU too)
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("float32"):
        _cache, f32_prefill = Engine(ref_cfg, params32, dataclasses.replace(
            scfg, cache_dtype="float32")).prefill(prompts)
    log(f"(d) control, prefill logits vs the plain path in f32: kernel path "
        f"{_rel_l2(logits[0], f32_prefill).max():.3e}, plain path in bf16 "
        f"{_rel_l2(ref_prefill, f32_prefill).max():.3e}")
    del params32
    if err_pre.max() > PARITY_REL_L2 or err_dec.max() > PARITY_REL_L2:
        raise SmokeFailure("logits parity against the plain-jnp path "
                           f"exceeds {PARITY_REL_L2}")
    faults = plan_faults(eng)
    if faults:
        raise SmokeFailure("; ".join(faults))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        device = device_report()
        log(f"(a) device: {device}")
        sys.path.insert(0, str(ROOT / "src"))
        try:
            from repro.launch import compile_cache
        except ImportError as e:
            raise SmokeFailure(f"no repro package under {ROOT / 'src'}: run "
                               "this script from a checkout of the repo "
                               f"({e})") from e
        os.environ.setdefault("REPRO_CACHE_DIR",
                              str(compile_cache.CACHE_ROOT / "repro"))
        log(f"compile cache: {compile_cache.enable()}; plan store: "
            f"{os.environ['REPRO_CACHE_DIR']}")
        run_paper_kernels(args.seed)
        run_serving(args.seed)
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
