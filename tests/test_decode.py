"""Decode hot-path tests: the kernelized S=1 attention step (position-offset
mask, pos-bucketed plans) and the SSD final-state / single-token decode
routes — parity against the plain-jnp references and the numpy executor at
the exp-bearing carry tolerance (5e-6, see tests/differential.py), plus the
registry-level serving contracts (phase-split stats, warmup warning dedupe,
pos bucketing)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compiler.registry import (PlanRegistry, default_registry,
                                     set_default_registry)
from repro.configs.base import load_arch
from repro.core.autopump import decode_kv_tile

from differential import DECODE_CASES, FACTORS, MODES, run_case


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path / "cache"))
    old = set_default_registry(None)
    yield
    set_default_registry(old)


def _ints(shape, seed=0, lo=-2, hi=3):
    return jnp.asarray(np.random.default_rng(seed).integers(
        lo, hi, shape).astype(np.float32))


def _gqa_setup(max_len=32, b=2):
    cfg = dataclasses.replace(load_arch("qwen3-0.6b", smoke=True),
                              attention_impl="pallas")
    p = {}
    from repro.models import attention as attn_mod
    p = attn_mod.gqa_init(jax.random.PRNGKey(0), cfg)
    kshape = (b, cfg.n_kv_heads, max_len, cfg.head_dim_)
    cache = {"k": _ints(kshape, 1), "v": _ints(kshape, 2)}
    x1 = _ints((b, 1, cfg.d_model), 3)
    return cfg, p, cache, x1


# --------------------------------------------- decode kernel differential --
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("factor", FACTORS)
@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_decode_kernel_matches_executor(case, factor, mode):
    """The Pallas decode kernel (interpret mode) against the reference
    executor and the numpy gold, across GQA groups 1/2/4 and one, two or
    four KV tiles.  The grid has one point per (row, KV head, KV tile) —
    times the pump axis where mode R runs each sub-tile's own sweep."""
    c = DECODE_CASES[case]
    kern = run_case(c, factor, mode, "pallas", pallas_mode="interpret")
    [region] = kern.report.emission.values()
    assert region["tier"] == "pallas"
    b, h, t, d = c.args
    hkv = c.kwargs["hkv"]
    bkv = c.kwargs["bkv"] or decode_kv_tile(t, d, h // hkv)
    points = int(np.prod([e for _s, e in region["grid"]]))
    assert points == b * hkv * (t // bkv) \
        * (region["pump"] if mode == "R" else 1)


def test_decode_plan_reports_grid_and_tile_and_counts_grid_points():
    """``PlanRegistry.plans()`` shows a decode plan's KV tile and grid, and
    emitting the kernel counts its grid's points under
    ``emission.grid_points``, so a trace's kernel time divides into time
    per grid step."""
    from repro import obs
    reg = PlanRegistry(pump=1, cache=False)
    before = obs.snapshot()["counters"].get("emission.grid_points", 0)
    q = _ints((2, 8, 8), 1)                   # 8 query heads over 2 KV heads
    kv = _ints((2, 2, 64, 8), 2)
    reg.decode_attention(q, kv, kv, jnp.asarray([40, 63], jnp.int32))
    [plan] = [pl for pl in reg.plans() if pl["kernel"] == "decode_attention"]
    assert plan["bkv"] == decode_kv_tile(64, 8, 4, 4) == 32
    assert plan["grid"] == [["bi", 2], ["kvh", 2], ["ji", 2]]
    after = obs.snapshot()["counters"]["emission.grid_points"]
    assert after - before == 2 * 2 * 2


@pytest.mark.parametrize("t, d, group, itemsize, want", [
    (1024, 128, 2, 2, 512),     # qwen3-0.6b's served cache: two tiles
    (1024, 64, 4, 2, 512),      # granite-3-2b's
    (8192, 128, 2, 2, 1024),    # long caches stop at the VMEM share
    (16, 8, 1, 4, 8),           # the smallest bucket keeps two tiles
    (8, 8, 1, 4, 8),            # below two sublane tiles: one tile
    (48, 8, 2, 4, 8),           # the largest power of two dividing t / 2
])
def test_decode_kv_tile_rule(t, d, group, itemsize, want):
    assert decode_kv_tile(t, d, group, itemsize) == want


# ----------------------------------------------------- decode parity sweep --
@pytest.mark.parametrize("pos", [0, 1, 15, 16, 31])
def test_decode_attention_parity_sweep(pos):
    """Kernelized decode (registry route) vs the full-recompute jnp
    reference at pos = fresh cache, one token, both sides of a bucket
    boundary (15 -> 16, 16 -> 32), and cache-full."""
    set_default_registry(PlanRegistry(pump=1, cache=False))
    from repro.models import attention as attn_mod
    cfg, p, cache, x1 = _gqa_setup(max_len=32)
    cfg_dir = dataclasses.replace(cfg, kernel_plan="direct")
    cc = dict(cache, pos=jnp.asarray(pos, jnp.int32))
    positions = jnp.array([pos])
    o_kern, _ = attn_mod.gqa_apply(p, cfg, x1, positions=positions,
                                   cache=dict(cc))
    o_ref, _ = attn_mod.gqa_apply(p, cfg_dir, x1, positions=positions,
                                  cache=dict(cc))
    np.testing.assert_allclose(np.asarray(o_kern), np.asarray(o_ref),
                               rtol=5e-6, atol=5e-6)


def test_decode_attention_buckets_on_pos():
    """A concrete decode position attends only the pos bucket of the cache:
    the resident plan is keyed on bucket_seq(pos + 1), not max_len."""
    reg = PlanRegistry(pump=1, cache=False)
    set_default_registry(reg)
    from repro.models import attention as attn_mod
    cfg, p, cache, x1 = _gqa_setup(max_len=64)
    for pos, want_t in ((3, 16), (20, 32)):
        cc = dict(cache, pos=jnp.asarray(pos, jnp.int32))
        attn_mod.gqa_apply(p, cfg, x1, positions=jnp.array([pos]),
                           cache=dict(cc))
    plans = [pl for pl in reg.plans() if pl["kernel"] == "decode_attention"]
    assert sorted(pl["args"][2] for pl in plans) == [16, 32]


def test_decode_attention_traced_pos_keys_full_cache_bucket():
    """Inside a jit trace pos is unknowable, so the decode plan keys on the
    preallocated cache length — one plan, warmable at launch — and the
    kernel's mask keeps parity with the eager reference."""
    reg = PlanRegistry(pump=1, cache=False)
    set_default_registry(reg)
    from repro.models import attention as attn_mod
    cfg, p, cache, x1 = _gqa_setup(max_len=32)
    cfg_dir = dataclasses.replace(cfg, kernel_plan="direct")
    positions = jnp.array([7])

    @jax.jit
    def step(cc, xx):
        out, _ = attn_mod.gqa_apply(p, cfg, xx, positions=positions,
                                    cache=cc)
        return out

    cc = dict(cache, pos=jnp.asarray(7, jnp.int32))
    o_jit = step(dict(cc), x1)
    o_ref, _ = attn_mod.gqa_apply(p, cfg_dir, x1, positions=positions,
                                  cache=dict(cc))
    np.testing.assert_allclose(np.asarray(o_jit), np.asarray(o_ref),
                               rtol=5e-6, atol=5e-6)
    [plan] = [pl for pl in reg.plans() if pl["kernel"] == "decode_attention"]
    assert plan["args"][2] == 32          # bucket_seq(max_len)


# ------------------------------------------------- SSD final state / decode --
def test_ssd_final_state_matches_numpy_executor():
    """The final-state output of the SSD builder is the carry state the
    numpy executor threads — across both lowering backends."""
    from repro import compiler
    from repro.core import executor
    from repro.core.autopump import BUILDERS
    rng = np.random.default_rng(5)
    inputs = {"x": rng.integers(-2, 3, (2, 16, 2, 4)).astype(np.float32),
              "dt": np.abs(rng.integers(0, 3, (2, 16, 2))) * 0.25 + 0.25,
              "a": -(np.abs(rng.integers(0, 3, (2,))) * 0.25 + 0.25),
              "bmat": rng.integers(-2, 3, (2, 16, 2, 4)).astype(np.float32),
              "cmat": rng.integers(-2, 3, (2, 16, 2, 4)).astype(np.float32)}
    inputs = {k: np.asarray(v, np.float32) for k, v in inputs.items()}
    for backend in ("jax", "pallas"):
        g, _ = BUILDERS["ssd_scan"](2, 16, 2, 4, 4, chunk=4,
                                    final_state=True)
        kern = compiler.compile(g, factor=2, backend=backend, cache=False,
                                memoize=False)
        out = kern(inputs)
        gold = executor.run(kern.graph, dict(inputs))
        for name in ("y", "state"):
            np.testing.assert_allclose(
                np.asarray(out[name]), gold[name], rtol=5e-6, atol=5e-6,
                err_msg=f"{name} ({backend})")


def test_ssd_cached_prefill_final_state_matches_xla():
    """Cached SSM prefill through the final-state kernel (measure route)
    matches the _ssd_xla reference — y and the decode state both."""
    set_default_registry(PlanRegistry(pump=1, cache=False))
    from repro.models import ssm as ssm_mod
    cfg = dataclasses.replace(load_arch("mamba2-1.3b", smoke=True),
                              ssm_impl="pallas")
    cfg_dir = dataclasses.replace(cfg, kernel_plan="direct")
    p = ssm_mod.mamba2_init(jax.random.PRNGKey(1), cfg)
    cache0 = ssm_mod.mamba2_cache_init(cfg, 2, jnp.float32)
    x = _ints((2, 16, cfg.d_model), 7)
    y_kern, nc_kern = ssm_mod.mamba2_apply(p, cfg, x, cache=dict(cache0))
    y_ref, nc_ref = ssm_mod.mamba2_apply(p, cfg_dir, x, cache=dict(cache0))
    np.testing.assert_allclose(np.asarray(y_kern), np.asarray(y_ref),
                               rtol=5e-6, atol=5e-6)
    np.testing.assert_allclose(np.asarray(nc_kern["state"]),
                               np.asarray(nc_ref["state"]),
                               rtol=2e-5, atol=5e-6)


def test_ssd_decode_step_matches_jnp_reference():
    set_default_registry(PlanRegistry(pump=1, cache=False))
    from repro.models import ssm as ssm_mod
    cfg = dataclasses.replace(load_arch("mamba2-1.3b", smoke=True),
                              ssm_impl="pallas")
    cfg_dir = dataclasses.replace(cfg, kernel_plan="direct")
    p = ssm_mod.mamba2_init(jax.random.PRNGKey(1), cfg)
    cache0 = ssm_mod.mamba2_cache_init(cfg, 2, jnp.float32)
    cache = dict(cache0, state=_ints(cache0["state"].shape, 4),
                 conv=_ints(cache0["conv"].shape, 5))
    x1 = _ints((2, 1, cfg.d_model), 6)
    y_kern, nc_kern = ssm_mod.mamba2_apply(p, cfg, x1, cache=dict(cache))
    y_ref, nc_ref = ssm_mod.mamba2_apply(p, cfg_dir, x1, cache=dict(cache))
    np.testing.assert_allclose(np.asarray(y_kern), np.asarray(y_ref),
                               rtol=5e-6, atol=5e-6)
    np.testing.assert_allclose(np.asarray(nc_kern["state"]),
                               np.asarray(nc_ref["state"]),
                               rtol=5e-6, atol=5e-6)


# --------------------------------------------------- registry serving glue --
def test_registry_stats_split_decode_from_prefill():
    """Decode-kernel lookups are counted under their own phase so a cold
    decode bucket is visible in the serve printout at a glance."""
    reg = PlanRegistry(pump=1, cache=False)
    q = _ints((1, 2, 8), 1)
    kv = _ints((1, 2, 16, 8), 2)
    reg.decode_attention(q, kv, kv, 5)                    # miss
    reg.decode_attention(q, kv, kv, 6)                    # same bucket: hit
    reg.flash_attention(_ints((1, 2, 16, 8), 3), kv, kv, causal=True)
    d = reg.stats.as_dict()
    assert d["decode"] == {"hits": 1, "misses": 1, "fallbacks": 0}
    assert d["prefill"] == {"hits": 0, "misses": 1, "fallbacks": 0}
    assert d["hits"] == 1 and d["misses"] == 2


def test_warmup_surfaces_each_unique_compile_warning_once():
    """A bucket-grid warmup sweep re-compiles the same kernel per bucket;
    identical degradation warnings must print once per sweep, not once per
    compile."""
    reg = PlanRegistry(pump=2, cache=False)   # factor 2, no autotune
    # grouped B/C (n_groups < h) puts a table on the innermost grid symbol,
    # so mode-T splitting warns 'cannot split hi' for every bucket compiled
    reqs = [("ssd_decode", dict(b=b, h=4, p=8, n=4, n_groups=2,
                                dtype="float32")) for b in (1, 3)]
    with pytest.warns(UserWarning) as rec:
        report = reg.warmup(reqs)
    assert len(report) == 2 and reg.stats.misses == 2
    hits = [str(w.message) for w in rec
            if "cannot split" in str(w.message)]
    assert len(hits) == 1, hits


def test_decode_attention_per_row_positions_stay_kernelized():
    """A (B,) pos vector buckets on the furthest row and runs the kernel
    (no silent jnp fallback); each row's own mask cuts its prefix."""
    from repro.compiler.registry import _decode_reference
    reg = PlanRegistry(pump=1, cache=False)
    q = _ints((2, 2, 8), 1)
    kv = _ints((2, 2, 32, 8), 2)
    pos = jnp.asarray([3, 20], jnp.int32)
    out = reg.decode_attention(q, kv, kv, pos)
    assert reg.stats.fallbacks == 0
    [plan] = [pl for pl in reg.plans() if pl["kernel"] == "decode_attention"]
    assert plan["args"][2] == 32          # bucket_seq(max(pos) + 1)
    ref = _decode_reference(q, kv, kv, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-6, atol=5e-6)


def test_ssd_scan_final_state_fallback_degrades_not_crashes(monkeypatch):
    """A compile failure on the final-state route must degrade to the jnp
    recurrence (which does produce the state), not re-raise through the
    compiler-only ops entry."""
    reg = PlanRegistry(pump=1, cache=False)

    def boom(*a, **kw):
        raise RuntimeError("forced compile failure")

    monkeypatch.setattr(reg, "kernel", boom)
    x = _ints((1, 8, 2, 4), 1)
    rng = np.random.default_rng(2)
    dt = jnp.asarray(np.abs(rng.integers(0, 3, (1, 8, 2))) * 0.25 + 0.25,
                     dtype=jnp.float32)
    A = jnp.asarray(-(np.abs(rng.integers(0, 3, (2,))) * 0.25 + 0.25),
                    dtype=jnp.float32)
    B = _ints((1, 8, 2, 4), 3)
    C = _ints((1, 8, 2, 4), 4)
    with pytest.warns(UserWarning, match="plain jnp scan"):
        y, st = reg.ssd_scan(x, dt, A, B, C, chunk=4, final_state=True)
    assert reg.stats.fallbacks == 1
    from repro.kernels import ops
    y_ref, st_ref = ops.ssd_scan(x, dt, A, B, C, chunk=4, final_state=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=5e-6, atol=5e-6)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               rtol=5e-6, atol=5e-6)


def test_engine_warms_decode_buckets():
    """The Engine's launch warmup covers the decode bucket grid: the jit'd
    decode step's trace-time lookups are pure hits."""
    from repro.models import model as model_mod
    from repro.serve.engine import Engine, ServeConfig
    set_default_registry(PlanRegistry(pump=1, cache=False))
    cfg = dataclasses.replace(load_arch("qwen3-0.6b", smoke=True),
                              attention_impl="pallas")
    params = model_mod.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, ServeConfig(batch=2, max_len=16))
    assert any(r["kernel"] == "decode_attention" for r in eng.warmup_report)
    reg = default_registry()
    before = reg.stats.phase["decode"]["misses"]
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 cfg.vocab_size)
    eng.generate(prompts, 3)
    assert reg.stats.phase["decode"]["misses"] == before
    assert reg.stats.phase["decode"]["hits"] >= 1
