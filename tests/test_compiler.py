"""repro.compiler tests: pass registry/pipeline, lowering backends vs the
numpy reference executor (differential — driven by the reusable harness in
``tests/differential.py``), persistent compile cache (including corruption
negative paths), the two passes (stream-fusion, fifo-depth), and the
fused-region Pallas emission backend (region partitioning, blocked-view
derivation, temporal grid axis, carry-aware emission, measured-runtime
autotune).

Differential data is integer-valued float32 so every backend computes the
same exactly-representable values regardless of reduction order — the
lowerings are required to be *bit-exact* against the reference executor
wherever the kernel math permits (see ``tests/differential.py`` for the
exp caveat on flash attention / SSD).
"""
import json

import numpy as np
import pytest

import jax.numpy as jnp

from repro import compiler
from repro.compiler import (CompileCache, LoweringError, Pipeline,
                            PASS_REGISTRY, make_pass)
from repro.compiler.cache import graph_fingerprint
from repro.compiler.lowering import _temporal_rechunk
from repro.compiler.passes import FifoDepthPass, StreamFusionPass
from repro.core import (AccessPattern, Affine, Domain, Graph, NodeKind,
                        apply_multipump, apply_streaming, autopump, executor)
from repro.core.autopump import BUILDERS
from repro.core.multipump import pump_spec_for
from repro.core.symbolic import blocked_access

from differential import FACTORS, MODES, Case, cases as diff_cases, run_case
from hypothesis_compat import given, settings, st


def _ints(rng, shape, lo=-4, hi=5):
    return rng.integers(lo, hi, shape).astype(np.float32)


def chain_graph(n=32, v=4):
    """Two computes through an intermediate memory: z = (x + 1) * 2."""
    g = Graph("chain")
    g.memory("x", (n,))
    g.memory("t", (n,))
    g.memory("z", (n,))
    dom = Domain.of(("i", 0, n // v))
    acc = AccessPattern(dom, (Affine.of("i", v),), width=v)
    g.compute("add1", dom, fn=lambda in0: {"out0": in0 + 1.0}, vector_width=v)
    g.compute("scale", dom, fn=lambda in0: {"out0": in0 * 2.0}, vector_width=v)
    g.connect("x", "add1", acc)
    g.connect("add1", "t", acc)
    g.connect("t", "scale", acc)
    g.connect("scale", "z", acc)
    return g


# ------------------------------------------------- differential harness --
# the copy-pasted per-kernel differential tests were replaced by the
# registry-driven sweep in tests/differential.py: every BUILDERS entry ×
# backend × M ∈ {1,2,4} × modes {T,R}, asserted against the reference
# executor (bit-exact where the math permits) and an independent numpy gold
_DIFF0 = diff_cases(0)
_DIFF1 = {k: v for k, v in diff_cases(1).items()
          if k in ("flash_attention", "ssd_scan", "grouped_gemm",
                   "grouped_gemm_ragged", "decode_attention",
                   "ssd_scan_final", "ssd_decode")}


@pytest.mark.parametrize("backend", ["reference", "jax", "pallas"])
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("factor", FACTORS)
@pytest.mark.parametrize("kernel", sorted(_DIFF0))
def test_differential_all_builders(kernel, factor, mode, backend):
    run_case(_DIFF0[kernel], factor, mode, backend)


@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("factor", FACTORS)
@pytest.mark.parametrize("kernel", sorted(_DIFF1))
def test_differential_second_shapes(kernel, factor, mode, backend):
    """Acceptance: the three subsumed kernels hold on a second, structurally
    different shape (GQA folding, grouped B/C, different raggedness)."""
    run_case(_DIFF1[kernel], factor, mode, backend)


@settings(max_examples=8, deadline=None)
@given(nblocks=st.integers(1, 4), v=st.integers(1, 8))
def test_differential_vecadd_shape_property(nblocks, v):
    """Shape-parametrized via hypothesis (skips without it installed)."""
    n = nblocks * v * 2
    run_case(Case("vecadd", (n,), dict(vector_width=v),
                  {"x": (n,), "y": (n,)}, ("z",)), 2, "T", "jax")


@settings(max_examples=6, deadline=None)
@given(sizes=st.lists(st.integers(1, 3), min_size=1, max_size=3))
def test_differential_ragged_shape_property(sizes):
    from differential import _grouped_gold_ragged
    sizes = tuple(s * 8 for s in sizes)
    rows = sum(sizes)
    run_case(Case("grouped_gemm", (len(sizes), 16, 8, 8),
                  dict(bc=8, bf=8, bd=8, group_sizes=sizes, vector_width=8),
                  {"x": (rows, 8), "w": (len(sizes), 8, 8)}, ("o",),
                  gold=_grouped_gold_ragged(sizes)), 2, "T", "pallas")


def test_reference_backend_matches_jax_backend(tmp_path):
    g, _ = BUILDERS["vecadd"](64, vector_width=8)
    rng = np.random.default_rng(7)
    inputs = {"x": _ints(rng, 64), "y": _ints(rng, 64)}
    cache = CompileCache(tmp_path / "c.json")
    kj = compiler.compile(g, factor=2, backend="jax", cache=cache,
                          memoize=False)
    kr = compiler.compile(g, factor=2, backend="reference", cache=cache,
                          memoize=False)
    np.testing.assert_array_equal(np.asarray(kj(inputs)["z"]),
                                  kr(inputs)["z"])


# --------------------------------------------- pallas backend: structure --
def test_region_partitioning_and_emission_tiers(tmp_path):
    """Adapters/streams fuse into one region per compute chain; emission
    picks blockloop for tile-able kernels and gather for the
    dependency-carrying floyd pivot loop."""
    g, _ = BUILDERS["matmul"](32, 32, 32, bm=16, bn=16, bk=16, vector_width=8)
    kern = compiler.compile(g, factor=2, backend="pallas",
                            cache=CompileCache(tmp_path / "c.json"),
                            memoize=False)
    assert list(kern.report.emission.values())[0]["tier"] == "blockloop"
    # the temporal axis is the innermost grid dim, and kk + _pump reduce
    em = list(kern.report.emission.values())[0]
    assert em["grid"][-1][0] == "_pump" and em["grid"][-1][1] == 2
    assert "_pump" in em["reduce"] and "kk" in em["reduce"]

    g2, _ = BUILDERS["floyd_warshall"](16)
    kern2 = compiler.compile(g2, factor=2, backend="pallas",
                             cache=CompileCache(tmp_path / "c.json"),
                             memoize=False)
    assert list(kern2.report.emission.values())[0]["tier"] == "gather"
    assert kern2.report.warnings                    # downgrade is visible


def test_pallas_interpret_emission_matches_reference(tmp_path):
    """Real pl.pallas_call (interpret mode on CPU) for pallas-expressible
    regions, bit-exact in both modes."""
    rng = np.random.default_rng(3)
    inputs = {"a": rng.integers(-3, 4, (32, 32)).astype(np.float32),
              "b": rng.integers(-3, 4, (32, 32)).astype(np.float32)}
    for mode in ("T", "R"):
        g, _ = BUILDERS["matmul"](32, 32, 32, bm=16, bn=16, bk=16,
                                  vector_width=8)
        kern = compiler.compile(g, factor=2, mode=mode, backend="pallas",
                                pallas_mode="interpret",
                                cache=CompileCache(tmp_path / "c.json"),
                                memoize=False)
        assert list(kern.report.emission.values())[0]["tier"] == "pallas"
        out = np.asarray(kern(inputs)["c"])
        np.testing.assert_array_equal(
            out, executor.run(kern.graph, dict(inputs))["c"])
        np.testing.assert_array_equal(out, inputs["a"] @ inputs["b"])


def test_carry_region_emission_structure(tmp_path):
    """Carry regions emit the carry-aware tier: flash attention's online
    softmax becomes a multi-output carryloop whose carry axis is the
    innermost grid dimension; mode T splits it into transactions × beats."""
    g, _ = BUILDERS["flash_attention"](1, 2, 32, 32, 8, bq=16, bkv=8,
                                       vector_width=8)
    kern = compiler.compile(g, factor=2, backend="pallas",
                            cache=CompileCache(tmp_path / "c.json"),
                            memoize=False)
    em = list(kern.report.emission.values())[0]
    assert em["tier"] == "carryloop"
    assert em["carry"] == ["ji", "_pump"]          # M beats continue the sweep
    assert em["grid"][-1] == ["_pump", 2]
    assert set(em["outputs"]) == {"o", "m", "l"}   # multi-output region

    # mode R: the _pump axis sits OUTSIDE the carry sweep (sub-tiles run
    # their own full sweeps) and narrows the labelled 'q' axis
    g2, _ = BUILDERS["flash_attention"](1, 2, 32, 32, 8, bq=16, bkv=8,
                                        vector_width=8)
    kern2 = compiler.compile(g2, factor=2, mode="R", backend="pallas",
                             cache=CompileCache(tmp_path / "c.json"),
                             memoize=False)
    em2 = list(kern2.report.emission.values())[0]
    syms2 = [s for s, _e in em2["grid"]]
    assert em2["carry"] == ["ji"]
    assert syms2.index("_pump") < syms2.index("ji")


def test_carry_pallas_interpret_emission(tmp_path):
    """Real pl.pallas_call emission for carry regions (interpret mode):
    state in VMEM scratch, pl.when-gated init/finalize — the hand-written
    flash-attention schedule, derived from the IR."""
    for kernel in ("flash_attention", "ssd_scan"):
        case = _DIFF0[kernel]
        run_case(case, 2, "T", "pallas", pallas_mode="interpret")
        g, _ = BUILDERS[case.kernel](*case.args, **case.kwargs)
        kern = compiler.compile(g, factor=2, backend="pallas",
                                pallas_mode="interpret",
                                cache=CompileCache(tmp_path / "c.json"),
                                memoize=False)
        assert list(kern.report.emission.values())[0]["tier"] == "pallas"


def test_ragged_blockspec_derivation():
    """Group-indexed (table) access decomposes into a blocked view whose
    offsets carry the lookup — and still divides into block units, so the
    ragged grouped gemm gets a real derivable BlockSpec."""
    g, _ = BUILDERS["grouped_gemm"](2, 32, 16, 8, bc=8, bf=8, bd=8,
                                    group_sizes=(16, 24))
    acc_x = g.in_edges("expert_tile")[0].access
    ba = blocked_access(acc_x, (40, 16))
    assert ba.block == (8, 8)
    assert ba.grid_symbols == ("ti", "ji", "ki")
    assert ba.offsets[0].tables               # row offsets are a table term
    assert ba.block_unit_offsets() is not None
    # the w operand maps each tile to its expert slab via a table
    acc_w = g.in_edges("expert_tile")[1].access
    bw = blocked_access(acc_w, (2, 16, 8))
    assert bw.offsets[0].tables and bw.block == (1, 8, 8)


def test_blocked_access_derivation():
    """Symbolic access patterns decompose into block/grid/offset views."""
    g, _ = BUILDERS["matmul"](64, 64, 64, bm=16, bn=16, bk=16, vector_width=8)
    acc_a = g.in_edges("mxu_tile")[0].access
    ba = blocked_access(acc_a, (64, 64))
    assert ba.block == (16, 16)
    assert ba.grid_symbols == ("i", "j", "kk")
    assert ba.block_unit_offsets() is not None      # pallas-expressible

    # stencil halo: overlapping windows are blockable but not block-unit
    g2, _ = BUILDERS["stencil"](10, 8, 8)
    ba2 = blocked_access(g2.in_edges("plane_update")[0].access, (10, 8, 8))
    assert ba2.block == (3, 8, 8)
    assert ba2.block_unit_offsets() is None


def test_pallas_backend_on_fused_chain(tmp_path):
    """Multi-compute regions (post stream-fusion) lower through the pallas
    backend's gather tier and stay value-exact."""
    g = chain_graph(32, 4)
    kern = compiler.compile(g, factor=2, backend="pallas",
                            cache=CompileCache(tmp_path / "c.json"),
                            memoize=False)
    assert "t" not in kern.graph.nodes
    rng = np.random.default_rng(5)
    x = _ints(rng, 32)
    out = np.asarray(kern({"x": x})["z"])
    np.testing.assert_array_equal(out, (x + 1.0) * 2.0)


def test_pallas_region_order_respects_memory_deps(tmp_path):
    """A region reading memory m must run after the region writing m, even
    when declaration/toposort position order says otherwise (regression:
    emission used to schedule by first-compute position)."""
    g = Graph("xregion")
    g.memory("y", (8,))
    g.memory("x", (8,))
    g.memory("m", (8,))
    g.memory("z", (8,))
    dom = Domain.of(("i", 0, 8))
    acc = AccessPattern(dom, (Affine.of("i"),))
    rev = AccessPattern(dom, (Affine.constant(7) - Affine.of("i"),))
    # consumer region: c0 -> c1, where only the *second* compute reads m
    # (c0's node-toposort position precedes the producer a0's, so position-
    # based region scheduling would run this region first, against zeros;
    # the reversed read defeats streaming/fusion, so m stays a boundary)
    g.compute("c0", dom, fn=lambda in0: {"out0": in0 + 1.0})
    g.compute("c1", dom, fn=lambda in0, in1: {"out0": in0 + in1})
    g.connect("x", "c0", acc)
    g.connect("c0", "c1")
    g.connect("m", "c1", rev)
    g.connect("c1", "z", acc)
    # producer region declared last: m = 2 * y
    g.compute("a0", dom, fn=lambda in0: {"out0": in0 * 2.0})
    g.connect("y", "a0", acc)
    g.connect("a0", "m", acc)

    from repro.core.executor import _toposort
    from repro.compiler.pallas_backend import partition_regions
    order = _toposort(g)
    assert order.index("c0") < order.index("a0")    # the trap this guards
    assert [r.name for r in partition_regions(g)] == ["a0", "c0"]

    rng = np.random.default_rng(9)
    inputs = {"x": _ints(rng, 8), "y": _ints(rng, 8)}
    kern = compiler.compile(g, factor=1, backend="pallas",
                            cache=CompileCache(tmp_path / "c.json"),
                            memoize=False)
    out = np.asarray(kern(inputs)["z"])
    gold = (inputs["x"] + 1.0) + (2.0 * inputs["y"])[::-1]
    np.testing.assert_array_equal(out, gold)
    np.testing.assert_array_equal(
        out, executor.run(kern.graph, dict(inputs))["z"])


# ------------------------------------------------ measured-runtime autotune --
def test_autotune_measure_and_cache_replay(tmp_path):
    path = tmp_path / "cache.json"
    g, est = BUILDERS["vecadd"](256, vector_width=8)
    k1 = compiler.compile(g, factor="auto", estimate=est, backend="pallas",
                          autotune="measure", cache=CompileCache(path),
                          memoize=False)
    at = k1.report.autotune
    assert at["policy"] == "measure" and at["replayed"] is False
    assert len(at["timings_us"]) >= 2               # measured >= 2 candidates
    assert at["winner"] == k1.spec.factor

    # second compile (fresh cache instance ≙ fresh process): disk hit that
    # replays the measured plan without re-measuring
    g2, _ = BUILDERS["vecadd"](256, vector_width=8)
    k2 = compiler.compile(g2, factor="auto", estimate=est, backend="pallas",
                          autotune="measure", cache=CompileCache(path),
                          memoize=False)
    assert k2.report.served_from == "disk"
    assert k2.report.autotune["replayed"] is True
    assert k2.spec.factor == k1.spec.factor


def test_autotune_measure_requires_executable_backend():
    g, est = BUILDERS["vecadd"](64, vector_width=8)
    with pytest.raises(ValueError):
        compiler.compile(g, estimate=est, backend="none",
                         autotune="measure", cache=False)


def test_autotune_key_distinct_from_capacity_plan(tmp_path):
    """A measured winner and a capacity-model guess for the same request
    must not collide in the persistent cache."""
    path = tmp_path / "cache.json"
    g, est = BUILDERS["vecadd"](256, vector_width=8)
    compiler.compile(g, factor="auto", estimate=est, backend="pallas",
                     cache=CompileCache(path), memoize=False)
    cache = CompileCache(path)
    k = compiler.compile(g, factor="auto", estimate=est, backend="pallas",
                         autotune="measure", cache=cache, memoize=False)
    assert k.report.served_from is None             # not the heuristic entry
    assert k.report.autotune and k.report.autotune["replayed"] is False


def test_ops_pump_measure_routes_through_backend(tmp_path, monkeypatch):
    """kernels.ops pump='measure' compiles the kernel's IR graph through the
    pallas backend with measured autotuning and reuses the winning factor."""
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    import jax.numpy as jnp
    from repro.kernels import ops
    x = jnp.arange(512, dtype=jnp.float32)
    y = jnp.ones(512, jnp.float32)
    out = ops.vecadd(x, y, pump="measure")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x + y))
    assert (tmp_path / "compile_cache.json").exists()


# --------------------------------------------- scatter-duplicate rejection --
def test_duplicate_scatter_raises_lowering_error(tmp_path):
    """A write pattern revisiting addresses (reduction dim absent from the
    output) must fail loudly instead of silently last-write-wins — and the
    error must carry the offending producer→memory edge by name."""
    g = Graph("dup")
    g.memory("x", (8,))
    g.memory("z", (8,))
    dom = Domain.of(("k", 0, 2))
    g.compute("badwrite", dom, fn=lambda in0: {"out0": in0})
    g.connect("x", "badwrite", AccessPattern(dom, (Affine.of("k", 4),),
                                             width=4))
    g.connect("badwrite", "z", AccessPattern(dom, (Affine.constant(0),),
                                             width=4))
    for backend in ("jax", "pallas"):
        with pytest.raises(LoweringError, match="duplicate address") as ei:
            compiler.compile(g, factor=1, backend=backend,
                             cache=False, memoize=False)
        assert "badwrite" in str(ei.value) and "z" in str(ei.value)


# ------------------------------------------------ cache corruption paths --
@pytest.mark.parametrize("payload", [
    "{not valid json!!",              # syntactically broken
    '{"version": 1, "entries"',       # truncated mid-write
    json.dumps([1, 2, 3]),            # wrong top-level schema
    json.dumps({"version": 1, "entries": {"k": "not-a-plan"}}),
])
def test_corrupted_cache_falls_back_to_cold_compile(tmp_path, payload):
    """A corrupted/truncated compile-cache file must degrade to a cold
    compile (cache-off behaviour), never crash the build."""
    path = tmp_path / "cache.json"
    path.write_text(payload)
    g, _ = BUILDERS["vecadd"](64, vector_width=8)
    kern = compiler.compile(g, factor=2, cache=CompileCache(path),
                            memoize=False)
    assert kern.report.served_from is None         # cold, not crashed
    x = np.arange(64, dtype=np.float32)
    np.testing.assert_array_equal(
        np.asarray(kern({"x": x, "y": x})["z"]), x + x)


def test_corrupted_cache_entry_value_is_a_miss(tmp_path):
    """An entry whose *value* lost its factor (schema drift, hand edits)
    must be treated as a miss and recompiled cold."""
    path = tmp_path / "cache.json"
    g, _ = BUILDERS["vecadd"](64, vector_width=8)
    compiler.compile(g, factor=2, cache=CompileCache(path), memoize=False)
    blob = json.loads(path.read_text())
    blob["entries"] = {k: {"mode": "T"} for k in blob["entries"]}  # no factor
    path.write_text(json.dumps(blob))
    kern = compiler.compile(g, factor=2, cache=CompileCache(path),
                            memoize=False)
    assert kern.report.served_from is None
    assert kern.spec.factor == 2


# ------------------------------------------------- mode-R axis narrowing --
def _modeR_regression_graph(labelled: bool):
    """z[j·b+r] = c[j·b+r] · Σ x[j·b : (j+1)·b] — both operands walk the
    same offset expression with the same block size, but only ``c``'s axis
    corresponds to the output: narrowing ``x`` splits the Σ and corrupts
    the result.  The old grid-symbol heuristic (and even offset-expression
    matching) narrows both; the declared axis correspondence narrows only
    the labelled operand."""
    n, b = 16, 8
    g = Graph("modeR")
    g.memory("c", (n,))
    g.memory("x", (n,))
    g.memory("z", (n,))
    dom_b = Domain.of(("j", 0, n // b), ("r", 0, b))
    dom_j = Domain.of(("j", 0, n // b))
    acc_elem = AccessPattern(dom_b, (Affine.of("j", b) + Affine.of("r"),),
                             width=1)
    acc_block = AccessPattern(dom_j, (Affine.of("j", b),), width=b)

    def fn(in0, in1):
        c2 = in0.reshape(n // b, b)
        x2 = in1.reshape(n // b, b)
        return {"out0": (c2 * x2.sum(axis=1, keepdims=True)).reshape(-1)}

    tile_fn = lambda in0, in1: {"out0": in0 * in1.sum()}   # noqa: E731
    meta = dict(fn=fn, tile_fn=tile_fn, vector_width=8)
    if labelled:
        meta["axes"] = dict(ins=({0: "n"}, {}), outs=({0: "n"},),
                            carry=(), narrow="n")
    g.compute("scalecol", dom_j, **meta)
    g.connect("c", "scalecol", acc_elem)
    g.connect("x", "scalecol", acc_block)
    g.connect("scalecol", "z", acc_elem)
    return g


def test_mode_r_narrowing_uses_axis_correspondence(tmp_path):
    """Regression for the grid-symbol narrowing heuristic: with the compute's
    declared axis correspondence, mode R narrows only the operand dimension
    that actually corresponds to the output axis — the whole-block operand
    (a Σ over the block) stays wide, and the result stays bit-exact."""
    rng = np.random.default_rng(17)
    inputs = {"c": _ints(rng, 16), "x": _ints(rng, 16)}
    gold = (inputs["c"].reshape(2, 8)
            * inputs["x"].reshape(2, 8).sum(axis=1, keepdims=True)
            ).reshape(-1)

    g = _modeR_regression_graph(labelled=True)
    kern = compiler.compile(g, factor=2, mode="R", backend="pallas",
                            cache=CompileCache(tmp_path / "c.json"),
                            memoize=False)
    em = list(kern.report.emission.values())[0]
    assert em["pump"] == 2                        # temporal axis realized
    out = np.asarray(kern(inputs)["z"])
    np.testing.assert_array_equal(out, gold)
    np.testing.assert_array_equal(
        out, executor.run(kern.graph, dict(inputs))["z"])

    # the unlabelled graph shows why the heuristic cannot be fixed without
    # the correspondence: both operands walk the same offset expression
    # with the same block size, so narrowing picks both and splits the Σ
    g2 = _modeR_regression_graph(labelled=False)
    kern2 = compiler.compile(g2, factor=2, mode="R", backend="pallas",
                             cache=CompileCache(tmp_path / "c2.json"),
                             memoize=False)
    assert not np.array_equal(np.asarray(kern2(inputs)["z"]), gold)


# --------------------------------------------- misaligned-pump visibility --
def test_misaligned_pump_factor_warns_in_report(tmp_path):
    """factor=3 does not divide the 64-element FIFO sequence: the gearbox
    degrades to pass-through and the report says so (counted, not silent)."""
    g, _ = BUILDERS["vecadd"](64, vector_width=2)
    kern = compiler.compile(g, factor=3, backend="jax",
                            cache=CompileCache(tmp_path / "c.json"),
                            memoize=False)
    assert kern.report.warning_count > 0
    assert any("not divisible by pump factor 3" in w
               for w in kern.report.warnings)
    assert f"warn={kern.report.warning_count}" in kern.report.summary()
    # degraded, but still value-exact
    x = np.arange(64, dtype=np.float32)
    y = np.ones(64, dtype=np.float32)
    np.testing.assert_array_equal(np.asarray(kern({"x": x, "y": y})["z"]),
                                  x + y)


# ------------------------------------------------- issuer/packer identity --
def test_issuer_packer_round_trip_identity():
    x = np.arange(64, dtype=np.float32)
    for m in (1, 2, 4, 8):
        np.testing.assert_array_equal(
            np.asarray(_temporal_rechunk(jnp.asarray(x), m)), x)
    # issuer ∘ packer over the same factor is the identity (paper's gearbox)
    z = _temporal_rechunk(_temporal_rechunk(jnp.asarray(x), 4), 4)
    np.testing.assert_array_equal(np.asarray(z), x)


# ------------------------------------------------------------ new passes --
def test_stream_fusion_collapses_memory_roundtrip():
    g = chain_graph(32, 4)
    sg, _ = apply_streaming(g)
    assert "t" in sg.nodes
    fuse = StreamFusionPass()
    ok, why = fuse.can_apply(sg)
    assert ok, why
    fg, rep = fuse.apply(sg)
    assert len(rep.fused) == 1
    assert "t" not in fg.nodes                      # memory round-trip gone
    assert len(fg.streams()) == len(sg.streams()) - 1
    # value preservation through the fused pipeline
    rng = np.random.default_rng(3)
    x = _ints(rng, 32)
    out = executor.run(fg, {"x": x})["z"]
    np.testing.assert_array_equal(out, (x + 1.0) * 2.0)


def test_stream_fusion_respects_keep_marker():
    g = chain_graph(32, 4)
    g.nodes["t"].meta["keep"] = True
    sg, _ = apply_streaming(g)
    ok, _ = StreamFusionPass().can_apply(sg)
    assert not ok


def test_fused_then_pumped_chain_differential(tmp_path):
    g = chain_graph(32, 4)
    kern = compiler.compile(g, factor=2,
                            cache=CompileCache(tmp_path / "c.json"),
                            memoize=False)
    assert kern.report.record("stream-fusion").applied
    assert "t" not in kern.graph.nodes
    rng = np.random.default_rng(4)
    x = _ints(rng, 32)
    out = np.asarray(kern({"x": x})["z"])
    np.testing.assert_array_equal(out, (x + 1.0) * 2.0)
    np.testing.assert_array_equal(out, executor.run(kern.graph, {"x": x})["z"])


def test_fifo_depth_sized_from_pump_factor():
    g, _ = BUILDERS["vecadd"](64, vector_width=8)
    sg, _ = apply_streaming(g)
    pg, _ = apply_multipump(sg, factor=4)
    assert all(s.depth == 2 for s in pg.streams())  # seed default
    out, rep = FifoDepthPass().apply(pg)
    assert rep.resized
    # boundary FIFOs hold a wide transaction: depth = 2 * M
    for s in out.streams():
        assert s.depth == 8, s.name
    # unpumped graphs keep the double-buffer minimum
    out2, _ = FifoDepthPass().apply(sg)
    assert all(s.depth == 2 for s in out2.streams())


def test_stream_fusion_preserves_operand_order():
    """The fused edge must take the consumed edge's position: executors bind
    compute operands (in0, in1, ...) by edge insertion order."""
    n, v = 32, 4
    g = Graph("oporder")
    g.memory("x", (n,))
    g.memory("t", (n,))
    g.memory("y", (n,))
    g.memory("z", (n,))
    dom = Domain.of(("i", 0, n // v))
    acc = AccessPattern(dom, (Affine.of("i", v),), width=v)
    g.compute("add1", dom, fn=lambda in0: {"out0": in0 + 1.0}, vector_width=v)
    # 'sub' reads the intermediate t as in0 and fresh input y as in1
    g.compute("sub", dom, fn=lambda in0, in1: {"out0": in0 - in1},
              vector_width=v)
    g.connect("x", "add1", acc)
    g.connect("add1", "t", acc)
    g.connect("t", "sub", acc)
    g.connect("y", "sub", acc)
    g.connect("sub", "z", acc)

    rng = np.random.default_rng(11)
    x, y = _ints(rng, n), _ints(rng, n, 50, 100)
    gold = (x + 1.0) - y
    sg, _ = apply_streaming(g)
    fg, rep = StreamFusionPass().apply(sg)
    assert rep.fused
    np.testing.assert_array_equal(
        executor.run(fg, {"x": x, "y": y})["z"], gold)


def test_stream_fusion_cascaded_chains():
    """Two chains sharing a stream must fuse iteratively, not crash."""
    n, v = 32, 4
    g = Graph("cascade")
    g.memory("x", (n,))
    g.memory("t1", (n,))
    g.memory("t2", (n,))
    g.memory("z", (n,))
    dom = Domain.of(("i", 0, n // v))
    acc = AccessPattern(dom, (Affine.of("i", v),), width=v)
    g.compute("a", dom, fn=lambda in0: {"out0": in0 + 1.0}, vector_width=v)
    g.compute("b", dom, fn=lambda in0: {"out0": in0 * 2.0}, vector_width=v)
    g.compute("c", dom, fn=lambda in0: {"out0": in0 - 3.0}, vector_width=v)
    g.connect("x", "a", acc)
    g.connect("a", "t1", acc)
    g.connect("t1", "b", acc)
    g.connect("b", "t2", acc)
    g.connect("t2", "c", acc)
    g.connect("c", "z", acc)
    sg, _ = apply_streaming(g)
    fg, rep = StreamFusionPass().apply(sg)
    assert len(rep.fused) == 2
    assert "t1" not in fg.nodes and "t2" not in fg.nodes
    rng = np.random.default_rng(12)
    x = _ints(rng, n)
    np.testing.assert_array_equal(executor.run(fg, {"x": x})["z"],
                                  (x + 1.0) * 2.0 - 3.0)


def test_shared_stream_widened_once():
    """A stream bordering the pumped region on both sides (post-fusion) must
    be widened by M, not M^2 — M^2 inflates the resource model and can make
    check_multipump spuriously reject a feasible factor."""
    g = chain_graph(32, 4)
    sg, _ = apply_streaming(g)
    fg, _ = StreamFusionPass().apply(sg)
    pg, rep = apply_multipump(fg, factor=4)
    assert rep.applied
    shared = [s for s in pg.streams() if s.name == "s_add1_t"]
    assert shared and shared[0].elem_width == 4 * 4   # v * M, not v * M^2


def test_memo_distinguishes_closure_values(tmp_path):
    """Structurally identical graphs whose fn closures capture different
    values must not share a memo entry."""
    compiler.clear_memo()

    def build(scale):
        g = Graph("closure")
        g.memory("x", (8,))
        g.memory("z", (8,))
        dom = Domain.of(("i", 0, 8))
        acc = AccessPattern(dom, (Affine.of("i"),))
        g.compute("mul", dom, fn=lambda in0: {"out0": in0 * scale})
        g.connect("x", "mul", acc)
        g.connect("mul", "z", acc)
        return g

    cache = CompileCache(tmp_path / "c.json")
    x = np.arange(8, dtype=np.float32)
    k2 = compiler.compile(build(2.0), factor=1, cache=cache)
    k3 = compiler.compile(build(3.0), factor=1, cache=cache)
    np.testing.assert_array_equal(np.asarray(k2({"x": x})["z"]), x * 2.0)
    np.testing.assert_array_equal(np.asarray(k3({"x": x})["z"]), x * 3.0)


# ----------------------------------------------------- pump_mode regression --
def test_apply_multipump_records_pump_mode():
    g, _ = BUILDERS["vecadd"](64, vector_width=8)
    sg, _ = apply_streaming(g)
    pg, rep = apply_multipump(sg, factor=2, mode="R")
    assert rep.applied
    comp = pg.computes()[0]
    assert comp.meta["pump_mode"] == "R"
    assert pump_spec_for(pg, comp.name).mode == "R"


# ------------------------------------------------------------------ cache --
def test_compile_cache_persists_across_instances(tmp_path):
    path = tmp_path / "cache.json"
    g, _ = BUILDERS["vecadd"](64, vector_width=8)
    c1 = CompileCache(path)
    k1 = compiler.compile(g, factor=2, cache=c1, memoize=False)
    assert k1.report.served_from is None and k1.report.cache_hits == 0
    assert c1.stats["entries"] == 1

    c2 = CompileCache(path)   # fresh instance ≙ fresh process
    k2 = compiler.compile(g, factor=2, cache=c2, memoize=False)
    assert k2.report.served_from == "disk"
    assert k2.report.cache_hits == 1
    assert c2.stats["hits"] == 1

    rng = np.random.default_rng(5)
    inputs = {"x": _ints(rng, 64), "y": _ints(rng, 64)}
    np.testing.assert_array_equal(np.asarray(k1(inputs)["z"]),
                                  np.asarray(k2(inputs)["z"]))


def test_compile_memo_serves_repeat_requests(tmp_path):
    compiler.clear_memo()
    cache = CompileCache(tmp_path / "cache.json")
    g1, _ = BUILDERS["vecadd"](64, vector_width=8)
    k1 = compiler.compile(g1, factor=2, cache=cache)
    g2, _ = BUILDERS["vecadd"](64, vector_width=8)   # structural rebuild
    k2 = compiler.compile(g2, factor=2, cache=cache)
    assert k2.fn is k1.fn and k2.graph is k1.graph   # compiled artifact shared
    assert k2.report.served_from == "memory" and k2.report.cache_hits >= 1
    # the cold compile's provenance record is not rewritten by later hits
    assert k1.report.served_from is None and k1.report.cache_hits == 0
    # a memo hit writes the plan through to a persistent cache that has
    # not seen the request yet
    fresh = CompileCache(tmp_path / "fresh.json")
    k3 = compiler.compile(g2, factor=2, cache=fresh)
    assert k3.report.served_from == "memory"
    assert (tmp_path / "fresh.json").exists() and len(fresh) == 1


def test_plan_shared_across_backends(tmp_path):
    """The persistent plan is backend-independent: an autopump-style
    backend='none' compile must warm the cache for a jax-backend compile."""
    compiler.clear_memo()
    cache = CompileCache(tmp_path / "c.json")
    g, est = BUILDERS["vecadd"](64, vector_width=8)
    k_none = compiler.compile(g, factor="auto", estimate=est, backend="none",
                              cache=cache, memoize=False)
    k_jax = compiler.compile(g, factor="auto", estimate=est, backend="jax",
                             cache=cache, memoize=False)
    assert k_jax.report.served_from == "disk"
    assert k_jax.spec.factor == k_none.spec.factor


def test_memo_distinguishes_array_closures():
    """repr() elides the middle of large arrays; the memo must still tell
    two captured weight tables apart (hashes the buffer, not the repr)."""
    compiler.clear_memo()
    n = 2048

    def build(w):
        g = Graph("wclosure")
        g.memory("x", (n,))
        g.memory("z", (n,))
        dom = Domain.of(("i", 0, n))
        acc = AccessPattern(dom, (Affine.of("i"),))
        g.compute("addw", dom, fn=lambda in0: {"out0": in0 + w})
        g.connect("x", "addw", acc)
        g.connect("addw", "z", acc)
        return g

    w1 = np.zeros(n, np.float32)
    w2 = w1.copy()
    w2[n // 2] = 5.0
    assert repr(w1) == repr(w2)          # the trap this test guards against
    x = np.zeros(n, np.float32)
    k1 = compiler.compile(build(w1), factor=1, cache=False)
    k2 = compiler.compile(build(w2), factor=1, cache=False)
    np.testing.assert_array_equal(np.asarray(k1({"x": x})["z"]), w1)
    np.testing.assert_array_equal(np.asarray(k2({"x": x})["z"]), w2)


def test_core_import_stays_jax_free():
    """repro.core must not drag in jax (the compiler re-export is lazy)."""
    import os
    import subprocess
    import sys
    r = subprocess.run(
        [sys.executable, "-c",
         "import repro.core, sys; print('jax' in sys.modules)"],
        capture_output=True, text=True, env=dict(os.environ))
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "False"
    # ... while the lazy attribute still resolves
    from repro.core import compiler as via_core
    assert via_core.compile is compiler.compile


def test_fingerprint_distinguishes_structure():
    g1, _ = BUILDERS["vecadd"](64, vector_width=8)
    g2, _ = BUILDERS["vecadd"](64, vector_width=8)
    g3, _ = BUILDERS["vecadd"](128, vector_width=8)
    assert graph_fingerprint(g1) == graph_fingerprint(g2)
    assert graph_fingerprint(g1) != graph_fingerprint(g3)
    sg, _ = apply_streaming(g1)
    assert graph_fingerprint(sg) != graph_fingerprint(g1)


# ------------------------------------------------------- registry/pipeline --
def test_pass_registry_and_default_order():
    assert {"streaming", "stream-fusion", "multipump", "fifo-depth"} \
        <= set(PASS_REGISTRY)
    pipe = Pipeline.default(factor=2)
    assert [p.name for p in pipe.passes] == \
        ["streaming", "stream-fusion", "multipump", "fifo-depth"]
    assert isinstance(make_pass("fifo-depth"), FifoDepthPass)
    with pytest.raises(KeyError):
        make_pass("nope")


def test_pipeline_records_skipped_passes(tmp_path):
    g, _ = BUILDERS["vecadd"](64, vector_width=8)
    kern = compiler.compile(g, factor=1,
                            cache=CompileCache(tmp_path / "c.json"),
                            memoize=False)
    rec = kern.report.record("multipump")
    assert rec is not None and not rec.applied and rec.reason
    assert kern.spec.factor == 1
    # streamed but unpumped: no adapter modules
    assert kern.graph.resources()["adapters"] == 0


# -------------------------------------------------------------- autopump --
def test_autopump_routes_through_pipeline(tmp_path):
    compiler.clear_memo()
    cache = CompileCache(tmp_path / "cache.json")
    r = autopump("vecadd", 4096, cache=cache)
    assert r.pipeline_report is not None
    assert [rec.name for rec in r.pipeline_report.records][0] == "streaming"
    assert r.pipeline_report.factor == r.spec.factor
    # second call is served from a cache layer (O(1) repeat compiles)
    r2 = autopump("vecadd", 4096, cache=cache)
    assert r2.pipeline_report.served_from in ("memory", "disk")
    assert r2.pipeline_report.cache_hits >= 1
    assert r2.spec == r.spec


@pytest.mark.parametrize("kernel", ["decode_attention", "vecadd",
                                    "ssd_decode", "flash_attention"])
def test_tile_views_keep_interpret_emission_exact(kernel):
    """Blocks that break the TPU tiling rule run through unit-axis views of
    their memories (decode's (1,) positions, ssd_decode's per-head rows,
    vecadd's 8-element blocks); the views must not change a result."""
    run_case(_DIFF0[kernel], 2, "T", "pallas", pallas_mode="interpret")


def test_tile_view_rules():
    from repro.compiler.pallas_backend import tile_view
    # a flash q/k/v/o block is legal as it stands
    assert tile_view((1, 16, 2048, 128), (1, 1, 128, 128), 2).kind == "same"
    # flash row statistics keep a trailing unit axis: (bq, 1) is legal
    assert tile_view((1, 16, 2048, 1), (1, 1, 128, 1), 4).kind == "same"
    # one (1, 1, d) row per (batch, head): a unit axis before the last
    v = tile_view((4, 16, 128), (1, 1, 128), 2)
    assert (v.kind, v.shape, v.block) == \
        ("unit", (4, 16, 1, 128), (1, 1, 1, 128))
    assert v.index((3, 5, 0)) == (3, 5, 0, 0)
    # one decode position per batch row
    v = tile_view((4,), (1,), 4)
    assert (v.kind, v.shape, v.block) == ("flat", (4, 1, 1), (1, 1, 1))
    assert v.index((3,)) == (3, 0, 0)
    # rank-1 blocks must match XLA's 1024-word tile or cover the array
    assert tile_view((1 << 20,), (1024,), 4).kind == "same"
    assert tile_view((1 << 20,), (512,), 4).kind == "flat"
    # a sub-lane slice of the last dim has no legal view (mode-R decode)
    assert tile_view((4, 8, 256, 128), (1, 1, 128, 64), 2) is None


def test_env_fingerprint_keys_on_device_kind(tmp_path, monkeypatch):
    """A pump winner measured on one device never replays on another: the
    platform and device kind are part of every plan key."""
    import jax
    from repro.compiler import cache as cache_mod
    fp = cache_mod.env_fingerprint
    assert fp("0.9.0", "cpu", "cpu") != fp("0.9.0", "tpu", "TPU v5 lite")
    assert fp("0.9.0", "tpu", "TPU v5 lite") != fp("0.9.0", "tpu",
                                                   "TPU v6 lite")
    dev = jax.devices()[0]
    assert cache_mod._env_fingerprint() == fp(jax.__version__, dev.platform,
                                              dev.device_kind)
    g, est = BUILDERS["vecadd"](64, vector_width=8)
    store = CompileCache(tmp_path / "c.json")
    key_here = compiler.measure_request_key(g, est)
    store.put(key_here, {"factor": 4})
    monkeypatch.setattr(cache_mod, "_env_fingerprint",
                        lambda: fp(jax.__version__, "tpu", "TPU v5 lite"))
    key_chip = compiler.measure_request_key(g, est)
    assert key_chip != key_here and store.get(key_chip) is None
