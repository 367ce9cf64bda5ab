"""The benchmark's plain reference against the program, on the CPU at the
smoke size, for both configuration families: qk-norm on (qwen3) and off
(granite).  Both sides run the same bench-made weights in float32."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench_helpers import family, smoke_config

DENSE = family("dense")
dense = DENSE.reference

FAMILIES = ["qwen3-0.6b", "granite-3-2b"]


def _program_logits(cj, params, tokens):
    from repro.models import transformer
    cfg = dataclasses.replace(DENSE.program_config(cj),
                              attention_impl="xla_chunked",
                              kernel_plan="direct", dtype="float32")
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        logits, _ = transformer.forward(cfg, p32, tokens)
    return np.asarray(logits)


@pytest.mark.parametrize("name", FAMILIES)
def test_weights_have_the_programs_layout(name):
    from repro.models import model as model_mod
    cj = smoke_config(name)
    cfg = DENSE.program_config(cj)
    want = jax.eval_shape(lambda k: model_mod.init_params(cfg, k),
                          jax.random.PRNGKey(0))
    got = weights.make(DENSE.weight_shapes(cj), 3)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == jnp.bfloat16


@pytest.mark.parametrize("name", FAMILIES)
def test_reference_matches_program_forward(name):
    cj = smoke_config(name)
    params = weights.make(DENSE.weight_shapes(cj), 7)
    tokens = np.random.default_rng(0).integers(0, cj["vocab_size"], (3, 40))
    ref = np.asarray(dense.logits(params, cj, tokens))
    prog = _program_logits(cj, params, jnp.asarray(tokens, jnp.int32))
    scale = np.abs(ref).max()
    assert np.abs(ref - prog).max() <= 1e-5 * scale


@pytest.mark.parametrize("name", FAMILIES)
def test_gaps_read_the_reference_logits(name):
    cj = smoke_config(name)
    params = weights.make(DENSE.weight_shapes(cj), 9)
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, cj["vocab_size"], (2, 24))
    query = rng.integers(0, cj["vocab_size"], (2, 24, 3))
    lg = np.asarray(dense.logits(params, cj, tokens))
    gap, top = dense.gaps(params, cj, tokens, query)
    want = lg.max(-1, keepdims=True) - np.take_along_axis(lg, query, -1)
    np.testing.assert_allclose(np.asarray(gap), want, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(top), lg.argmax(-1))


def test_qk_norm_is_applied_where_configured():
    cj = smoke_config("qwen3-0.6b")
    params = weights.make(DENSE.weight_shapes(cj), 5)
    tokens = np.arange(16)[None] % cj["vocab_size"]
    on = np.asarray(dense.logits(params, cj, tokens))
    off = np.asarray(dense.logits(params, dict(cj, qk_norm=False), tokens))
    assert np.abs(on - off).max() > 1e-3


def test_fp8_control_departs_from_float32():
    cj = smoke_config("qwen3-0.6b")
    params = weights.make(DENSE.weight_shapes(cj), 5)
    tokens = np.random.default_rng(2).integers(0, cj["vocab_size"], (2, 32))
    f32 = np.asarray(dense.logits(params, cj, tokens))
    f8 = np.asarray(dense.logits(params, cj, tokens, quant="fp8"))
    rel = np.abs(f8 - f32).max() / np.abs(f32).max()
    assert 1e-3 < rel < 0.5
