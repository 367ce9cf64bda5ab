"""End-to-end system tests: training convergence, multipumped gradient
equivalence, checkpoint/restore, failure recovery, serving."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.configs.base import ModelConfig, ShapeConfig
from repro.checkpoint import manager as ckpt
from repro.data.pipeline import DataConfig, DataIterator, synthetic_batch
from repro.launch import steps as steps_mod
from repro.models import model as model_mod
from repro.runtime import failover
from repro.train.trainer import TrainConfig, train

TINY = ModelConfig("tiny", "dense", 2, 32, 4, 2, 64, 64, dtype="float32")
SHAPE = ShapeConfig("t", 32, 8, "train")


# ------------------------------------------------------------- convergence --
def test_training_loss_decreases():
    out = train(TINY, SHAPE,
                optim.AdamWConfig(lr=3e-3, warmup_steps=5, total_steps=80),
                TrainConfig(n_steps=80, log_every=10))
    h = out["history"]
    assert h[-1]["loss"] < h[0]["loss"] * 0.95
    assert all(np.isfinite(e["loss"]) for e in h)


# --------------------------------------------- multipump gradient identity --
def test_pumped_step_matches_unpumped():
    """Trainer Mode T correctness: M microbatches accumulated == one big
    batch (same tokens), to float tolerance.  This is the pod-scale
    issuer/packer value-preservation property."""
    optcfg = optim.AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10,
                               grad_clip=0.0)
    params = model_mod.init_params(TINY, jax.random.PRNGKey(0))
    opt0 = optim.init(optcfg, params)

    batch = synthetic_batch(TINY, SHAPE, DataConfig(), 0)
    p1, _, m1 = jax.jit(steps_mod.make_train_step(TINY, optcfg))(
        params, opt0, batch)

    pumped = jax.tree.map(
        lambda a: a.reshape((4, 2) + a.shape[1:]), batch)
    opt0b = optim.init(optcfg, params)
    p2, _, m2 = jax.jit(steps_mod.make_train_step(TINY, optcfg, 4))(
        params, opt0b, pumped)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    diffs = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), p1, p2)
    assert max(jax.tree.leaves(diffs)) < 1e-5


# ------------------------------------------------------------- data stream --
def test_data_stream_is_deterministic_and_checkpointable():
    it1 = DataIterator(TINY, SHAPE)
    for _ in range(3):
        next(it1)
    state = it1.state()
    b_next = next(it1)

    it2 = DataIterator.from_state(TINY, SHAPE, state)
    b_replay = next(it2)
    np.testing.assert_array_equal(np.asarray(b_next["tokens"]),
                                  np.asarray(b_replay["tokens"]))


# -------------------------------------------------------------- checkpoint --
def test_checkpoint_roundtrip(tmp_path):
    root = str(tmp_path / "ckpt")
    state = {"w": jnp.arange(12.0).reshape(3, 4),
             "nested": {"b": jnp.ones((5,), jnp.bfloat16)}}
    ckpt.save(root, 7, state, extra={"step": 7})
    latest = ckpt.latest_valid(root)
    assert latest and latest.endswith("step_00000007")
    restored, extra = ckpt.restore(latest, state)
    assert extra["step"] == 7
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(state["w"]))
    assert restored["nested"]["b"].dtype == jnp.bfloat16


def test_checkpoint_detects_corruption(tmp_path):
    root = str(tmp_path / "ckpt")
    state = {"w": jnp.ones((4,))}
    ckpt.save(root, 1, state, extra={"step": 1})
    ckpt.save(root, 2, state, extra={"step": 2})
    # corrupt the newest shard
    shard = os.path.join(root, "step_00000002", "shard_00000.npz")
    with open(shard, "r+b") as f:
        f.seek(10)
        f.write(b"\xde\xad\xbe\xef")
    latest = ckpt.latest_valid(root)
    assert latest is not None and latest.endswith("step_00000001")


def test_checkpoint_prune(tmp_path):
    root = str(tmp_path / "ckpt")
    for s in range(6):
        ckpt.save(root, s, {"w": jnp.zeros(1)}, extra={"step": s})
    ckpt.prune(root, keep=2)
    assert ckpt.available_steps(root) == [4, 5]


# ---------------------------------------------------------------- failover --
def test_run_with_recovery_resumes_after_injected_failure(tmp_path):
    root = str(tmp_path / "ckpt")
    calls = {"n": 0, "fail_at": 7}

    def train_fn(state, step):
        calls["n"] += 1
        if step == calls["fail_at"] and calls["fail_at"] is not None:
            calls["fail_at"] = None            # fail exactly once
            raise failover.FailureInjected("simulated node loss")
        return {"x": state["x"] + 1.0}

    final = failover.run_with_recovery(
        train_fn, {"x": jnp.zeros(())}, n_steps=12, ckpt_root=root,
        ckpt_every=5)
    # exactly-once semantics: final state reflects 12 effective steps
    assert float(final["x"]) == 12.0


def test_heartbeat_and_straggler_policy():
    hb = failover.Heartbeat(timeout_s=10)
    hb.stamp(0, 5, now=100.0)
    hb.stamp(1, 5, now=100.0)
    assert hb.dead_workers(now=105.0) == []
    assert hb.dead_workers(now=115.0) == [0, 1]

    pol = failover.StragglerPolicy(base_pump=8)
    for w, t in [(0, 1.0), (1, 1.0), (2, 4.0)]:
        for _ in range(20):
            pol.observe(w, t)
    pf = pol.pump_factors()
    assert pf[0] == 8 and pf[1] == 8
    assert pf[2] < 8                            # the straggler gets derated


def test_elastic_remesh(tmp_path):
    from repro.launch import sharding as shard_mod
    root = str(tmp_path / "ckpt")
    tree = {"w": jnp.arange(64.0).reshape(8, 8)}
    ckpt.save(root, 3, tree, extra={"step": 3})
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    placed, extra = failover.elastic_remesh(
        ckpt.latest_valid(root), tree, mesh,
        lambda t, m: shard_mod.shardings(t, m))
    assert extra["step"] == 3
    np.testing.assert_array_equal(np.asarray(placed["w"]),
                                  np.asarray(tree["w"]))


# ----------------------------------------------------------------- serving --
def test_generate_greedy_is_deterministic():
    from repro.serve.engine import Engine, ServeConfig
    cfg = TINY
    params = model_mod.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, ServeConfig(batch=2, max_len=32))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0,
                                 cfg.vocab_size)
    out1 = eng.generate(prompts, 6)
    out2 = eng.generate(prompts, 6)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert out1.shape == (2, 6)


def test_trainer_checkpoint_resume_bitexact(tmp_path):
    root = str(tmp_path / "ck")
    optcfg = optim.AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
    # run 1: 10 steps with ckpt every 5
    train(TINY, SHAPE, optcfg,
          TrainConfig(n_steps=10, ckpt_root=root, ckpt_every=5, log_every=5))
    # run 2: resume to 15
    out2 = train(TINY, SHAPE, optcfg,
                 TrainConfig(n_steps=15, ckpt_root=root, ckpt_every=5,
                             log_every=5))
    # run 3 (control): fresh 15 steps, no resume
    out3 = train(TINY, SHAPE, optcfg,
                 TrainConfig(n_steps=15, log_every=5))
    w2 = jax.tree.leaves(out2["final_state"].params)[0]
    w3 = jax.tree.leaves(out3["final_state"].params)[0]
    np.testing.assert_allclose(np.asarray(w2), np.asarray(w3), atol=1e-6)


def test_compile_cache_dir_respects_the_environment(monkeypatch):
    """A set JAX_COMPILATION_CACHE_DIR is JAX's to read: the helper returns
    it and sets no directory of its own."""
    import jax
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax-cache")
    assert compile_cache.enable() == "/elsewhere/jax-cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(
        monkeypatch):
    import jax
    from pathlib import Path
    from repro.launch import compile_cache
    root = Path(__file__).resolve().parents[1]
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        first, second = compile_cache.enable(), compile_cache.enable()
        assert first == second == str(root / ".cache" / "jax")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".cache/" in (root / ".gitignore").read_text().split()
