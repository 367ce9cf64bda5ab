"""Distribution-layer tests: sharding rules, HLO collective parser,
input specs, and a small real-mesh lower/compile (8 fake devices via
subprocess isolation is avoided — tests run divisibility-safe on 1 device).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, load_arch
from repro.launch import sharding as shard_mod
from repro.launch import steps as steps_mod
from repro.launch.dryrun import collective_bytes
from repro import optim


def host_mesh():
    return jax.make_mesh((1, 1), ("data", "model"))


# ----------------------------------------------------------- rule fitting --
def test_fit_drops_nondividing_axes():
    mesh = host_mesh()
    spec = shard_mod._fit(P("data", "model"), (3, 5), mesh)
    assert spec == P(None, None)   # 1-device mesh: everything replicates


def test_param_specs_cover_all_leaves():
    from repro.models import model as model_mod
    for arch in ("qwen3-0.6b", "deepseek-v2-lite-16b", "mamba2-1.3b",
                 "zamba2-2.7b", "whisper-base", "internvl2-2b"):
        cfg = load_arch(arch, smoke=True)
        params = jax.eval_shape(
            lambda k: model_mod.init_params(cfg, k), jax.random.PRNGKey(0))
        specs = shard_mod.param_specs(params)
        flat_p = jax.tree_util.tree_leaves(params)
        flat_s = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_p) == len(flat_s)
        for leaf, spec in zip(flat_p, flat_s):
            assert len(spec) <= leaf.ndim, (leaf.shape, spec)


def test_embedding_and_mlp_rules():
    specs = shard_mod.param_specs(
        {"embed": {"embedding": jax.ShapeDtypeStruct((1024, 64), jnp.float32)},
         "mlp": {"down": {"w": jax.ShapeDtypeStruct((256, 64), jnp.float32)},
                 "up": {"w": jax.ShapeDtypeStruct((64, 256), jnp.float32)}}})
    assert specs["embed"]["embedding"] == P("model", "data")
    assert specs["mlp"]["down"]["w"] == P("model", "data")   # row-parallel
    assert specs["mlp"]["up"]["w"] == P("data", "model")     # col-parallel


def test_cache_specs_head_vs_sequence_sharding():
    mesh = host_mesh()
    cache = {"k": jax.ShapeDtypeStruct((2, 4, 8, 16, 32), jnp.bfloat16),
             "v": jax.ShapeDtypeStruct((2, 4, 8, 16, 32), jnp.bfloat16),
             "pos": jax.ShapeDtypeStruct((2,), jnp.int32)}
    specs = shard_mod.cache_specs(cache, mesh)
    assert specs["pos"] == P()


# ------------------------------------------------------------- HLO parser --
def test_collective_bytes_parser():
    hlo = """
  %ag = f32[16,128]{1,0} all-gather(%x), replica_groups={{0,1}}
  %ar = bf16[2,4,8]{2,1,0} all-reduce(%y), to_apply=%add
  %rs = f32[64]{0} reduce-scatter(%z), dimensions={0}
  %a2a = s32[10]{0} all-to-all(%w)
  %cp = f32[4,4]{1,0} collective-permute(%v)
  %dot = f32[8,8]{1,0} dot(%a, %b)
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 16 * 128 * 4
    assert out["all-reduce"] == 2 * 4 * 8 * 2
    assert out["reduce-scatter"] == 64 * 4
    assert out["all-to-all"] == 10 * 4
    assert out["collective-permute"] == 16 * 4
    assert out["count"] == 5


def test_collective_bytes_ignores_noncollectives():
    assert collective_bytes("%d = f32[8]{0} dot(%a, %b)")["count"] == 0


# ------------------------------------------------------------ input specs --
@pytest.mark.parametrize("arch", ["qwen3-0.6b", "whisper-base",
                                  "internvl2-2b"])
def test_abstract_batch_shapes(arch):
    cfg = load_arch(arch)
    shape = SHAPES["train_4k"]
    batch = steps_mod.abstract_batch(cfg, shape)
    assert batch["tokens"].shape == (256, 4096)
    if cfg.family == "encdec":
        assert batch["frames"].shape == (256, cfg.encoder_seq, cfg.d_model)
    if cfg.family == "vlm":
        assert batch["patches"].shape == (256, cfg.n_vision_tokens,
                                          cfg.d_vision)
    pumped = steps_mod.abstract_batch(cfg, shape, pump_factor=4)
    assert pumped["tokens"].shape == (4, 64, 4096)


def test_abstract_cache_matches_family():
    cfg = load_arch("mamba2-1.3b")
    cache = steps_mod.abstract_cache(cfg, SHAPES["decode_32k"])
    leaves = jax.tree_util.tree_leaves(cache)
    assert leaves  # ssm caches exist, no KV tensors of seq length
    assert all(l.shape[0] == cfg.n_layers for l in leaves
               if hasattr(l, "shape") and l.ndim > 1)


# ----------------------------------------------- end-to-end sharded lower --
def test_train_step_lowers_on_host_mesh():
    cfg = load_arch("qwen3-0.6b", smoke=True)
    mesh = host_mesh()
    optcfg = optim.AdamWConfig()
    from repro.configs.base import ShapeConfig
    shape = ShapeConfig("t", 64, 4, "train")
    step = steps_mod.make_train_step(cfg, optcfg, pump_factor=2)
    in_sh, out_sh, args = steps_mod.train_shardings(
        cfg, optcfg, mesh, shape, jnp.float32, pump_factor=2)
    with jax.set_mesh(mesh):
        lowered = jax.jit(step, in_shardings=in_sh,
                          out_shardings=out_sh).lower(*args)
        compiled = lowered.compile()
    assert compiled.cost_analysis() is not None


def test_mesh_factories():
    from repro.launch import mesh as mesh_mod
    m = mesh_mod.make_host_mesh()
    assert set(m.axis_names) == {"data", "model"}
    assert mesh_mod.dp_degree(m) >= 1


# ------------------------------------------------- installed mesh context --
@pytest.mark.parametrize("opt_in,installed,constrained", [
    (True, True, True), (False, True, False), (True, False, False)])
def test_moe_ep_constraint_lowers_under_the_installed_mesh(
        monkeypatch, opt_in, installed, constrained):
    import contextlib
    from repro.launch import mesh as mesh_mod
    from repro.models.moe import _ep_constraint
    monkeypatch.setenv("REPRO_MOE_EP_CONSTRAINT", "1" if opt_in else "0")
    ctx = jax.set_mesh(mesh_mod.make_host_mesh()) if installed \
        else contextlib.nullcontext()
    with ctx:   # a fresh function: the env var is read at trace time
        text = jax.jit(lambda a: _ep_constraint(a)).lower(
            jnp.zeros((8, 4, 16))).as_text()
    assert ("sharding_constraint" in text) == constrained


def test_engine_traces_its_steps_under_its_mesh(monkeypatch):
    """The engine's steps see the mesh through the abstract-mesh context,
    which is what the MoE expert-parallel hint reads."""
    from repro.models import moe
    from repro.serve.engine import Engine, ServeConfig
    from repro.models import model as model_mod
    seen = []
    real = moe._ep_constraint

    def spy(arr):
        seen.append(jax.sharding.get_abstract_mesh().axis_names)
        return real(arr)

    monkeypatch.setattr(moe, "_ep_constraint", spy)
    cfg = load_arch("deepseek-v2-lite-16b", smoke=True)
    eng = Engine(cfg, model_mod.init_params(cfg, jax.random.PRNGKey(0)),
                 ServeConfig(batch=1, max_len=8))
    eng.prefill(jnp.zeros((1, 4), jnp.int32))
    assert seen and all(names == ("data", "model") for names in seen)
