"""Training loop: multipumped gradient accumulation, mixed precision,
checkpointing, failure recovery, metrics.

The trainer is the pod-scale consumer of the paper's transformation
(DESIGN.md §2): ``TrainConfig.pump_factor`` M sets how many microbatch
compute iterations (fast domain) feed one gradient synchronization + update
(wide transaction on the slow domain).  ``pump_factor='auto'`` asks
``core.pump_plan.plan_trainer_pump`` for the factor that amortizes the
collective below 10 % of compute.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro import obs, optim
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.pump_plan import plan_trainer_pump
from repro.data.pipeline import DataConfig, DataIterator
from repro.checkpoint import manager as ckpt_mod
from repro.launch import mesh as mesh_mod
from repro.launch import sharding as shard_mod
from repro.launch import steps as steps_mod
from repro.models import model as model_mod


@dataclasses.dataclass
class TrainConfig:
    n_steps: int = 100
    pump_factor: Any = 1              # int or "auto"
    param_dtype: str = "float32"
    ckpt_root: Optional[str] = None
    ckpt_every: int = 50
    log_every: int = 10
    seed: int = 0


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: optim.AdamWState
    step: int = 0


def resolve_pump(cfg: ModelConfig, shape: ShapeConfig, mesh, pump) -> int:
    if pump != "auto":
        return int(pump)
    grad_bytes = cfg.param_count() * 4
    tokens = shape.global_batch * shape.seq_len
    step_flops = 6.0 * cfg.active_param_count() * tokens
    return plan_trainer_pump(grad_bytes, step_flops, mesh.devices.size,
                             mesh_mod.dp_degree(mesh))


def make_trainer(cfg: ModelConfig, shape: ShapeConfig,
                 optcfg: optim.AdamWConfig = optim.AdamWConfig(),
                 tcfg: TrainConfig = TrainConfig(),
                 mesh=None, batch_override: Optional[int] = None):
    """Returns (init_fn, step_fn, data_iter).  Host-side driver below."""
    mesh = mesh or mesh_mod.make_host_mesh()
    pump = resolve_pump(cfg, shape, mesh, tcfg.pump_factor)
    pdt = jnp.dtype(tcfg.param_dtype)

    step = steps_mod.make_train_step(cfg, optcfg, pump)
    in_sh, out_sh, _ = steps_mod.train_shardings(cfg, optcfg, mesh, shape,
                                                 pdt, pump)
    jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=(0, 1))

    def init_fn(key) -> TrainState:
        with jax.set_mesh(mesh):
            params = jax.jit(
                lambda k: model_mod.init_params(cfg, k, dtype=pdt),
                out_shardings=in_sh[0])(key)
            opt_state = jax.jit(
                lambda p: optim.init(optcfg, p),
                out_shardings=in_sh[1])(params)
        return TrainState(params, opt_state, 0)

    def step_fn(state: TrainState, batch) -> tuple:
        with jax.set_mesh(mesh):
            params, opt_state, metrics = jitted(state.params, state.opt_state,
                                                batch)
        return TrainState(params, opt_state, state.step + 1), metrics

    data = DataIterator(cfg, shape, DataConfig(seed=tcfg.seed),
                        batch_override=batch_override, pump_factor=pump)
    return init_fn, step_fn, data, pump


def train(cfg: ModelConfig, shape: ShapeConfig,
          optcfg: optim.AdamWConfig = optim.AdamWConfig(),
          tcfg: TrainConfig = TrainConfig(),
          mesh=None, batch_override: Optional[int] = None,
          log=print, heartbeat=None, straggler=None) -> Dict[str, Any]:
    """Full driver: init → (restore) → loop → checkpoint.  Returns metrics.

    ``heartbeat`` (:class:`repro.runtime.failover.Heartbeat`) gets this
    host's step stamped after every update — the liveness signal the
    monitor side reads.  ``straggler``
    (:class:`~repro.runtime.failover.StragglerPolicy`) observes per-step
    wall time and derates this host's pump factor from the EWMAs; the
    derated factor is gauged (``train.pump_derated``) and logged when it
    moves, so a slow host is visible before it stalls the whole mesh.
    """
    init_fn, step_fn, data, pump = make_trainer(
        cfg, shape, optcfg, tcfg, mesh, batch_override)
    state = init_fn(jax.random.PRNGKey(tcfg.seed))
    worker = jax.process_index()
    pump_derated = pump

    if tcfg.ckpt_root:
        latest = ckpt_mod.latest_valid(tcfg.ckpt_root)
        if latest:
            like = {"params": state.params, "opt_state": state.opt_state}
            tree, extra = ckpt_mod.restore(latest, like)
            state = TrainState(tree["params"], tree["opt_state"],
                               extra["step"])
            data.step = extra["data_step"]
            log(f"[trainer] resumed from {latest} at step {state.step}")

    if straggler is not None:
        # the policy derates from the *resolved* pump factor (the CLI may
        # have asked for 'auto', resolved only inside make_trainer)
        straggler.base_pump = pump
    history = []
    t_last = time.time()
    t_step = time.time()
    while state.step < tcfg.n_steps:
        batch = next(data)
        state, metrics = step_fn(state, batch)
        if heartbeat is not None:
            heartbeat.stamp(worker, state.step)
        if straggler is not None:
            now = time.time()
            straggler.observe(worker, now - t_step)
            t_step = now
            derated = straggler.pump_factors().get(worker, pump_derated)
            if derated != pump_derated:
                log(f"[trainer] straggler policy derated pump "
                    f"{pump_derated} -> {derated} (worker {worker})")
                obs.count("train.pump_derate", frm=str(pump_derated),
                          to=str(derated))
                pump_derated = derated
            obs.gauge("train.pump_derated", pump_derated)
        if state.step % tcfg.log_every == 0 or state.step == tcfg.n_steps:
            dt = time.time() - t_last
            t_last = time.time()
            loss = float(metrics["loss"])
            history.append({"step": state.step, "loss": loss,
                            "grad_norm": float(metrics["grad_norm"]),
                            "lr": float(metrics["lr"]), "sec": dt})
            log(f"[trainer] step {state.step:5d} loss {loss:.4f} "
                f"gnorm {float(metrics['grad_norm']):.3f} "
                f"lr {float(metrics['lr']):.2e} ({dt:.1f}s) pump={pump}")
        if tcfg.ckpt_root and state.step % tcfg.ckpt_every == 0:
            ckpt_mod.save(tcfg.ckpt_root, state.step,
                          {"params": state.params,
                           "opt_state": state.opt_state},
                          extra={"step": state.step,
                                 "data_step": data.step})
    if tcfg.ckpt_root:
        ckpt_mod.save(tcfg.ckpt_root, state.step,
                      {"params": state.params, "opt_state": state.opt_state},
                      extra={"step": state.step, "data_step": data.step})
    return {"history": history, "final_state": state, "pump": pump}
