"""Mixture-of-Experts layer (DeepSeek style: shared + routed top-k experts).

Dispatch is capacity-based with scatter/gather indexing (no (T, E, C) one-hot
tensor): top-k routing → per-expert slot assignment via a stable sort by
expert id → scatter tokens into a (E, C, d) buffer → batched expert SwiGLU
(einsum over the expert axis, EP-shardable) → gather + gate-weighted combine.
Tokens overflowing an expert's capacity are dropped (standard GShard
semantics); the auxiliary load-balance loss pushes the router away from
overflow.

The (E, C, d) expert buffer is the unit the ``model`` mesh axis shards for
expert parallelism; XLA inserts the dispatch all-to-all automatically from
the sharding annotations in launch/sharding.py.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .layers import dense, dense_init


def _ep_constraint(arr):
    """Pin the (E, C, d) expert buffer to expert-parallel sharding when a
    mesh is active (no-op otherwise): experts over 'model', capacity over
    'data'.  Both dims sharded ⇒ the dispatch lowers as an all-to-all and
    the expert GEMMs stay fully distributed (§Perf iteration A2/A3)."""
    import os
    if os.environ.get("REPRO_MOE_EP_CONSTRAINT", "0") != "1":
        # Measured on deepseek-v3 train_4k (EXPERIMENTS.md §Perf A2/A3):
        # forcing EP×DP layout on the buffer made GSPMD reshard the scatter
        # operands (+2.2× bytes, +3.5× collectives).  GSPMD's propagated
        # layout matches the unconstrained optimum, so this is opt-in only.
        return arr
    from jax.sharding import PartitionSpec as P
    # the mesh the caller installed with jax.set_mesh (launch/mesh.py); a
    # bare PartitionSpec below resolves against it (a GSPMD hint: Auto axes)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "model" not in mesh.axis_names:
        return arr
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    espec = "model" if arr.shape[0] % sizes["model"] == 0 else None
    cspec = "data" if ("data" in sizes
                       and arr.shape[1] % sizes["data"] == 0) else None
    if espec is None and cspec is None:
        return arr
    return jax.lax.with_sharding_constraint(arr, P(espec, cspec, None))


def _ragged_dropless_experts(p, cfg, xt, gate, idx):
    """Expert SwiGLU over ragged row groups (the megablocks idiom).

    Tokens sort by expert id into a row-major concatenation of per-expert
    groups; all three expert GEMMs (gate/up/down) run as one ragged grouped
    GEMM each, with row groups padded to the row tile instead of a dense
    capacity.  ``cfg.kernel_plan == 'measure'`` routes through the plan
    registry (bucketed group sizes, measured pump); ``'direct'`` calls
    ``kernels.ops.grouped_gemm`` with the default pump.
    """
    mo = cfg.moe
    t, d = xt.shape
    e, k = mo.n_experts, mo.top_k
    flat_e = np.asarray(idx).reshape(-1)                          # (T*k,)
    order = np.argsort(flat_e, kind="stable")
    counts = np.bincount(flat_e, minlength=e)

    if cfg.kernel_plan == "measure":
        from repro.compiler.registry import default_registry
        reg = default_registry()
        bucket = reg.policy.bucket_group

        def gg(a, w):
            return reg.grouped_gemm(a, w, group_sizes=padded)
    else:
        from repro.kernels.ops import grouped_gemm as _gg
        bucket = lambda c: -(-c // 16) * 16 if c else 0   # noqa: E731

        def gg(a, w):
            return _gg(a, w, group_sizes=padded, bc=16)

    # scatter tokens into the bucketed padded row layout ONCE; all three
    # expert GEMMs consume it directly (group sizes == padded sizes, so
    # the ragged execution core skips per-group segmentation/re-slicing)
    padded = [int(bucket(int(c))) for c in counts]
    rows_p = sum(padded)
    offs = np.concatenate(([0], np.cumsum(padded)[:-1]))
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
    sorted_e = flat_e[order]
    rows = offs[sorted_e] + (np.arange(t * k) - starts[sorted_e])
    tok_idx = np.repeat(np.arange(t), k)
    xs = jnp.zeros((rows_p, d), xt.dtype).at[rows].set(xt[tok_idx[order]])

    h_gate = gg(xs, p["gate"].astype(xt.dtype))
    h_up = gg(xs, p["up"].astype(xt.dtype))
    h = jax.nn.silu(h_gate) * h_up
    y_pad = gg(h, p["down"].astype(xt.dtype))

    y_sorted = y_pad[rows]                  # back to assignment order
    inv = np.empty_like(order)
    inv[order] = np.arange(t * k)
    gathered = y_sorted[inv].reshape(t, k, d)                     # dropless:
    return jnp.einsum("tkd,tk->td", gathered.astype(jnp.float32),
                      gate).astype(xt.dtype)                      # keep all


def moe_init(key, cfg, dtype=jnp.float32):
    mo = cfg.moe
    d, de = cfg.d_model, mo.d_expert
    ks = jax.random.split(key, 5)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    p = {
        "router": dense_init(ks[0], d, mo.n_experts, dtype=dtype),
        # routed experts, stacked: (E, d, de) / (E, de, d)
        "gate": jax.random.normal(ks[1], (mo.n_experts, d, de), dtype) * scale,
        "up": jax.random.normal(ks[2], (mo.n_experts, d, de), dtype) * scale,
        "down": jax.random.normal(ks[3], (mo.n_experts, de, d), dtype) \
            * (1.0 / jnp.sqrt(de).astype(jnp.float32)),
    }
    if mo.n_shared_experts:
        from .layers import swiglu_init
        p["shared"] = swiglu_init(ks[4], d, de * mo.n_shared_experts, dtype)
    return p


def moe_apply(p, cfg, x, *, dropless: bool = False
              ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss).

    ``dropless=True`` (the serve path) sizes capacity to the worst case so
    no token is ever dropped — decode must be deterministic and match the
    full forward pass; training uses GShard capacity semantics."""
    mo = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = mo.n_experts, mo.top_k
    if dropless:
        icf = mo.inference_capacity_factor
        cap = t * k if icf <= 0 else min(t * k, -(-int(icf * t * k) // e) + 1)
    else:
        cap = int(mo.capacity_factor * t * k / e) + 1
        if t >= 4096:                    # production shapes: align for EP×DP
            cap = ((cap + 255) // 256) * 256

    xt = x.reshape(t, d)
    logits = dense(p["router"], xt.astype(jnp.float32))          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)                          # (T, k)
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)    # renormalize

    # load-balance aux loss (Switch-style)
    me = probs.mean(axis=0)                                       # (E,)
    ce = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(
        jnp.ones((t * k,), jnp.float32)) / (t * k)
    aux = e * jnp.sum(me * ce) * mo.router_aux_weight

    # ---- ragged dropless path (serving): skip the dense capacity buffer ---
    # The ragged grouped-gemm kernel consumes per-expert row groups padded
    # only to the row tile — no (E, cap, d) worst-case buffer, empty experts
    # emit no tiles.  Group sizes must be static (they parameterize the
    # group-indexed BlockSpec tables), so this engages only on concrete
    # (non-traced) routing; jit'd calls keep the dense reference path.
    # Only the *strictly* dropless regime (icf <= 0) qualifies: a positive
    # inference_capacity_factor caps-and-drops in the dense path, and the
    # ragged path (which keeps every routed token) must not silently
    # diverge from that reference.
    if dropless and mo.ragged_dropless \
            and mo.inference_capacity_factor <= 0 \
            and not isinstance(x, jax.core.Tracer):
        y = _ragged_dropless_experts(p, cfg, xt, gate, idx)
        if mo.n_shared_experts:
            from .layers import swiglu
            y = y + swiglu(p["shared"], xt)
        return y.reshape(b, s, d), aux

    # ---- slot assignment: stable sort of (expert, arrival) pairs ----------
    flat_e = idx.reshape(-1)                                      # (T*k,)
    order = jnp.argsort(flat_e, stable=True)                      # sorted by e
    sorted_e = flat_e[order]
    # position within expert = index - start offset of that expert
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    pos_sorted = jnp.arange(t * k) - starts[sorted_e]
    pos = jnp.zeros((t * k,), jnp.int32).at[order].set(pos_sorted)
    keep = pos < cap                                              # drop overflow
    posc = jnp.where(keep, pos, cap)                              # cap = trash row

    # ---- dispatch: 3-D scatter into the (E, cap+1, d) expert buffer --------
    # Keeping the expert axis a REAL tensor dim (not flattened) lets GSPMD
    # shard the buffer P('model', None, None) (expert parallelism) and lower
    # the dispatch as an all-to-all instead of replicating the token stream
    # (§Perf iteration A2 — the flattened (E·C+1, d) form forced involuntary
    # full rematerialization and ~16× collective blowup on deepseek-v3).
    tok_idx = jnp.repeat(jnp.arange(t), k)
    buf = jnp.zeros((e, cap + 1, d), x.dtype)
    buf = buf.at[flat_e, posc].set(xt[tok_idx], mode="drop")
    expert_in = _ep_constraint(buf[:, :cap])                      # (E, cap, d)

    # ---- batched expert SwiGLU (EP axis = leading expert dim) --------------
    h_gate = jnp.einsum("ecd,edf->ecf", expert_in,
                        p["gate"].astype(x.dtype))
    h_up = jnp.einsum("ecd,edf->ecf", expert_in, p["up"].astype(x.dtype))
    h = jax.nn.silu(h_gate) * h_up
    expert_out = jnp.einsum("ecf,efd->ecd", h, p["down"].astype(x.dtype))
    expert_out = _ep_constraint(expert_out)

    # ---- combine: gather slots back, weight by gates ------------------------
    padded = jnp.concatenate(
        [expert_out, jnp.zeros((e, 1, d), x.dtype)], axis=1)      # trash row
    gathered = padded[flat_e, posc].reshape(t, k, d)
    y = jnp.einsum("tkd,tk->td", gathered.astype(jnp.float32),
                   gate * keep.reshape(t, k)).astype(x.dtype)

    if mo.n_shared_experts:
        from .layers import swiglu
        y = y + swiglu(p["shared"], xt)
    return y.reshape(b, s, d), aux
