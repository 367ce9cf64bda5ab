"""Mamba-2 block (SSD — state-space duality, arXiv:2405.21060).

Block structure: in-proj → short causal conv → SSD scan (the temporal-
vectorization flagship kernel) → gated out-proj.  Two SSD paths selected by
``cfg.ssm_impl``: ``pallas`` (the compiled SSD scan kernel) and
``xla`` (chunked jnp with a lax.scan over chunks — the same chunked math the
kernel implements, so the two agree to float tolerance).

Decode keeps a recurrent state (B, H, N, P) + conv tail (B, conv_w-1, d_in)
per layer: O(1) per token, the reason mamba2/zamba2 run the long_500k cell.
Under ``ssm_impl='pallas'`` + ``kernel_plan='measure'`` both cached paths
are compiled: prefill runs the SSD scan kernel with its final-state output
(so the decode state comes out of the same measured kernel that computed y)
and the per-token step runs the ``ssd_decode`` multi-output tile kernel;
``kernel_plan='direct'`` keeps the jnp math as the differential reference.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import dense, dense_init, rmsnorm, rmsnorm_init


def mamba2_init(key, cfg, dtype=jnp.float32):
    s = cfg.ssm
    d = cfg.d_model
    d_in = s.expand * d
    n_heads = d_in // s.head_dim
    conv_dim = d_in + 2 * s.n_groups * s.state_dim
    ks = jax.random.split(key, 5)
    return {
        # fused input projection: [z (gate), x, B, C, dt]
        "in_proj": dense_init(ks[0], d,
                              2 * d_in + 2 * s.n_groups * s.state_dim + n_heads,
                              dtype=dtype),
        "conv_w": jax.random.normal(ks[1], (s.conv_width, conv_dim), dtype) * 0.1,
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, n_heads)).astype(dtype),
        "dt_bias": jnp.zeros((n_heads,), dtype),
        "D": jnp.ones((n_heads,), dtype),
        "norm": rmsnorm_init(d_in, dtype),
        "out_proj": dense_init(ks[4], d_in, d, dtype=dtype),
    }


def _split_proj(cfg, proj):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    n_heads = d_in // s.head_dim
    gn = s.n_groups * s.state_dim
    z, xbc, dt = jnp.split(proj, [d_in, 2 * d_in + 2 * gn], axis=-1)
    return z, xbc, dt, d_in, n_heads, gn


def _causal_conv(xbc, w, b):
    """Depthwise causal conv over time.  xbc: (B, L, C); w: (W, C)."""
    wdt = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (wdt - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xbc.shape[1], :] * w[i] for i in range(wdt))
    return jax.nn.silu(out + b)


def _ssd_xla(x, dt, A, B, C, chunk):
    """Chunked SSD in pure jnp (same math as the Pallas kernel).

    Group-aware (§Perf C3): B/C projections are shared across the
    ``hpg = h/g`` heads of a group, so all einsums carry explicit (g, j)
    axes instead of materializing head-repeated copies of B and C — on
    mamba2-1.3b prefill the two ``jnp.repeat`` tensors were the largest
    intermediates in the block.  Matmul precision follows the input dtype
    (bf16 activations → bf16 MXU operands, fp32 accumulation); the decay
    cumsum and the inter-chunk state stay fp32.
    """
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    hpg = h // g
    nch = l // chunk
    cdt = x.dtype if x.dtype == jnp.bfloat16 else jnp.float32
    f32 = jnp.float32
    xg = x.reshape(b, nch, chunk, g, hpg, p).astype(cdt)
    dtg = dt.reshape(b, nch, chunk, g, hpg).astype(f32)
    Bc = B.reshape(b, nch, chunk, g, n).astype(cdt)
    Cc = C.reshape(b, nch, chunk, g, n).astype(cdt)
    Ag = A.reshape(g, hpg)
    logp = jnp.cumsum(Ag[None, None, None] * dtg, axis=2)  # (b,nch,c,g,j)

    # intra-chunk dual form; cb is PER GROUP (tiny), decay per head
    cb = jnp.einsum("bncgk,bnsgk->bngcs", Cc, Bc,
                    preferred_element_type=f32)            # (b,nch,g,c,c)
    lp_t = logp.transpose(0, 1, 3, 4, 2)                   # (b,nch,g,j,c)
    diff = lp_t[..., :, None] - lp_t[..., None, :]         # (b,nch,g,j,c,c)
    t_idx = jnp.arange(chunk)
    mask = t_idx[:, None] >= t_idx[None, :]
    G = jnp.where(mask, cb[:, :, :, None]
                  * jnp.exp(jnp.where(mask, diff, 0.0))
                  * dtg.transpose(0, 1, 3, 4, 2)[..., None, :], 0.0)
    y_intra = jnp.einsum("bngjcs,bnsgjp->bncgjp", G.astype(cdt), xg,
                         preferred_element_type=f32)

    # inter-chunk state scan (fp32 carry)
    w = jnp.exp(lp_t[..., -1:] - lp_t) \
        * dtg.transpose(0, 1, 3, 4, 2)                     # (b,nch,g,j,c)
    chunk_contrib = jnp.einsum("bncgk,bngjc,bncgjp->bngjkp",
                               Bc, w.astype(cdt), xg,
                               preferred_element_type=f32)
    chunk_decay = jnp.exp(lp_t[..., -1])                   # (b,nch,g,j)

    def scan_step(s_prev, inp):
        contrib, decay = inp
        s_new = s_prev * decay[..., None, None] + contrib
        return s_new, s_prev

    init = jnp.zeros((b, g, hpg, n, p), f32)
    s_final, s_starts = jax.lax.scan(
        scan_step, init,
        (chunk_contrib.swapaxes(0, 1), chunk_decay.swapaxes(0, 1)))
    s_starts = s_starts.swapaxes(0, 1)                     # (b,nch,g,j,n,p)
    y_carry = jnp.einsum("bncgk,bngjkp,bncgj->bncgjp",
                         Cc, s_starts.astype(cdt),
                         jnp.exp(logp).astype(cdt),
                         preferred_element_type=f32)
    y = (y_intra + y_carry).reshape(b, l, h, p)
    return y.astype(x.dtype), s_final.reshape(b, h, n, p)


def mamba2_apply(p, cfg, x, *, cache=None):
    """x: (B, L, d) -> (out, new_cache).  cache: dict(state, conv, pos)."""
    s = cfg.ssm
    b, l, d = x.shape
    proj = dense(p["in_proj"], x)
    z, xbc, dt, d_in, n_heads, gn = _split_proj(cfg, proj)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(dt.dtype))      # (B,L,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))                  # (H,)

    if cache is not None and l == 1:
        # single-token recurrent step
        conv_tail = cache["conv"]                                 # (B, W-1, C)
        window = jnp.concatenate([conv_tail, xbc], axis=1)        # (B, W, C)
        w = p["conv_w"].astype(x.dtype)
        conv_out = jax.nn.silu((window * w).sum(axis=1, keepdims=True)
                               + p["conv_b"].astype(x.dtype))
        new_conv = window[:, 1:]
        xs, B_, C_ = jnp.split(conv_out, [d_in, d_in + gn], axis=-1)
        xh = xs.reshape(b, n_heads, s.head_dim)
        Bg = B_.reshape(b, s.n_groups, s.state_dim)
        Cg = C_.reshape(b, s.n_groups, s.state_dim)
        dt1 = dt[:, 0]                                            # (B,H)
        state = cache["state"].astype(jnp.float32)
        if cfg.ssm_impl == "pallas" and cfg.kernel_plan == "measure":
            # kernelized per-token step: y and the new state come out of
            # one compiled multi-output tile kernel (group-folded B/C —
            # no head-repeated copies), served from a warm registry plan
            from repro.compiler.registry import default_registry
            y, state = default_registry().ssd_decode(state, xh, dt1, A,
                                                     Bg, Cg)
            y = y.astype(jnp.float32)
        else:
            hpg = n_heads // s.n_groups
            Bh = jnp.repeat(Bg, hpg, axis=1)
            Ch = jnp.repeat(Cg, hpg, axis=1)
            decay = jnp.exp(A[None] * dt1)                        # (B,H)
            upd = jnp.einsum("bhn,bhp->bhnp", Bh.astype(jnp.float32)
                             * dt1[..., None], xh.astype(jnp.float32))
            state = state * decay[..., None, None] + upd
            y = jnp.einsum("bhn,bhnp->bhp", Ch.astype(jnp.float32), state)
        y = y + p["D"].astype(jnp.float32)[None, :, None] \
            * xh.astype(jnp.float32)
        y = y.reshape(b, 1, d_in).astype(x.dtype)
        new_cache = {"state": state.astype(cache["state"].dtype),
                     "conv": new_conv, "pos": cache["pos"] + 1}
    else:
        cont = cache is not None and cfg.prefill_continuation
        if cont:
            # continuation chunk (pos > 0): the causal conv window is
            # seeded from the cached tail instead of zeros, so token 0 of
            # this chunk sees the last conv_width-1 tokens of the previous
            # chunk.  A zero tail (pos == 0) reduces to _causal_conv.
            window = jnp.concatenate(
                [cache["conv"].astype(x.dtype), xbc], axis=1)
            w = p["conv_w"].astype(x.dtype)
            conv_out = jax.nn.silu(
                sum(window[:, i:i + l, :] * w[i]
                    for i in range(s.conv_width))
                + p["conv_b"].astype(x.dtype))
        else:
            conv_out = _causal_conv(xbc, p["conv_w"].astype(x.dtype),
                                    p["conv_b"].astype(x.dtype))
        xs, B_, C_ = jnp.split(conv_out, [d_in, d_in + gn], axis=-1)
        xh = xs.reshape(b, l, n_heads, s.head_dim)
        Bg = B_.reshape(b, l, s.n_groups, s.state_dim)
        Cg = C_.reshape(b, l, s.n_groups, s.state_dim)
        chunk = min(s.chunk, l)
        if l % chunk:
            chunk = 1
        use_kernel = cfg.ssm_impl == "pallas" and cfg.kernel_plan == "measure"
        if use_kernel and cache is None:
            # plan-registry route: L pads to a seq bucket (dt=0 steps
            # are state identities, so padding is exact) and the pump
            # factor replays the measured winner from the compile cache
            # pass the configured chunk, not the l-divisibility fixup:
            # the bucketed L is what must divide it, and the registry
            # clamps the chunk to the bucket itself
            from repro.compiler.registry import default_registry
            y = default_registry().ssd_scan(xh, dt, A, Bg, Cg, chunk=s.chunk)
            s_final = None
        elif use_kernel:
            # cached prefill: the SSD builder's final-state output makes
            # the kernel usable here — the per-sweep carry state lands in a
            # real graph output instead of being recomputed by _ssd_xla
            from repro.compiler.registry import default_registry
            y, s_final = default_registry().ssd_scan(xh, dt, A, Bg, Cg,
                                                     chunk=s.chunk,
                                                     final_state=True)
        elif cfg.ssm_impl == "pallas" and cache is None:
            from repro.kernels.ops import ssd_scan as _ssd
            y = _ssd(xh, dt, A, Bg, Cg, chunk=chunk)
            s_final = None
        else:
            y, s_final = _ssd_xla(xh, dt, A, Bg, Cg, chunk)
        if cont:
            # exact initial-state continuation on top of the zero-init
            # scan: with s0 the cached state, s_t = s0·exp(Σ_{1..t} A·dt)
            # + (zero-init part), so y_t gains C_t·s0·exp(cumsum_t) and
            # the final state gains s0·exp(total decay).  Both terms are
            # exactly zero at s0 = 0, so a fresh chunk is bit-identical.
            s0 = cache["state"].astype(jnp.float32)            # (B,H,N,P)
            lp = jnp.cumsum(A[None, None, :] * dt.astype(jnp.float32),
                            axis=1)                            # (B,L,H)
            hpg = n_heads // s.n_groups
            Ch = jnp.repeat(Cg, hpg, axis=2).astype(jnp.float32)
            y_init = jnp.einsum("blhn,bhnp->blhp", Ch, s0) \
                * jnp.exp(lp)[..., None]
            y = (y.astype(jnp.float32) + y_init).astype(xh.dtype)
            s_final = s_final.astype(jnp.float32) \
                + s0 * jnp.exp(lp[:, -1])[..., None, None]
        y = y + p["D"].astype(y.dtype)[None, None, :, None] * xh
        y = y.reshape(b, l, d_in)
        new_cache = None
        if cache is not None:
            # prefill: store final SSD state + conv tail for decoding.  A
            # continuation chunk shorter than the conv window must keep the
            # earlier tokens' tail entries, so its tail comes off the
            # seeded window rather than zero-padded current tokens.
            wdt = s.conv_width
            if cont:
                tail = window[:, -(wdt - 1):, :]
            else:
                tail = jnp.pad(xbc,
                               ((0, 0), (max(0, wdt - 1 - l), 0), (0, 0))
                               )[:, -(wdt - 1):, :]
            new_cache = {"state": s_final.astype(cache["state"].dtype),
                         "conv": tail.astype(cache["conv"].dtype),
                         "pos": cache["pos"] + l}

    y = rmsnorm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    return dense(p["out_proj"], y), new_cache


def mamba2_cache_init(cfg, batch: int, dtype=jnp.bfloat16,
                      per_slot_pos: bool = False):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    n_heads = d_in // s.head_dim
    conv_dim = d_in + 2 * s.n_groups * s.state_dim
    # the recurrent step itself is position-free (state + conv tail carry
    # all history), so per-slot mode only changes the pos bookkeeping leaf
    return {
        "state": jnp.zeros((batch, n_heads, s.state_dim, s.head_dim), jnp.float32),
        "conv": jnp.zeros((batch, s.conv_width - 1, conv_dim), dtype),
        "pos": jnp.zeros((batch,) if per_slot_pos else (), jnp.int32),
    }
