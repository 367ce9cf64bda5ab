"""Attention variants: GQA (qk-norm / qkv-bias options) and DeepSeek MLA.

Two execution paths, selected by ``cfg.attention_impl``:

  - ``xla_chunked``: a pure-jnp flash-style attention — ``lax.scan`` over KV
    blocks with an online-softmax carry.  This *is* temporal vectorization in
    XLA form: the KV stream is consumed in wide blocks while the softmax
    dependency chain stays sequential.  Memory is O(S·block), so 32k prefill
    lowers without materializing S×S logits.  Differentiable; used by the
    dry-run and trainer.
  - ``pallas``: the compiler-emitted flash-attention kernel (interpret
    mode only when no TPU is attached) — the serving path on the chip.

Decode attends one query token against a preallocated KV cache.  Under
``attention_impl='pallas'`` + ``kernel_plan='measure'`` (the serving
default) the step routes through the compiled decode kernel — the plan
registry buckets the attended prefix on pos and replays the measured pump
plan — while the plain-jnp O(T) softmax stays as the ``'direct'``
differential reference.  MLA caches the *compressed* c_kv + rope key
(576 B/token for deepseek-v3) and uses the absorbed-matmul decode path.

Cache positions come in two shapes.  A scalar ``pos`` is the classic
one-batch-at-a-time engine: every row is at the same depth.  A **per-slot**
``pos`` vector ``(B,)`` is the continuous-batching engine
(:mod:`repro.serve.scheduler`): each cache row is an independent decode
lane at its own depth, so the single-token write mask, the KV validity
mask and the rope positions are all per-row.  The vector form is
decode-only (S == 1) — slot prefill always runs on a fresh scalar-pos
cache and is scattered into its lane afterwards.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import layers
from .layers import apply_rope, dense, dense_init, rmsnorm, rmsnorm_init

NEG_INF = -1e30


def _rope_positions(positions):
    """Broadcast shape for ``apply_rope`` over (B, H, S, D) heads: accepts
    the classic per-step ``(S,)`` vector or per-slot ``(B, S)`` ragged
    positions (continuous batching — each batch row at its own depth)."""
    return positions[None, :] if positions.ndim == 1 \
        else positions[:, None, :]


def _kv_valid_mask(length: int, pos, s: int):
    """Valid-slot mask for a cache of ``length`` after writing ``s`` tokens
    at ``pos``: ``(length,)`` for a scalar pos, ``(B, length)`` per-slot."""
    idx = jnp.arange(length)
    if jnp.ndim(pos):
        return idx[None, :] < (pos[:, None] + s)
    return idx < (pos + s)


def _flash_kernel(cfg, q, k, v, *, causal):
    """Flash-attention kernel dispatch for the ``pallas`` impl paths.

    ``cfg.kernel_plan == 'measure'`` (default) routes through the process
    plan registry: shapes pad to buckets and the pump factor replays the
    measured-runtime winner, so serving decode/prefill hits a warm plan in
    O(1).  ``'direct'`` keeps the raw ``kernels.ops`` call (default pump) —
    the differential reference for the registry path."""
    if cfg.kernel_plan == "measure":
        from repro.compiler.registry import default_registry
        return default_registry().flash_attention(q, k, v, causal=causal)
    from repro.kernels.ops import flash_attention as _flash
    return _flash(q, k, v, causal=causal)


# ------------------------------------------------------------ core attention
def chunked_attention(q, k, v, *, causal: bool, q_pos=None, kv_mask=None,
                      block: int = 1024, scale: float | None = None):
    """Flash-style attention via lax.scan over KV blocks.

    q: (B, H, S, D); k/v: (B, Hkv, T, Dk/Dv).  GQA folded by reshaping q into
    (B, Hkv, G, S, D).  Returns (B, H, S, Dv).
    """
    b, h, s, d = q.shape
    _, hkv, t, dk = k.shape
    dv = v.shape[-1]
    g = h // hkv
    scale = scale if scale is not None else d ** -0.5
    block = min(block, t)
    nblk = -(-t // block)
    tpad = nblk * block

    if tpad != t:
        pad = [(0, 0), (0, 0), (0, tpad - t), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
        base_mask = jnp.arange(tpad) < t
    else:
        base_mask = jnp.ones((tpad,), bool)
    if kv_mask is not None:
        base_mask = base_mask & jnp.pad(kv_mask, (0, tpad - t),
                                        constant_values=False)
    if q_pos is None:
        q_pos = jnp.arange(s)

    qg = q.reshape(b, hkv, g, s, d).astype(jnp.float32) * scale
    kb = k.reshape(b, hkv, nblk, block, dk).astype(jnp.float32)
    vb = v.reshape(b, hkv, nblk, block, dv).astype(jnp.float32)
    mb = base_mask.reshape(nblk, block)

    def step(carry, inputs):
        m_run, l_run, acc = carry
        kc, vc, mask_c, kpos = inputs          # (b,hkv,block,dk) ...
        sblk = jnp.einsum("bkgsd,bktd->bkgst", qg, kc)
        mask = mask_c[None, None, None, None, :]
        if causal:
            mask = mask & (q_pos[:, None] >= kpos[None, :])[None, None, None]
        sblk = jnp.where(mask, sblk, NEG_INF)
        m_new = jnp.maximum(m_run, sblk.max(axis=-1))
        p = jnp.exp(sblk - m_new[..., None])
        alpha = jnp.exp(m_run - m_new)
        l_new = l_run * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bkgst,bktd->bkgsd", p, vc)
        return (m_new, l_new, acc), None

    kb_t = jnp.moveaxis(kb, 2, 0)              # (nblk, b, hkv, block, dk)
    vb_t = jnp.moveaxis(vb, 2, 0)
    kpos_t = jnp.arange(tpad).reshape(nblk, block)
    init = (jnp.full((b, hkv, g, s), NEG_INF, jnp.float32),
            jnp.zeros((b, hkv, g, s), jnp.float32),
            jnp.zeros((b, hkv, g, s, dv), jnp.float32))
    (m_run, l_run, acc), _ = jax.lax.scan(step, init, (kb_t, vb_t, mb, kpos_t))
    l_run = jnp.where(l_run == 0.0, 1.0, l_run)
    out = acc / l_run[..., None]
    return out.reshape(b, h, s, dv).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, kv_mask, *, scale=None):
    """Single-position attention. q: (B, H, D); caches: (B, Hkv, T, D)."""
    b, h, d = q.shape
    hkv, t = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    scale = scale if scale is not None else d ** -0.5
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32) * scale
    s = jnp.einsum("bkgd,bktd->bkgt", qg, k_cache.astype(jnp.float32))
    s = jnp.where(kv_mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,bktd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, h, v_cache.shape[-1]).astype(q.dtype)


# --------------------------------------------------------------- GQA module
def gqa_init(key, cfg, dtype=jnp.float32):
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, h * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": dense_init(ks[1], d, hkv * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": dense_init(ks[2], d, hkv * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": dense_init(ks[3], h * hd, d, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def gqa_apply(p, cfg, x, *, positions, causal=True, cache=None,
              kv_input=None):
    """GQA attention.  x: (B, S, d).  Returns (out, new_cache).

    ``kv_input`` (B, T, d) switches to cross-attention (no cache, no causal).
    ``cache``: dict(k, v, pos) for incremental decode (S == 1).
    """
    b, s, d = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    kv_src = kv_input if kv_input is not None else x
    t = kv_src.shape[1]

    q = dense(p["wq"], x).reshape(b, s, h, hd)
    k = dense(p["wk"], kv_src).reshape(b, t, hkv, hd)
    v = dense(p["wv"], kv_src).reshape(b, t, hkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    if kv_input is None:  # self-attention: rope
        rp = _rope_positions(positions)
        q = apply_rope(q.swapaxes(1, 2), rp, cfg.rope_theta).swapaxes(1, 2)
        k = apply_rope(k.swapaxes(1, 2), rp, cfg.rope_theta).swapaxes(1, 2)

    q = q.swapaxes(1, 2)   # (B, H, S, hd)
    k = k.swapaxes(1, 2)
    v = v.swapaxes(1, 2)

    new_cache = None
    if cache is not None:
        # write current kv at position, attend over the whole cache
        pos = cache["pos"]
        if s == 1:
            # mask-based single-token write: elementwise on the (possibly
            # sequence-sharded) cache, so GSPMD keeps it shard-local —
            # dynamic_update_slice at a traced offset forced one cache
            # shard through collectives per layer per token
            # (EXPERIMENTS.md §Perf E1).  A per-slot pos vector makes the
            # mask per-row: each decode lane writes at its own depth.
            idx = jnp.arange(cache["k"].shape[2])
            tmask = ((idx[None, :] == pos[:, None])[:, None, :, None]
                     if jnp.ndim(pos)
                     else (idx == pos)[None, None, :, None])
            kc = jnp.where(tmask, k.astype(cache["k"].dtype), cache["k"])
            vc = jnp.where(tmask, v.astype(cache["v"].dtype), cache["v"])
        else:
            if jnp.ndim(pos):
                raise ValueError(
                    "per-slot cache positions are decode-only (S == 1): "
                    "prefill runs on a fresh scalar-pos cache and is "
                    "scattered into its slot (serve.scheduler.insert_rows)")
            kc = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, pos, 0))
            vc = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, pos, 0))
        new_cache = {"k": kc, "v": vc, "pos": pos + s}
        kv_mask = _kv_valid_mask(kc.shape[2], pos, s)
        if s == 1:
            if cfg.attention_impl == "pallas" and cfg.kernel_plan == "measure":
                # kernelized decode: the plan registry buckets the attended
                # cache prefix (pow2 over pos) and replays the measured pump
                # plan; the kernel's position mask covers slots 0..pos —
                # exactly kv_mask for the just-written cache
                from repro.compiler.registry import default_registry
                out = default_registry().decode_attention(q[:, :, 0], kc, vc,
                                                          pos)
            else:
                out = decode_attention(
                    q[:, :, 0], kc, vc,
                    jnp.broadcast_to(kv_mask, (b, kc.shape[2])))
            out = out[:, :, None, :]
        elif cfg.attention_impl == "pallas" and cfg.fresh_prefill_kernel:
            # fresh-cache prefill (pos == 0 — the flag's contract, set by
            # the serve Engine whose prefill always builds a new cache):
            # attention over the just-written cache under kv_mask equals
            # causal attention over the current tokens' k/v, which the
            # plan-registry kernel serves from a warm measured plan.  The
            # kernel's causal mask is position-relative, so the contract is
            # enforced at runtime: a pos > 0 continuation (traced pos —
            # unknowable here) selects the position-aware chunked branch.
            out = jax.lax.cond(
                pos == 0,
                lambda: _flash_kernel(cfg, q, k, v, causal=causal),
                lambda: chunked_attention(q, kc, vc, causal=causal,
                                          q_pos=positions, kv_mask=kv_mask,
                                          block=cfg.attn_block_kv))
        else:
            # prefill into the cache (assumes contiguous fill from `pos`)
            out = chunked_attention(q, kc, vc, causal=causal,
                                    q_pos=positions, kv_mask=kv_mask,
                                    block=cfg.attn_block_kv)
    elif cfg.attention_impl == "pallas" and kv_input is None:
        out = _flash_kernel(cfg, q, k, v, causal=causal)
    else:
        out = chunked_attention(q, k, v, causal=causal and kv_input is None,
                                q_pos=positions, block=cfg.attn_block_kv)
    out = out.swapaxes(1, 2).reshape(b, s, h * hd)
    return dense(p["wo"], out), new_cache


def gqa_cache_init(cfg, batch: int, max_len: int, dtype=jnp.bfloat16,
                   per_slot_pos: bool = False):
    hkv, hd = cfg.n_kv_heads, cfg.head_dim_
    pos_shape = (batch,) if per_slot_pos else ()
    return {"k": jnp.zeros((batch, hkv, max_len, hd), dtype),
            "v": jnp.zeros((batch, hkv, max_len, hd), dtype),
            "pos": jnp.zeros(pos_shape, jnp.int32)}


# --------------------------------------------------------------- MLA module
def mla_init(key, cfg, dtype=jnp.float32):
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    dn, dr, dv, kvr = m.nope_head_dim, m.rope_head_dim, m.v_head_dim, \
        m.kv_lora_rank
    ks = jax.random.split(key, 6)
    p = {}
    if m.q_lora_rank:
        p["wq_a"] = dense_init(ks[0], d, m.q_lora_rank, dtype=dtype)
        p["q_norm"] = rmsnorm_init(m.q_lora_rank, dtype)
        p["wq_b"] = dense_init(ks[1], m.q_lora_rank, h * (dn + dr), dtype=dtype)
    else:
        p["wq"] = dense_init(ks[0], d, h * (dn + dr), dtype=dtype)
    p["wkv_a"] = dense_init(ks[2], d, kvr + dr, dtype=dtype)
    p["kv_norm"] = rmsnorm_init(kvr, dtype)
    p["wkv_b"] = dense_init(ks[3], kvr, h * (dn + dv), dtype=dtype)
    p["wo"] = dense_init(ks[4], h * dv, d, dtype=dtype)
    return p


def _mla_q(p, cfg, x):
    m = cfg.mla
    h, dn, dr = cfg.n_heads, m.nope_head_dim, m.rope_head_dim
    b, s, _ = x.shape
    if m.q_lora_rank:
        q = dense(p["wq_b"], rmsnorm(p["q_norm"], dense(p["wq_a"], x),
                                     cfg.norm_eps))
    else:
        q = dense(p["wq"], x)
    q = q.reshape(b, s, h, dn + dr)
    return q[..., :dn], q[..., dn:]


def mla_apply(p, cfg, x, *, positions, causal=True, cache=None):
    """MLA attention.  Prefill/train: decompressed path + chunked flash.
    Decode: absorbed path over the compressed cache."""
    m = cfg.mla
    b, s, d = x.shape
    h = cfg.n_heads
    dn, dr, dv, kvr = m.nope_head_dim, m.rope_head_dim, m.v_head_dim, \
        m.kv_lora_rank
    scale = (dn + dr) ** -0.5

    q_nope, q_rope = _mla_q(p, cfg, x)
    rp = _rope_positions(positions)
    q_rope = apply_rope(q_rope.swapaxes(1, 2), rp,
                        cfg.rope_theta).swapaxes(1, 2)

    kv_a = dense(p["wkv_a"], x)
    c_kv = rmsnorm(p["kv_norm"], kv_a[..., :kvr], cfg.norm_eps)  # (B,S,kvr)
    k_rope = apply_rope(kv_a[..., None, kvr:].swapaxes(1, 2),
                        rp, cfg.rope_theta).swapaxes(1, 2)
    # k_rope: (B, S, 1, dr) shared over heads

    if cache is not None and s > 1:
        # prefill: write the compressed cache, attend over current tokens
        pos = cache["pos"]
        if jnp.ndim(pos):
            raise ValueError(
                "per-slot cache positions are decode-only (S == 1): "
                "prefill runs on a fresh scalar-pos cache and is "
                "scattered into its slot (serve.scheduler.insert_rows)")
        ckv_c = jax.lax.dynamic_update_slice(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, pos, 0))
        krope_c = jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope[:, :, 0].astype(cache["k_rope"].dtype),
            (0, pos, 0))
        new_cache = {"c_kv": ckv_c, "k_rope": krope_c, "pos": pos + s}
        if cfg.prefill_continuation:
            # continuation chunk (pos > 0): the current tokens must attend
            # over the WHOLE written cache, not just this chunk — the
            # compressed prefix is decompressed through wkv_b (same math
            # as decode's absorbed path, unabsorbed) and masked to the
            # valid pos + s slots.  At pos == 0 the mask reduces this to
            # the chunk-local computation below.
            t = ckv_c.shape[1]
            kv = dense(p["wkv_b"], ckv_c.astype(x.dtype)) \
                .reshape(b, t, h, dn + dv)
            k_nope, v = kv[..., :dn], kv[..., dn:]
            k = jnp.concatenate(
                [k_nope,
                 jnp.broadcast_to(krope_c[:, :, None, :].astype(x.dtype),
                                  (b, t, h, dr))], axis=-1)
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            q, k, v = (u.swapaxes(1, 2) for u in (q, k, v))
            out = chunked_attention(q, k, v, causal=causal,
                                    q_pos=positions,
                                    kv_mask=_kv_valid_mask(t, pos, s),
                                    block=cfg.attn_block_kv, scale=scale)
            out = out.swapaxes(1, 2).reshape(b, s, h * dv)
            return dense(p["wo"], out), new_cache
        kv = dense(p["wkv_b"], c_kv).reshape(b, s, h, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, s, h, dr))], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        q, k, v = (u.swapaxes(1, 2) for u in (q, k, v))
        if cfg.attention_impl == "pallas" and dn + dr == dv \
                and cfg.fresh_prefill_kernel:
            # fresh-cache serving prefill: the registry kernel replaces
            # chunked attention over the current tokens; the runtime cond
            # keeps any pos > 0 continuation on the reference chunked path
            out = jax.lax.cond(
                pos == 0,
                lambda: _flash_kernel(cfg, q, k, v, causal=causal),
                lambda: chunked_attention(q, k, v, causal=causal,
                                          q_pos=positions,
                                          block=cfg.attn_block_kv,
                                          scale=scale))
        else:
            out = chunked_attention(q, k, v, causal=causal, q_pos=positions,
                                    block=cfg.attn_block_kv, scale=scale)
        out = out.swapaxes(1, 2).reshape(b, s, h * dv)
        return dense(p["wo"], out), new_cache

    if cache is not None:
        pos = cache["pos"]
        if jnp.ndim(pos):
            # per-slot decode lanes: mask-based write at each row's depth
            wm = (jnp.arange(cache["c_kv"].shape[1])[None, :]
                  == pos[:, None])[:, :, None]            # (B, T, 1)
            ckv_c = jnp.where(wm, c_kv.astype(cache["c_kv"].dtype),
                              cache["c_kv"])
            krope_c = jnp.where(
                wm, k_rope[:, :, 0].astype(cache["k_rope"].dtype),
                cache["k_rope"])
        else:
            ckv_c = jax.lax.dynamic_update_slice(
                cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, pos, 0))
            krope_c = jax.lax.dynamic_update_slice(
                cache["k_rope"],
                k_rope[:, :, 0].astype(cache["k_rope"].dtype), (0, pos, 0))
        new_cache = {"c_kv": ckv_c, "k_rope": krope_c, "pos": pos + s}
        t = ckv_c.shape[1]
        kv_mask = _kv_valid_mask(t, pos, s)
        # absorbed decode: w_uk (kvr, h, dn), w_uv (kvr, h, dv).
        # All cache-touching einsums run on the NATIVE (bf16) cache with
        # fp32 accumulation (preferred_element_type) — materializing an
        # fp32 copy of the compressed cache doubled decode HBM traffic
        # (EXPERIMENTS.md §Perf B2).
        wkv_b = p["wkv_b"]["w"].reshape(kvr, h, dn + dv)
        w_uk, w_uv = wkv_b[..., :dn], wkv_b[..., dn:]
        q_abs = jnp.einsum("bhd,khd->bhk", q_nope[:, 0], w_uk,
                           preferred_element_type=jnp.float32)  # (B,H,kvr)
        sc = jnp.einsum("bhk,btk->bht", q_abs.astype(ckv_c.dtype), ckv_c,
                        preferred_element_type=jnp.float32)
        sc += jnp.einsum("bhr,btr->bht", q_rope[:, 0].astype(krope_c.dtype),
                         krope_c, preferred_element_type=jnp.float32)
        sc = jnp.where(kv_mask[:, None, :] if kv_mask.ndim == 2
                       else kv_mask[None, None, :], sc * scale, NEG_INF)
        attn = jax.nn.softmax(sc, axis=-1)
        out_c = jnp.einsum("bht,btk->bhk", attn.astype(ckv_c.dtype), ckv_c,
                           preferred_element_type=jnp.float32)
        out = jnp.einsum("bhk,khd->bhd", out_c.astype(w_uv.dtype), w_uv,
                         preferred_element_type=jnp.float32)
        out = out.reshape(b, 1, h * dv).astype(x.dtype)
        return dense(p["wo"], out), new_cache

    # prefill / train: decompress and run standard attention
    kv = dense(p["wkv_b"], c_kv).reshape(b, s, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, h, dr))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    q, k, v = (u.swapaxes(1, 2) for u in (q, k, v))
    if cfg.attention_impl == "pallas" and dn + dr == dv:
        out = _flash_kernel(cfg, q, k, v, causal=causal)
    else:
        out = chunked_attention(q, k, v, causal=causal, q_pos=positions,
                                block=cfg.attn_block_kv, scale=scale)
    out = out.swapaxes(1, 2).reshape(b, s, h * dv)
    return dense(p["wo"], out), None


def mla_cache_init(cfg, batch: int, max_len: int, dtype=jnp.bfloat16,
                   per_slot_pos: bool = False):
    m = cfg.mla
    pos_shape = (batch,) if per_slot_pos else ()
    return {"c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_len, m.rope_head_dim), dtype),
            "pos": jnp.zeros(pos_shape, jnp.int32)}
