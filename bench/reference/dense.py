"""Dense decoder forward in plain float32 ``jax.numpy``.

Follows the published description of the Qwen3 and Granite decoders:
token embedding, then per layer RMSNorm -> grouped-query attention (with
RMSNorm over each query and key head where ``qk_norm`` is set, rotary
position embedding of the half-split form, causal softmax) -> residual ->
RMSNorm -> SwiGLU -> residual, then a final RMSNorm and the tied head.
Every matrix product runs at ``jax.default_matmul_precision("highest")``.

Departures from the published models, shared with the program: Granite's
four scalar multipliers (embedding, attention, residual, logits) run at
their neutral values, as the configuration file states under ``reduced``.

The forward runs in blocks: one scanned layer at a time, with that layer's
weights cast to float32 inside the scan body, and the head over chunks of
positions.  It never materialises logits for more than one chunk; callers
get reductions of them (``gaps``) instead.

``quant='fp8'`` is the lower-precision control: the inputs of every weight
matrix product (activations per row, weights per output column) are
rounded to float8 e4m3 with a scale that maps their largest magnitude to
448, the format's largest finite value.  Attention itself stays float32.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

F8_MAX = 448.0
HEAD_CHUNK = 128


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _matmul(x, w, quant):
    """x (..., k) @ w (k, n) in float32, or through fp8 when ``quant``."""
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x = _fp8(x, axis=-1)
        w = _fp8(w, axis=0)
    return x @ w


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """Half-split rotary embedding. x: (R, T, heads, hd); pos: (T,)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv          # (T, hd/2)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def dims(cfg: Dict) -> Dict:
    """The sizes the forward needs, from a configuration file's keys."""
    h = cfg["num_attention_heads"]
    d = cfg["hidden_size"]
    return dict(d=d, h=h, hkv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // h,
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]),
                qk_norm=bool(cfg["qk_norm"]))


def _layer(x, p, pos, k, quant):
    r, t, _ = x.shape
    h, hkv, hd = k["h"], k["hkv"], k["hd"]
    a = _rmsnorm(x, p["norm1"]["scale"], k["eps"])
    q = _matmul(a, p["attn"]["wq"]["w"], quant).reshape(r, t, h, hd)
    kk = _matmul(a, p["attn"]["wk"]["w"], quant).reshape(r, t, hkv, hd)
    v = _matmul(a, p["attn"]["wv"]["w"], quant).reshape(r, t, hkv, hd)
    if k["qk_norm"]:
        q = _rmsnorm(q, p["attn"]["q_norm"]["scale"], k["eps"])
        kk = _rmsnorm(kk, p["attn"]["k_norm"]["scale"], k["eps"])
    q = _rope(q, pos, k["theta"])
    kk = _rope(kk, pos, k["theta"])
    g = h // hkv
    qg = q.reshape(r, t, hkv, g, hd)
    s = jnp.einsum("rtkgd,rukd->rkgtu", qg, kk) * hd ** -0.5
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None, None, None], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("rkgtu,rukd->rtkgd", pr, v).reshape(r, t, h * hd)
    x = x + _matmul(o, p["attn"]["wo"]["w"], quant)
    a = _rmsnorm(x, p["norm2"]["scale"], k["eps"])
    gate = _matmul(a, p["mlp"]["gate"]["w"], quant)
    up = _matmul(a, p["mlp"]["up"]["w"], quant)
    return x + _matmul(jax.nn.silu(gate) * up, p["mlp"]["down"]["w"], quant)


def hidden(params, tokens, k, quant=None):
    """Final-normed hidden states (R, T, d) for token rows (R, T)."""
    x = params["embed"]["embedding"][tokens].astype(jnp.float32)
    pos = jnp.arange(tokens.shape[1])

    def body(xc, p):
        return _layer(xc, p, pos, k, quant), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    return _rmsnorm(x, params["final_norm"]["scale"], k["eps"])


def _head_chunks(params, h, quant, fn):
    """Apply ``fn(logits_chunk, start)`` over position chunks of the tied
    head; stacks the per-chunk results along the position axis."""
    emb = params["embed"]["embedding"]
    r, t, d = h.shape
    n = -(-t // HEAD_CHUNK)
    hp = jnp.pad(h, ((0, 0), (0, n * HEAD_CHUNK - t), (0, 0)))
    hc = hp.reshape(r, n, HEAD_CHUNK, d).swapaxes(0, 1)

    def body(_, xs):
        i, x = xs
        logits = _matmul(x, emb.T, quant)                 # (R, C, V)
        return None, fn(logits, i * HEAD_CHUNK)

    _, out = jax.lax.scan(body, None, (jnp.arange(n), hc))
    out = jax.tree.map(lambda a: a.swapaxes(0, 1).reshape(
        (r, n * HEAD_CHUNK) + a.shape[3:])[:, :t], out)
    return out


@functools.partial(jax.jit, static_argnames=("kdims", "quant"))
def _gaps(params, tokens, query, kdims, quant=None):
    k = dict(kdims)
    with jax.default_matmul_precision("highest"):
        h = hidden(params, tokens, k, quant)
        qp = jnp.pad(query, ((0, 0), (0, -(-tokens.shape[1] // HEAD_CHUNK)
                                       * HEAD_CHUNK - tokens.shape[1]),
                             (0, 0)))

        def fn(logits, start):
            qc = jax.lax.dynamic_slice_in_dim(qp, start, HEAD_CHUNK, axis=1)
            best = jnp.max(logits, axis=-1, keepdims=True)
            picked = jnp.take_along_axis(logits, qc, axis=-1)
            return best - picked, jnp.argmax(logits, axis=-1).astype(
                jnp.int32)

        return _head_chunks(params, h, quant, fn)


def gaps(params, cfg: Dict, tokens, query, quant: Optional[str] = None):
    """Per position of each row: how far the logit of each ``query`` token
    lies below the best logit, and the argmax token.

    tokens: (R, T) int32 inputs; query: (R, T, Q) int32 token ids, one set
    per position.  Returns ``(gap (R, T, Q) float32, argmax (R, T) int32)``.
    """
    kd = tuple(sorted(dims(cfg).items()))
    return _gaps(params, jnp.asarray(tokens, jnp.int32),
                 jnp.asarray(query, jnp.int32), kd, quant)


@functools.partial(jax.jit, static_argnames=("kdims", "quant"))
def _logits(params, tokens, kdims, quant=None):
    with jax.default_matmul_precision("highest"):
        h = hidden(params, tokens, dict(kdims), quant)
        return _head_chunks(params, h, quant, lambda lg, _s: lg)


def logits(params, cfg: Dict, tokens, quant: Optional[str] = None):
    """Whole logits (R, T, V): for small sizes only (tests)."""
    kd = tuple(sorted(dims(cfg).items()))
    return _logits(params, jnp.asarray(tokens, jnp.int32), kd, quant)
