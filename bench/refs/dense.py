"""Plain reference of a dense decoder language model, in float32.

Token embedding, then every layer's pre-norm causal self-attention (rotary
positions, rotate-half) and gated MLP, each residual, then a final RMS
norm and the LM head. Written from the layer equations, not from the
program: attention is a full causal softmax of one sequence at a time,
and every product runs at HIGHEST precision. It imports nothing of the
program and reads only the weights the benchmark made, by name.

``prec="fp8"`` is the control: every matrix product takes its operands
through float8 e4m3, with a scale per weight column and per activation
row, as a float8 deployment would.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _q8(a, axis):
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(a, w, prec):
    """a (..., K) @ w (K, N) in float32, or through float8 for the
    control."""
    a = a.astype(F32)
    w = w.astype(F32)
    if prec == "fp8":
        a, w = _q8(a, -1), _q8(w, 0)
    return jnp.einsum("...k,kn->...n", a, w, precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(F32)


def _rope(t, theta):
    """Rotate-half rotary embedding over (B, L, H, hd)."""
    hd = t.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(t.shape[1], dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    t1, t2 = t[..., :hd // 2], t[..., hd // 2:]
    return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], -1)


def _attention_block(p, x, m, prec):
    """Pre-norm causal self-attention and gated MLP, each residual."""
    b, l, d = x.shape
    nh, nkv = m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or d // nh
    eps = m["norm_eps"]
    h = _rms(x, p["ln1"]["scale"], eps)
    at = p["attn"]
    q = _rope(_mm(h, at["wq"]["w"], prec).reshape(b, l, nh, hd),
              m["rope_theta"])
    k = _rope(_mm(h, at["wk"]["w"], prec).reshape(b, l, nkv, hd),
              m["rope_theta"])
    v = _mm(h, at["wv"]["w"], prec).reshape(b, l, nkv, hd)
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    mask = jnp.tril(jnp.ones((l, l), bool))

    def one(args):                      # one sequence at a time
        qi, ki, vi = args
        s = jnp.einsum("qhd,khd->hqk", qi, ki, precision=HI) / math.sqrt(hd)
        s = jnp.where(mask[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), vi,
                          precision=HI)

    o = jax.lax.map(one, (q, k, v)).reshape(b, l, nh * hd)
    x = x + _mm(o, at["wo"]["w"], prec)
    h = _rms(x, p["ln2"]["scale"], eps)
    mlp = p["mlp"]
    if "gate" in mlp:
        u = jax.nn.silu(_mm(h, mlp["gate"]["w"], prec)) * \
            _mm(h, mlp["up"]["w"], prec)
    else:
        u = jax.nn.gelu(_mm(h, mlp["up"]["w"], prec))
    return x + _mm(u, mlp["down"]["w"], prec)


@functools.partial(jax.jit, static_argnames=("m", "prec"))
def _block_jit(p, x, m, prec):
    return _attention_block(p, x, dict(m), prec)


@functools.partial(jax.jit, static_argnames=("m", "prec", "start"))
def _head(params, x, m, prec, start):
    m = dict(m)
    x = _rms(x[:, start:], params["ln_f"]["scale"], m["norm_eps"])
    table = params["embed" if m.get("tie_embeddings") else "unembed"]
    w = table["table"].astype(F32)
    if prec == "fp8":
        x, w = _q8(x, -1), _q8(w, -1)
    return jnp.einsum("bld,vd->blv", x, w, precision=HI)


def logits(params, m: dict, tokens, start: int, prec: str = "f32"):
    """Logits (B, L - start, V) at positions ``start..L-1`` of ``tokens``
    (B, L), layer by layer."""
    key = tuple(sorted((k, v) for k, v in m.items()
                       if isinstance(v, (int, float, str, bool))))
    x = params["embed"]["table"].astype(F32)[tokens]
    blocks = params["blocks"]
    for i in range(jax.tree.leaves(blocks)[0].shape[0]):
        x = _block_jit(jax.tree.map(lambda t: t[i], blocks), x, key, prec)
    return _head(params, x, key, prec, start)
