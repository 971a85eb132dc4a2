"""Plain reference of GLM-4's decoder (arXiv:2406.12793; hf:THUDM/glm-4-9b
``modeling_chatglm.py``), in float32.

Token embedding, then in every layer: RMS norm; query, key and value
projections, each with its bias; rotary positions on the first
``rope_dim`` dims of every head, rotating the adjacent pair (2i, 2i+1)
by the angle ``t * theta ** (-2i / rope_dim)`` at position t and passing
the other dims through; causal softmax attention at ``1/sqrt(head_dim)``
in which each of the ``n_kv_heads`` key/value groups serves
``n_heads / n_kv_heads`` query heads; the output projection (no bias);
the residual; RMS norm; the SwiGLU MLP ``down(silu(gate h) * up h)``
(no bias); the residual. Then a final RMS norm and the untied LM head.

Written from those equations, not from the program: it imports nothing
of it and reads only the weights the benchmark made, by name, one layer
at a time in float32. Attention runs one sequence at a time in blocks of
``QBLOCK`` queries, so that a block's scores over the whole sequence fit
beside a layer's weights; every product runs at HIGHEST precision.

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
#: Queries per attention block.
QBLOCK = 512


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


def _linear(p, a, prec):
    y = _mm(a, p["w"], prec)
    return y + p["b"].astype(F32) if "b" in p else y


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(F32)


def rotary(t, rope_dim: int, theta: float):
    """GLM's rotary over (B, L, H, hd): adjacent pairs of the first
    ``rope_dim`` dims, the rest unchanged."""
    half = rope_dim // 2
    freqs = theta ** (-2.0 * jnp.arange(half, dtype=F32) / rope_dim)
    ang = jnp.arange(t.shape[1], dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    even, odd = t[..., 0:rope_dim:2], t[..., 1:rope_dim:2]
    rot = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return jnp.concatenate(
        [rot.reshape(t.shape[:-1] + (rope_dim,)), t[..., rope_dim:]], -1)


def _attend(q, k, v):
    """Causal attention of one sequence: q (L, G, R, hd), k and v
    (L, G, hd), in blocks of ``QBLOCK`` queries."""
    l, g, r, hd = q.shape
    nb = -(-l // QBLOCK)
    qb = jnp.pad(q, ((0, nb * QBLOCK - l),) + ((0, 0),) * 3).reshape(
        nb, QBLOCK, g, r, hd)
    kpos = jnp.arange(l)

    def block(args):
        i, qi = args
        s = jnp.einsum("qgrd,kgd->grqk", qi, k, precision=HI) / math.sqrt(hd)
        qpos = i * QBLOCK + jnp.arange(QBLOCK)
        s = jnp.where((kpos[None, :] <= qpos[:, None])[None, None], s,
                      -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(s, -1), v,
                          precision=HI)

    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.reshape(nb * QBLOCK, g, r, hd)[:l]


def _block(p, x, m, prec):
    b, l, d = x.shape
    nh, nkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    h = _rms(x, p["ln1"]["scale"], eps)
    at = p["attn"]
    q = _linear(at["wq"], h, prec).reshape(b, l, nh, hd)
    k = _linear(at["wk"], h, prec).reshape(b, l, nkv, hd)
    v = _linear(at["wv"], h, prec).reshape(b, l, nkv, hd)
    q = rotary(q, m["rope_dim"], m["rope_theta"])
    k = rotary(k, m["rope_dim"], m["rope_theta"])
    q = q.reshape(b, l, nkv, nh // nkv, hd)
    o = jax.lax.map(lambda a: _attend(*a), (q, k, v)).reshape(b, l, nh * hd)
    x = x + _linear(at["wo"], o, prec)
    h = _rms(x, p["ln2"]["scale"], eps)
    mlp = p["mlp"]
    u = jax.nn.silu(_linear(mlp["gate"], h, prec)) * \
        _linear(mlp["up"], h, prec)
    return x + _linear(mlp["down"], u, prec)


@functools.partial(jax.jit, static_argnames=("m", "prec"))
def _block_jit(p, x, m, prec):
    return _block(p, x, dict(m), prec)


@functools.partial(jax.jit, static_argnames=("m", "prec", "start"))
def _head(params, x, m, prec, start):
    m = dict(m)
    x = _rms(x[:, start:], params["ln_f"]["scale"], m["norm_eps"])
    w = params["unembed"]["table"].astype(F32)
    if prec == "fp8":
        x, w = _q8(x, -1), _q8(w, -1)
    return jnp.einsum("bld,vd->blv", x, w, precision=HI)


def logits(params, m: dict, tokens, start: int, prec: str = "f32"):
    """Logits (B, L - start, V) at positions ``start..L-1`` of ``tokens``
    (B, L), layer by layer."""
    m = dict(m, head_dim=m.get("head_dim") or m["d_model"] // m["n_heads"])
    m["rope_dim"] = m.get("rope_dim") or m["head_dim"]
    key = tuple(sorted((k, v) for k, v in m.items()
                       if isinstance(v, (int, float, str, bool))))
    x = params["embed"]["table"][tokens].astype(F32)
    blocks = params["blocks"]
    for i in range(jax.tree.leaves(blocks)[0].shape[0]):
        x = _block_jit(jax.tree.map(lambda t: t[i], blocks), x, key, prec)
    return _head(params, x, key, prec, start)
