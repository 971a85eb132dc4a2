"""Core pure-JAX layers: norms, dense, MLPs, RoPE, embeddings.

All modules are (init, apply) function pairs over plain dict pytrees — no
framework dependency. ``shard`` is an optional callback
``(logical_name, array) -> array`` used by the distribution layer to insert
``with_sharding_constraint``; models stay mesh-agnostic.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

Identity = lambda name, x: x


def kv_splits(shard) -> int:
    """Into how many equal parts the distribution layer splits a KV
    cache's sequence axis across devices (a ``shard`` callback's
    ``kv_splits``); 1 for a callback that splits nothing."""
    return getattr(shard, "kv_splits", 1)


def truncated_normal(key, shape, std, dtype=jnp.float32):
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                             jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(dim: int) -> dict:
    return {"scale": jnp.ones((dim,), jnp.float32)}


def rms_norm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"]).astype(dtype)


def init_layernorm(dim: int) -> dict:
    return {"scale": jnp.ones((dim,), jnp.float32),
            "bias": jnp.zeros((dim,), jnp.float32)}


def layer_norm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * params["scale"] + params["bias"]).astype(dtype)


# ---------------------------------------------------------------------------
# Dense / MLP
# ---------------------------------------------------------------------------

def init_dense(key, d_in: int, d_out: int, dtype=jnp.float32,
               bias: bool = False) -> dict:
    std = 1.0 / math.sqrt(d_in)
    p = {"w": truncated_normal(key, (d_in, d_out), std, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(params: dict, x: jax.Array) -> jax.Array:
    y = x @ params["w"].astype(x.dtype)
    if "b" in params:
        y = y + params["b"].astype(x.dtype)
    return y


def init_mlp(key, d_model: int, d_ff: int, gated: bool,
             dtype=jnp.float32) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"up": init_dense(k1, d_model, d_ff, dtype),
         "down": init_dense(k2, d_ff, d_model, dtype)}
    if gated:
        p["gate"] = init_dense(k3, d_model, d_ff, dtype)
    return p


def mlp(params: dict, x: jax.Array, gated: bool, shard=Identity) -> jax.Array:
    h = dense(params["up"], x)
    if gated:
        h = jax.nn.silu(dense(params["gate"], x)) * h
    else:
        h = jax.nn.gelu(h)
    h = shard("ffn_hidden", h)
    return dense(params["down"], h)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               rope_dim: int = 0, interleaved: bool = False) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq).

    Rotates the first ``rope_dim`` dims of each head (0: all of them) at
    frequencies ``theta ** (-2i / rope_dim)`` and passes the rest
    through. Pair i is (i, i + rope_dim/2) (rotate-half) or, with
    ``interleaved``, the adjacent (2i, 2i+1)."""
    hd = x.shape[-1]
    rd = rope_dim or hd
    freqs = rope_freqs(rd, theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (.., L, rd/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    xf = x.astype(jnp.float32)
    xr = xf if rd == hd else xf[..., :rd]
    if interleaved:
        pairs = xr.reshape(xr.shape[:-1] + (rd // 2, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                        axis=-1).reshape(xr.shape)
    else:
        x1, x2 = jnp.split(xr, 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                              axis=-1)
    if rd < hd:
        out = jnp.concatenate([out, xf[..., rd:]], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embeddings
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d_model: int, dtype=jnp.float32) -> dict:
    return {"table": truncated_normal(key, (vocab, d_model), 0.02, dtype)}


def embed(params: dict, ids: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return params["table"].astype(dtype)[ids]


def unembed(params: dict, x: jax.Array) -> jax.Array:
    return x @ params["table"].astype(x.dtype).T
