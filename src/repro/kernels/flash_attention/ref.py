"""Pure-jnp oracle for flash attention."""

import math

import jax
import jax.numpy as jnp


def attention_ref(q, k, v, causal=True, sm_scale=None):
    """q,k,v: (B, L, H, hd) -> (B, L, H, hd), in f32 (HIGHEST: the TPU's
    default precision would pass the f32 operands through bf16), scores
    times ``sm_scale`` (None: ``1/sqrt(hd)``)."""
    hd = q.shape[-1]
    hi = jax.lax.Precision.HIGHEST
    scale = 1.0 / math.sqrt(hd) if sm_scale is None else sm_scale
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32), precision=hi) * scale
    if causal:
        lq, lk = q.shape[1], k.shape[1]
        mask = jnp.arange(lk)[None, :] <= jnp.arange(lq)[:, None]
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                      precision=hi).astype(q.dtype)
