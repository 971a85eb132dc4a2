"""Plain references of the executed plan's two kernel families, in float32.

``matmul``: (M, K) @ (K, N) at HIGHEST precision. ``attention``: softmax
attention over (B, L, H, hd), causal where asked, at HIGHEST precision,
one head at a time. They import nothing of the program.

The controls, one precision step below what each op states:
``matmul(..., prec="int4")`` takes both operands through symmetric int4
(a scale per row of x and per column of w), the step below the program's
int8; ``attention(..., prec="high")`` runs its products at HIGH (three
bfloat16 passes), the step below float32 at HIGHEST.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _q4(a, axis):
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 7.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(a / s), -7, 7) * s


@functools.partial(jax.jit, static_argnames=("prec",))
def matmul(x, w, prec: str = "f32"):
    x = x.astype(F32)
    w = w.astype(F32)
    if prec == "int4":
        x, w = _q4(x, 1), _q4(w, 0)
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("causal", "prec"))
def attention(q, k, v, causal: bool = True, prec: str = "f32"):
    p = jax.lax.Precision.HIGH if prec == "high" \
        else jax.lax.Precision.HIGHEST
    lq, lk, hd = q.shape[1], k.shape[1], q.shape[-1]
    visible = jnp.arange(lk)[None, :] <= jnp.arange(lq)[:, None] + (lk - lq)

    def head(args):
        qh, kh, vh = args                       # (B, L, hd)
        s = jnp.einsum("bqd,bkd->bqk", qh, kh, precision=p) / math.sqrt(hd)
        if causal:
            s = jnp.where(visible[None], s, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), vh,
                          precision=p)

    by_head = lambda t: jnp.moveaxis(t.astype(F32), 2, 0)
    out = jax.lax.map(head, (by_head(q), by_head(k), by_head(v)))
    return jnp.moveaxis(out, 0, 2)
