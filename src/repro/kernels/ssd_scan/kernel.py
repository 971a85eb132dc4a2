"""SSD intra-chunk Pallas kernel (Mamba2 state-space duality).

Computes, per (batch-chunk, head) grid cell:
    y[t] = Σ_{τ<=t} (C_t·B_τ) · exp(s_t − s_τ) · dt_τ · x_τ

Fusion win vs the jnp reference: the (Q, Q) decay matrix is built inside
VMEM from the (Q,) cumsum vector instead of materializing a
(B, NC, Q, Q, H) tensor in HBM — the dominant memory term of the SSD
prefill path at 32k+ sequence lengths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_CLIP = -60.0   # exp(-60) == 0 in f32; avoids inf-inf NaNs


def _ssd_kernel(c_ref, b_ref, s_col_ref, s_ref, dt_ref, x_ref, y_ref, *,
                precision):
    c = c_ref[0].astype(jnp.float32)          # (Q, N)
    b = b_ref[0].astype(jnp.float32)          # (Q, N)
    s_col = s_col_ref[0].astype(jnp.float32)  # (Q, 1)
    s = s_ref[0].astype(jnp.float32)          # (1, Q)
    dt = dt_ref[0].astype(jnp.float32)        # (1, Q)
    x = x_ref[0].astype(jnp.float32)          # (Q, P)
    q = c.shape[0]
    seg = s_col - s                           # (Q, Q)
    tri = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    decay = jnp.exp(jnp.where(tri, jnp.maximum(seg, NEG_CLIP), NEG_CLIP))
    decay = jnp.where(tri, decay, 0.0)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 precision=precision,
                                 preferred_element_type=jnp.float32)
    scores = scores * decay * dt
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            precision=precision,
                            preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_intra_chunk_bh(c: jax.Array, b: jax.Array, s: jax.Array,
                       dt: jax.Array, x: jax.Array, *,
                       interpret: bool = False) -> jax.Array:
    """c, b: (BCH, Q, N); s, dt: (BCH, Q); x: (BCH, Q, P) -> (BCH, Q, P).
    BCH = batch * n_chunks * heads (flattened grid).

    ``s`` and ``dt`` enter the kernel as (BCH, 1, Q) rows, and ``s`` once
    more as (BCH, Q, 1) columns for the (Q, Q) decay: a (1, Q) block of a
    (BCH, Q) array is illegal on the chip once BCH > 1, since its
    second-minor dim is neither a multiple of 8 nor the whole dim.

    The dots run at HIGHEST precision for f32 inputs (the TPU's default
    passes f32 operands through bf16) and at the default for bf16 ones."""
    bch, qq, n = c.shape
    p = x.shape[-1]
    grid = (bch,)
    precision = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 \
        else None
    return pl.pallas_call(
        functools.partial(_ssd_kernel, precision=precision),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, qq, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, qq, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, qq, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, qq), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, qq), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, qq, p), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, qq, p), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bch, qq, p), x.dtype),
        interpret=interpret,
    )(c, b, s.reshape(bch, qq, 1), s.reshape(bch, 1, qq),
      dt.reshape(bch, 1, qq), x)
