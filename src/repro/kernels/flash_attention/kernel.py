"""Causal flash attention Pallas kernel (online-softmax, VMEM-tiled).

Grid (batch*heads, Lq/block_q); each step streams K/V blocks up to the
causal frontier with running (max, sum, acc) in VMEM scratch. Block sizes
are MXU/VPU aligned (multiples of 128 lanes); the MIREDO TPU bridge checks
the VMEM working set (q + k + v + acc blocks, x2 for pipelining) against
capacity — eq. (9) with psi^DM = 1.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.chips import VMEM_LIMIT_BYTES

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  block_q: int, block_k: int, seq_k: int, sm_scale: float,
                  causal: bool, precision):
    qi = pl.program_id(1)
    kv_step = pl.program_id(2)

    @pl.when(kv_step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = kv_step * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    def attend():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=precision,
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        if causal:
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # skip fully-masked KV blocks beyond the causal frontier
        first_masked = (qi + 1) * block_q  # k positions >= this are masked
        pl.when(kv_step * block_k < first_masked)(attend)
    else:
        attend()

    @pl.when(kv_step == (seq_k // block_k) - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "causal",
                                             "sm_scale", "interpret"))
def flash_attention_bh(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       block_q: int = 256, block_k: int = 256,
                       causal: bool = True, sm_scale: float | None = None,
                       interpret: bool = False) -> jax.Array:
    """q, k, v: (BH, L, hd) -> (BH, L, hd). ``sm_scale`` multiplies the
    scores before the softmax (None: ``1/sqrt(hd)``).

    The dots run in the precision of the inputs: f32 inputs at HIGHEST
    (the TPU's default passes f32 operands through bf16), bf16 inputs at
    the default, which is exact for them."""
    bh, lq, hd = q.shape
    lk = k.shape[1]
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    assert lq % block_q == 0 and lk % block_k == 0
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    precision = jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 \
        else None
    grid = (bh, lq // block_q, lk // block_k)
    return pl.pallas_call(
        functools.partial(_flash_kernel, block_q=block_q, block_k=block_k,
                          seq_k=lk, sm_scale=sm_scale, causal=causal,
                          precision=precision),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, s: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, s: (b, s, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, s: (b, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, s: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(q, k, v)
