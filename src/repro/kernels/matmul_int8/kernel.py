"""INT8 quantized matmul Pallas kernel — the CIM MVM primitive, TPU-native.

CIM -> TPU adaptation (DESIGN.md §TPU bridge): the CIM macro holds an INT8 weight
tile and streams bit-serial inputs; on TPU the analogous structure is an
MXU-aligned weight block resident in VMEM while activation blocks stream
HBM->VMEM through Pallas' pipelined (double-buffered) BlockSpecs — the same
capacity/overlap trade-off MIREDO's psi^DM models (double-buffering halves
usable VMEM). Block shapes (bm, bk, bn) are selected by the MIREDO MIP via
core/tpu_bridge.py.

Grid (M/bm, N/bn, K/bk); INT8 x INT8 -> INT32 accumulation in a VMEM
scratch accumulator, dequantized on the final K step with per-channel
weight scales x per-row activation scales.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.chips import VMEM_LIMIT_BYTES


def _matmul_kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_ref, *,
                   n_k_steps: int):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 operands straight into the MXU; Mosaic has no int32 x int32 dot
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k_step == n_k_steps - 1)
    def _finish():
        # same multiply order as ref.py, so the result is bit-identical
        out = acc_ref[...].astype(jnp.float32) * sx_ref[...] * sw_ref[...]
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret",
                                             "out_dtype"))
def matmul_int8(x_q: jax.Array, w_q: jax.Array, x_scale: jax.Array,
                w_scale: jax.Array, *, bm: int = 256, bk: int = 256,
                bn: int = 256, out_dtype=jnp.bfloat16,
                interpret: bool = False) -> jax.Array:
    """x_q: (M, K) int8; w_q: (K, N) int8; x_scale: (M,) f32;
    w_scale: (N,) f32 -> (M, N) out_dtype.

    The scales enter the kernel as (M, 1) columns and (1, N) rows: a rank-1
    (bm,) block is only legal on the chip when bm is a multiple of 128 or
    the whole dim, a (bm, 1) block whenever bm is a multiple of 8."""
    m, k = x_q.shape
    k2, n = w_q.shape
    assert k == k2
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
            pl.BlockSpec((bm, 1), lambda i, j, s: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, s: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x_q, w_q, x_scale.astype(jnp.float32).reshape(m, 1),
      w_scale.astype(jnp.float32).reshape(1, n))
