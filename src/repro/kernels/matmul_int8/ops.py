"""Public op: quantize-and-matmul with MIREDO-selected block shapes."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.matmul_int8.kernel import matmul_int8
from repro.kernels.matmul_int8.ref import matmul_int8_ref, quantize_rowwise
from repro.tracing import count, region


def quantized_matmul(x: jax.Array, w: jax.Array, *,
                     block_shapes: tuple[int, int, int] | None = None,
                     use_kernel: bool = True, interpret: bool = False,
                     out_dtype=jnp.bfloat16) -> jax.Array:
    """bf16/f32 (M,K) @ (K,N) via INT8 quantization (CIM-style W8A8).

    ``block_shapes`` come from the MIREDO TPU bridge
    (core/tpu_bridge.py:select_matmul_blocks); defaults are MXU-aligned.
    The kernel compiles for the TPU; ``interpret=True`` runs it in the
    Pallas interpreter instead, which is how it runs on a CPU.

    Regions (``repro.tracing``): ``matmul_int8.quantize``,
    ``matmul_int8.pad`` (only where the blocks do not divide the dims) and
    ``matmul_int8.kernel``; an eager call counts ``matmul_int8.calls``.
    """
    m, k = x.shape
    _, n = w.shape
    if not isinstance(x, jax.core.Tracer):
        count("matmul_int8.calls")
    with region("matmul_int8.quantize"):
        x_q, x_s = quantize_rowwise(x, axis=1)
        w_q, w_s = quantize_rowwise(w, axis=0)
    if not use_kernel:
        return matmul_int8_ref(x_q, w_q, x_s, w_s, out_dtype)
    bm, bk, bn = block_shapes or default_blocks(m, k, n)
    # The bridge may return MXU-aligned blocks that do not divide the dims
    # (dims without an aligned divisor are padded up): zero-pad the
    # quantized operands to block multiples — padded K contributes 0 to the
    # int32 accumulator, padded M/N rows/cols are sliced off the output.
    mp, kp, np_ = (-(-d // b) * b for d, b in ((m, bm), (k, bk), (n, bn)))
    if (mp, kp, np_) != (m, k, n):
        with region("matmul_int8.pad"):
            x_q = jnp.pad(x_q, ((0, mp - m), (0, kp - k)))
            w_q = jnp.pad(w_q, ((0, kp - k), (0, np_ - n)))
            x_s = jnp.pad(x_s, (0, mp - m))
            w_s = jnp.pad(w_s, (0, np_ - n))
    with region("matmul_int8.kernel"):
        out = matmul_int8(x_q, w_q, x_s, w_s, bm=bm, bk=bk, bn=bn,
                          out_dtype=out_dtype, interpret=interpret)
        return out[:m, :n]


def quantized_matmul_and_ref(x: jax.Array, w: jax.Array, *,
                             block_shapes: tuple[int, int, int] | None = None,
                             interpret: bool = False,
                             out_dtype=jnp.float32
                             ) -> tuple[jax.Array, jax.Array]:
    """Kernel and pure-jnp oracle on identical quantized operands.

    The measured-execution backend (`core/executor.py`) checks every kernel
    invocation against its ``ref.py``; both paths quantize the same way and
    apply the scales in the same order, so the two are bit-identical.
    Returns ``(kernel, ref)``."""
    out = quantized_matmul(x, w, block_shapes=block_shapes, use_kernel=True,
                           interpret=interpret, out_dtype=out_dtype)
    ref = quantized_matmul(x, w, use_kernel=False, out_dtype=out_dtype)
    return out, ref


def default_blocks(m: int, k: int, n: int) -> tuple[int, int, int]:
    def pick(d, pref):
        for b in (pref, 512, 256, 128, 64, 32, 16, 8):
            if d % b == 0 and b <= d:
                return b
        return d
    return pick(m, 256), pick(k, 512), pick(n, 256)
