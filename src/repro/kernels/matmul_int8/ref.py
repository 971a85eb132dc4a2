"""Pure-jnp oracle for the INT8 matmul kernel."""

import jax.numpy as jnp


def matmul_int8_ref(x_q, w_q, x_scale, w_scale, out_dtype=jnp.bfloat16):
    acc = jnp.dot(x_q.astype(jnp.int32), w_q.astype(jnp.int32),
                  preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * x_scale.astype(jnp.float32)[:, None] * \
        w_scale.astype(jnp.float32)[None, :]
    return out.astype(out_dtype)


def quantize_rowwise(x, axis=-1):
    """Symmetric per-row INT8 quantization; returns (q, scale).

    The scale is amax times the f32 constant 1/127, the form XLA rewrites
    a division by the constant into when it compiles it, so that an eager
    call and a compiled one give the same bits."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) * jnp.float32(1 / 127)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.squeeze(axis)
