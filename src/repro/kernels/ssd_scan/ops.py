"""Public op: SSD intra-chunk over the (B, NC, Q, H, ...) layout."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.kernel import ssd_intra_chunk_bh


def ssd_intra_chunk(c: jax.Array, b: jax.Array, s: jax.Array,
                    dt: jax.Array, x: jax.Array, *,
                    interpret: bool = False) -> jax.Array:
    """c, b: (B,NC,Q,H,N); s, dt: (B,NC,Q,H); x: (B,NC,Q,H,P)."""
    bsz, nc, q, h, n = c.shape
    p = x.shape[-1]
    f5 = lambda t: t.transpose(0, 1, 3, 2, 4).reshape(bsz * nc * h, q,
                                                      t.shape[-1])
    f4 = lambda t: t.transpose(0, 1, 3, 2).reshape(bsz * nc * h, q)
    y = ssd_intra_chunk_bh(f5(c), f5(b), f4(s), f4(dt), f5(x),
                           interpret=interpret)
    return y.reshape(bsz, nc, h, q, p).transpose(0, 1, 3, 2, 4)


def ssd_intra_chunk_and_ref(c: jax.Array, b: jax.Array, s: jax.Array,
                            dt: jax.Array, x: jax.Array, *,
                            interpret: bool = False
                            ) -> tuple[jax.Array, jax.Array]:
    """Kernel and pure-jnp oracle on identical inputs — the executor's
    per-invocation numerics check (`core/executor.py`). Returns
    ``(kernel, ref)``."""
    from repro.kernels.ssd_scan.ref import ssd_intra_chunk_ref
    return (ssd_intra_chunk(c, b, s, dt, x, interpret=interpret),
            ssd_intra_chunk_ref(c, b, s, dt, x))
