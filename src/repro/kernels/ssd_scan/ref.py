"""Pure-jnp oracle for the SSD intra-chunk kernel (and the sequential
recurrence oracle used to validate the whole chunked algorithm)."""

import jax
import jax.numpy as jnp


def ssd_intra_chunk_ref(c, b, s, dt, x):
    """c,b: (B,NC,Q,H,N); s,dt: (B,NC,Q,H); x: (B,NC,Q,H,P). In f32
    (HIGHEST: the TPU's default precision would pass f32 operands through
    bf16)."""
    hi = jax.lax.Precision.HIGHEST
    seg = s[:, :, :, None, :] - s[:, :, None, :, :]        # (B,NC,Q,Q,H)
    q = s.shape[2]
    tri = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.where(tri[None, None, :, :, None],
                      jnp.exp(jnp.maximum(seg, -60.0)), 0.0)
    scores = jnp.einsum("bcqhn,bckhn->bcqkh", c.astype(jnp.float32),
                        b.astype(jnp.float32), precision=hi)
    scores = scores * decay * dt[:, :, None, :, :]
    return jnp.einsum("bcqkh,bckhp->bcqhp", scores, x.astype(jnp.float32),
                      precision=hi).astype(x.dtype)


def ssd_sequential_ref(x, dt, a, b, c, d_skip):
    """Step-by-step recurrence oracle for the full SSD layer.
    x: (B,L,H,P); dt: (B,L,H); a: (H,); b,c: (B,L,G,N)."""
    bsz, l, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    rep = h // g
    bb = jnp.repeat(b, rep, axis=2)
    cc = jnp.repeat(c, rep, axis=2)

    def step(hstate, t):
        xt, dtt, bt, ct = t
        dec = jnp.exp(dtt * a)                             # (B,H)
        upd = jnp.einsum("bh,bhn,bhp->bhpn", dtt, bt, xt)
        hstate = hstate * dec[:, :, None, None] + upd
        y = jnp.einsum("bhpn,bhn->bhp", hstate, ct)
        return hstate, y

    h0 = jnp.zeros((bsz, h, p, n), jnp.float32)
    xs = (jnp.moveaxis(x.astype(jnp.float32), 1, 0),
          jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(bb.astype(jnp.float32), 1, 0),
          jnp.moveaxis(cc.astype(jnp.float32), 1, 0))
    h_final, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1) + x.astype(jnp.float32) * \
        d_skip[None, None, :, None]
    return y.astype(x.dtype), h_final
