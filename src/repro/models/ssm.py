"""Mamba2 / SSD (state-space duality) blocks [arXiv:2405.21060].

Prefill/training uses the chunked SSD algorithm: quadratic attention-like
intra-chunk term + inter-chunk state recurrence (lax.scan over chunks).
Decode is the O(1) recurrent update. The intra-chunk einsum stack is the
compute hot-spot backed by the ``ssd_scan`` Pallas kernel; this module is
also its jnp reference semantics.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.layers import (Identity, dense, init_dense, init_rmsnorm,
                                 rms_norm, truncated_normal)


class SSMState(NamedTuple):
    h: jax.Array           # (B, H, P, N)
    conv: jax.Array        # (B, K-1, conv_dim)


def ssd_dims(d_model: int, expand: int, head_dim: int, groups: int,
             state: int) -> tuple[int, int, int]:
    d_inner = expand * d_model
    n_heads = d_inner // head_dim
    conv_dim = d_inner + 2 * groups * state
    return d_inner, n_heads, conv_dim


def init_mamba2(key, d_model: int, *, expand: int, head_dim: int,
                groups: int, state: int, conv: int,
                dtype=jnp.float32) -> dict:
    d_inner, n_heads, conv_dim = ssd_dims(d_model, expand, head_dim,
                                          groups, state)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d_proj = 2 * d_inner + 2 * groups * state + n_heads
    return {
        "in_proj": init_dense(k1, d_model, d_proj, dtype),
        "conv_w": truncated_normal(k2, (conv, conv_dim), 0.1, dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, n_heads)).astype(
            jnp.float32),
        "dt_bias": jnp.zeros((n_heads,), jnp.float32),
        "d_skip": jnp.ones((n_heads,), jnp.float32),
        "norm": init_rmsnorm(d_inner),
        "out_proj": init_dense(k4, d_inner, d_model, dtype),
    }


def _split_proj(cfgd: dict, zxbcdt: jax.Array):
    d_inner, gn, h = cfgd["d_inner"], cfgd["gn"], cfgd["n_heads"]
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:d_inner * 2 + 2 * gn]
    dt = zxbcdt[..., -h:]
    return z, xbc, dt


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, d_skip: jax.Array, chunk: int = 256,
                h0: jax.Array | None = None, use_kernel: bool = False,
                interpret: bool = False):
    """Chunked SSD. ``interpret`` runs the ``use_kernel`` path in the
    Pallas interpreter (CPU).

    x: (B, L, H, P); dt: (B, L, H); a: (H,) (negative);
    b, c: (B, L, G, N); d_skip: (H,).
    Returns (y (B,L,H,P), h_final (B,H,P,N)).
    """
    bsz, l, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    assert l % chunk == 0, (l, chunk)
    nc = l // chunk
    rep = h // g

    xc = x.reshape(bsz, nc, chunk, h, p)
    dtc = dt.reshape(bsz, nc, chunk, h)
    bc = jnp.repeat(b.reshape(bsz, nc, chunk, g, n), rep, axis=3)
    cc = jnp.repeat(c.reshape(bsz, nc, chunk, g, n), rep, axis=3)

    da = dtc * a                                    # (B,NC,Q,H), negative
    s = jnp.cumsum(da, axis=2)                      # within-chunk cumsum
    # intra-chunk: scores[t, tau] = (C_t . B_tau) exp(s_t - s_tau) dt_tau
    if use_kernel:
        # Pallas kernel builds the (Q,Q) decay in VMEM from s — no
        # (B,NC,Q,Q,H) HBM tensor.
        from repro.kernels.ssd_scan.ops import ssd_intra_chunk
        y_intra = ssd_intra_chunk(cc, bc, s, dtc, xc,
                                  interpret=interpret).astype(x.dtype)
    else:
        seg = s[:, :, :, None, :] - s[:, :, None, :, :]      # (B,NC,Q,Q,H)
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.where(tri[None, None, :, :, None], jnp.exp(seg), 0.0)
        scores = jnp.einsum("bcqhn,bckhn->bcqkh", cc, bc,
                            preferred_element_type=jnp.float32)
        scores = scores * decay * dtc[:, :, None, :, :]
        y_intra = jnp.einsum("bcqkh,bckhp->bcqhp",
                             scores.astype(x.dtype), xc)

    # chunk summary state: S = sum_tau exp(s_Q - s_tau) dt_tau B_tau x_tau^T
    tail = s[:, :, -1:, :] - s                                  # (B,NC,Q,H)
    w = (jnp.exp(tail) * dtc).astype(x.dtype)
    s_chunk = jnp.einsum("bcqhn,bcqh,bcqhp->bchpn", bc, w, xc)

    # inter-chunk recurrence over chunk index
    chunk_decay = jnp.exp(s[:, :, -1, :])                       # (B,NC,H)
    if h0 is None:
        h0 = jnp.zeros((bsz, h, p, n), jnp.float32)

    def step(hprev, inp):
        dec, s_c = inp                                         # (B,H), (B,H,P,N)
        hnext = hprev * dec[:, :, None, None] + s_c.astype(jnp.float32)
        return hnext, hprev

    dec_t = jnp.moveaxis(chunk_decay, 1, 0)                     # (NC,B,H)
    s_t = jnp.moveaxis(s_chunk, 1, 0)                           # (NC,B,H,P,N)
    h_final, h_prevs = jax.lax.scan(step, h0, (dec_t, s_t))
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)                       # (B,NC,H,P,N)

    # inter-chunk contribution: y_t += (C_t . h_prev) * exp(s_t)
    y_inter = jnp.einsum("bcqhn,bchpn->bcqhp",
                         (cc * jnp.exp(s)[..., None]).astype(x.dtype),
                         h_prevs.astype(x.dtype))
    y = y_intra + y_inter + \
        xc * d_skip[None, None, None, :, None].astype(x.dtype)
    return y.reshape(bsz, l, h, p), h_final


def ssd_recurrent_step(x, dt, a, b, c, d_skip, h):
    """O(1) decode update. x:(B,H,P) dt:(B,H) b,c:(B,G,N) h:(B,H,P,N)."""
    bsz, nh, p = x.shape
    g = b.shape[1]
    rep = nh // g
    bb = jnp.repeat(b, rep, axis=1)                 # (B,H,N)
    cc = jnp.repeat(c, rep, axis=1)
    dec = jnp.exp(dt * a)                           # (B,H)
    upd = jnp.einsum("bh,bhn,bhp->bhpn", dt, bb, x)
    h_new = h * dec[:, :, None, None] + upd.astype(jnp.float32)
    y = jnp.einsum("bhpn,bhn->bhp", h_new.astype(x.dtype), cc)
    return y + x * d_skip[None, :, None].astype(x.dtype), h_new


def _causal_conv(xbc: jax.Array, w: jax.Array, bias: jax.Array,
                 conv_state: jax.Array | None = None):
    """Depthwise causal conv over seq. xbc: (B, L, C); w: (K, C).
    Returns (out, new_conv_state=(B, K-1, C))."""
    k = w.shape[0]
    if conv_state is None:
        pad = jnp.zeros((xbc.shape[0], k - 1, xbc.shape[2]), xbc.dtype)
    else:
        pad = conv_state.astype(xbc.dtype)
    xp = jnp.concatenate([pad, xbc], axis=1)
    out = sum(xp[:, i:i + xbc.shape[1], :] * w[i][None, None, :].astype(
        xbc.dtype) for i in range(k))
    out = out + bias[None, None, :].astype(xbc.dtype)
    new_state = xp[:, -(k - 1):, :] if k > 1 else pad
    return jax.nn.silu(out), new_state


def mamba2_block(params: dict, x: jax.Array, cfg, *,
                 state: SSMState | None = None, chunk: int = 256,
                 shard=Identity, use_kernel: bool = False):
    """x: (B, L, D) (prefill/train) or (B, 1, D) with state (decode).
    Returns (out, new_state)."""
    d_inner, n_heads, conv_dim = ssd_dims(
        x.shape[-1], cfg.ssm_expand, cfg.ssm_head_dim, cfg.ssm_groups,
        cfg.ssm_state)
    gn = cfg.ssm_groups * cfg.ssm_state
    meta = {"d_inner": d_inner, "gn": gn, "n_heads": n_heads}
    bsz, l, _ = x.shape
    zxbcdt = dense(params["in_proj"], x)
    z, xbc, dt = _split_proj(meta, zxbcdt)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["a_log"])                   # (H,) negative

    decode = state is not None and l == 1
    conv_state = state.conv if state is not None else None
    xbc, new_conv = _causal_conv(xbc, params["conv_w"], params["conv_b"],
                                 conv_state)
    xs = xbc[..., :d_inner].reshape(bsz, l, n_heads, cfg.ssm_head_dim)
    bmat = xbc[..., d_inner:d_inner + gn].reshape(
        bsz, l, cfg.ssm_groups, cfg.ssm_state)
    cmat = xbc[..., d_inner + gn:].reshape(
        bsz, l, cfg.ssm_groups, cfg.ssm_state)
    xs = shard("ssm_x", xs)

    if decode:
        y, h_new = ssd_recurrent_step(
            xs[:, 0], dt[:, 0], a, bmat[:, 0], cmat[:, 0],
            params["d_skip"], state.h)
        y = y[:, None]
    else:
        h0 = state.h if state is not None else None
        pad_to = (-l) % chunk
        if pad_to:
            padc = lambda t: jnp.pad(t, [(0, 0), (0, pad_to)] +
                                     [(0, 0)] * (t.ndim - 2))
            xs, dt = padc(xs), padc(dt)
            bmat, cmat = padc(bmat), padc(cmat)
        y, h_new = ssd_chunked(xs, dt, a, bmat, cmat, params["d_skip"],
                               chunk=min(chunk, xs.shape[1]), h0=h0,
                               use_kernel=use_kernel)
        y = y[:, :l]
    y = y.reshape(bsz, l, d_inner)
    y = rms_norm(params["norm"], y * jax.nn.silu(z.astype(y.dtype)))
    out = dense(params["out_proj"], y)
    return out, SSMState(h=h_new, conv=new_conv)


def init_ssm_state(batch: int, cfg, d_model: int,
                   dtype=jnp.float32) -> SSMState:
    d_inner, n_heads, conv_dim = ssd_dims(
        d_model, cfg.ssm_expand, cfg.ssm_head_dim, cfg.ssm_groups,
        cfg.ssm_state)
    return SSMState(
        h=jnp.zeros((batch, n_heads, cfg.ssm_head_dim, cfg.ssm_state),
                    jnp.float32),
        conv=jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), dtype))
