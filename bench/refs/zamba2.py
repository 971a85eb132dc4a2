"""Plain reference of Zamba2 (arXiv:2411.15242; hf:Zyphra/Zamba2-7B-Instruct,
transformers' ``modeling_zamba2.py``), in float32.

Token embedding ``e``; ``x = e``. Then in every layer i:

- on a hybrid layer (``hybrid_layer_ids[j] == i``), shared block ``j %
  num_mem_blocks`` with application j's own adapters computes, with no
  residual inside it: ``h = rms(concat(x, e))``; q, k and v projected
  from h (each plus its adapter ``b(a(h))`` where the attention adapters
  are on); rotary over the whole head (rotate-half, base
  ``rope_theta``); causal softmax attention at ``attn_scale`` (Zamba2:
  ``(head_dim / 2) ** -0.5``); the output projection to ``d_model``;
  ``g = rms(that)``; ``(gate, up) = gate_up(g) + b(a(g))`` (the MLP
  adapter); ``down(act(gate) * up)`` with ``act`` the exact GELU (or
  SiLU); and application j's linear: ``t``. Elsewhere ``t = 0``;
- ``x = x + mamba_i(rms(x + t))``. The Mamba2 mixer: ``in_proj`` into
  z, xBC and dt (in that order); a depthwise causal convolution of width
  ``ssm_conv`` with bias over xBC, then SiLU; xBC into x (heads of
  ``ssm_head_dim``), B and C (``ssm_groups`` groups of ``ssm_state``;
  head h reads group ``h // (heads / groups)``); ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(a_log)``; the recurrence, one position at a
  time, ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t +
  D x_t``; ``y * silu(z)`` normalised over each of the groups of
  ``d_inner / ssm_groups`` channels and scaled; ``out_proj``.

Then a final RMS norm and the LM head, tied to the embedding.

Written from those equations, not from the program: it imports nothing of
it and reads only the weights the benchmark made, by name, one layer at a
time in float32 under ``jax.default_matmul_precision("highest")``.
Attention runs one sequence at a time in blocks of ``QBLOCK`` queries.
The SSM runs the sequential recurrence above, where the program runs the
chunked (state-space dual) algorithm.

Departures from ``modeling_zamba2.py``: its pure-PyTorch path also clamps
dt below at ``time_step_min``; its CUDA path, with ``time_step_limit``
null, does not, and neither does this reference. Its RMS norms multiply
the weight after casting back to the input dtype; here everything stays
float32.

``prec="fp8"`` is the control: every product with a weight takes its
operands through float8 e4m3, with a scale per weight column and per
activation row, as a float8 deployment would.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
#: Queries per attention block.
QBLOCK = 512


def _q8(a, axis):
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(a, w, prec):
    """a (..., K) @ w (K, N) in float32, or through float8 for the
    control."""
    a = a.astype(F32)
    w = w.astype(F32)
    if prec == "fp8":
        a, w = _q8(a, -1), _q8(w, 0)
    return jnp.einsum("...k,kn->...n", a, w, precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(F32)


def _adapter(p, a, prec):
    return _mm(_mm(a, p["a"]["w"], prec), p["b"]["w"], prec)


def _rotary(t, theta):
    """Rotate-half rotary over the whole head of (B, L, H, hd)."""
    hd = t.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(t.shape[1], dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    t1, t2 = t[..., :hd // 2], t[..., hd // 2:]
    return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], -1)


def _attend(q, k, v, scale):
    """Causal attention of one sequence: q (L, H, hd), k and v (L, H, hd),
    in blocks of ``QBLOCK`` queries."""
    l, h, hd = q.shape
    nb = -(-l // QBLOCK)
    qb = jnp.pad(q, ((0, nb * QBLOCK - l), (0, 0), (0, 0))).reshape(
        nb, QBLOCK, h, hd)
    kpos = jnp.arange(l)

    def block(args):
        i, qi = args
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) * scale
        qpos = i * QBLOCK + jnp.arange(QBLOCK)
        s = jnp.where((kpos[None, :] <= qpos[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                          precision=HI)

    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.reshape(nb * QBLOCK, h, hd)[:l]


def _shared(blk, app, x, e, m, prec):
    """One application of a shared block: its output ``t`` (no residual)."""
    b, l, d = x.shape
    nh, nkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    h = _rms(jnp.concatenate([x, e], -1), blk["ln_in"]["scale"], eps)
    at, ad = blk["attn"], app.get("attn_adapters", {})

    def proj(name, heads):
        y = _mm(h, at["w" + name]["w"], prec)
        if name in ad:
            y = y + _adapter(ad[name], h, prec)
        return y.reshape(b, l, heads, hd)

    q = _rotary(proj("q", nh), m["rope_theta"])
    k = _rotary(proj("k", nkv), m["rope_theta"])
    v = proj("v", nkv)
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    scale = m["attn_scale"] or hd ** -0.5
    o = jax.lax.map(lambda a: _attend(*a, scale), (q, k, v))
    a = _mm(o.reshape(b, l, nh * hd), at["wo"]["w"], prec)
    g = _rms(a, blk["ln_ff"]["scale"], eps)
    mlp = blk["mlp"]
    gate = _mm(g, mlp["gate"]["w"], prec)
    up = _mm(g, mlp["up"]["w"], prec)
    if "mlp_adapter" in app:
        gu = _adapter(app["mlp_adapter"], g, prec)
        gate, up = gate + gu[..., :m["d_ff"]], up + gu[..., m["d_ff"]:]
    act = jax.nn.silu(gate) if m["mlp_act"] == "silu" else \
        jax.nn.gelu(gate, approximate=False)
    return _mm(_mm(act * up, mlp["down"]["w"], prec), app["linear"]["w"],
               prec)


def _ssm(x, dt, a, bm, cm, d_skip):
    """The recurrence over positions. x (B, L, H, P); dt (B, L, H); a, d_skip
    (H,); bm, cm (B, L, H, N) (each head's group). Returns (B, L, H, P)."""
    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = h * jnp.exp(dtt * a)[..., None, None] + \
            (dtt[..., None] * xt)[..., None] * bt[:, :, None, :]
        return h, jnp.einsum("bhpn,bhn->bhp", h, ct, precision=HI)

    bsz, _, nh, p = x.shape
    h0 = jnp.zeros((bsz, nh, p, bm.shape[-1]), F32)
    _, ys = jax.lax.scan(step, h0, tuple(jnp.moveaxis(t, 1, 0)
                                         for t in (x, dt, bm, cm)))
    return jnp.moveaxis(ys, 0, 1) + x * d_skip[:, None]


def _mamba(blk, x, m, prec):
    """``mamba(rms(x))``: the mixer's output for the layer's input x."""
    b, l, d = x.shape
    mb = blk["mamba"]
    d_in = m["ssm_expand"] * d
    p, n, g = m["ssm_head_dim"], m["ssm_state"], m["ssm_groups"]
    nh = d_in // p
    u = _mm(_rms(x, blk["ln"]["scale"], m["norm_eps"]), mb["in_proj"]["w"],
            prec)
    z, xbc, dt = u[..., :d_in], u[..., d_in:2 * d_in + 2 * g * n], \
        u[..., 2 * d_in + 2 * g * n:]
    w = mb["conv_w"].astype(F32)                       # (K, channels)
    kw = w.shape[0]
    xp = jnp.pad(xbc, ((0, 0), (kw - 1, 0), (0, 0)))
    xbc = sum(xp[:, i:i + l] * w[i] for i in range(kw)) + \
        mb["conv_b"].astype(F32)
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :d_in].reshape(b, l, nh, p)
    grp = jnp.arange(nh) // (nh // g)

    def heads(t):
        return t.reshape(b, l, g, n)[:, :, grp]

    bm, cm = heads(xbc[..., d_in:d_in + g * n]), heads(xbc[..., d_in + g * n:])
    dt = jax.nn.softplus(dt + mb["dt_bias"].astype(F32))
    a = -jnp.exp(mb["a_log"].astype(F32))
    y = _ssm(xs, dt, a, bm, cm, mb["d_skip"].astype(F32)).reshape(b, l, d_in)
    y = y * jax.nn.silu(z)
    yg = y.reshape(b, l, g, d_in // g)
    yg = yg / jnp.sqrt(jnp.mean(yg * yg, -1, keepdims=True) + 1e-5)
    y = yg.reshape(b, l, d_in) * mb["norm"]["scale"].astype(F32)
    return _mm(y, mb["out_proj"]["w"], prec)


@functools.partial(jax.jit, static_argnames=("m", "prec"))
def _layer_jit(blk, x, m, prec):
    with jax.default_matmul_precision("highest"):
        return x + _mamba(blk, x, dict(m), prec)


@functools.partial(jax.jit, static_argnames=("m", "prec"))
def _hybrid_jit(blk, app, mix, x, e, m, prec):
    with jax.default_matmul_precision("highest"):
        m = dict(m)
        t = _shared(blk, app, x, e, m, prec)
        return x + _mamba(mix, x + t, m, prec)


@functools.partial(jax.jit, static_argnames=("m", "prec", "start"))
def _head(params, x, m, prec, start):
    m = dict(m)
    with jax.default_matmul_precision("highest"):
        x = _rms(x[:, start:], params["ln_f"]["scale"], m["norm_eps"])
        w = params["embed"]["table"].astype(F32)
        if prec == "fp8":
            x, w = _q8(x, -1), _q8(w, -1)
        return jnp.einsum("bld,vd->blv", x, w, precision=HI)


def logits(params, m: dict, tokens, start: int, prec: str = "f32"):
    """Logits (B, L - start, V) at positions ``start..L-1`` of ``tokens``
    (B, L), layer by layer."""
    m = dict(m, head_dim=m.get("head_dim") or m["d_model"] // m["n_heads"],
             attn_scale=m.get("attn_scale", 0.0),
             mlp_act=m.get("mlp_act", "silu"))
    ids = list(m.pop("hybrid_layer_ids", ()))
    nb = m.get("num_mem_blocks", 1)
    key = tuple(sorted((k, v) for k, v in m.items()
                       if isinstance(v, (int, float, str, bool))))
    e = params["embed"]["table"][jnp.asarray(tokens)].astype(F32)
    x = e
    take = lambda tree, i: jax.tree.map(lambda t: t[i], tree)
    blocks = params["blocks"]
    for i in range(jax.tree.leaves(blocks)[0].shape[0]):
        blk = take(blocks, i)
        if i in ids:
            j = ids.index(i)
            x = _hybrid_jit(take(params["shared_blocks"], j % nb),
                            take(params["app_blocks"], j), blk, x, e, key,
                            prec)
        else:
            x = _layer_jit(blk, x, key, prec)
    return _head(params, x, key, prec, start)
