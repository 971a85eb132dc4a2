"""GQA attention with RoPE, causal masking, KV caching and an optional
flash-attention Pallas kernel path (repro/kernels/flash_attention).

Decode with grouped heads (``n_kv_heads < n_heads``) attends each KV head
once for the query heads that share it, without a repeated cache. Where
the distribution layer splits the cache's sequence axis over devices
(``layers.kv_splits(shard) > 1``), every device attends its own part of
the positions for every query head, and the parts' softmax statistics
are then combined (``grouped_decode_attention``)."""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.layers import (Identity, apply_rope, dense, init_dense,
                                 kv_splits, lora)
from repro.tracing import region


class KVCache(NamedTuple):
    k: jax.Array          # (B, S, KV, hd); (L, B, ...) for a layer stack
    v: jax.Array          # (B, S, KV, hd)
    length: jax.Array     # (B,) int32 — valid prefix length
    # Always None; kept because the decode benchmark's restore names them.
    k_scale: None = None
    v_scale: None = None


def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, dtype=jnp.float32,
                   qkv_bias: bool = False, d_out: int | None = None) -> dict:
    """Projections from ``d_model`` wide inputs; the output projection
    back to ``d_out`` (default ``d_model``)."""
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": init_dense(kq, d_model, n_heads * head_dim, dtype, qkv_bias),
        "wk": init_dense(kk, d_model, n_kv_heads * head_dim, dtype,
                         qkv_bias),
        "wv": init_dense(kv, d_model, n_kv_heads * head_dim, dtype,
                         qkv_bias),
        "wo": init_dense(ko, n_heads * head_dim, d_out or d_model, dtype),
    }


def _repeat_kv(x: jax.Array, rep: int) -> jax.Array:
    if rep == 1:
        return x
    b, s, kv, hd = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :],
                            (b, s, kv, rep, hd)).reshape(b, s, kv * rep, hd)


def _scale(hd: int, scale: float | None) -> float:
    """The softmax scale: ``scale``, or ``1/sqrt(hd)`` where it is None."""
    return 1.0 / math.sqrt(hd) if scale is None else scale


def dot_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool, q_offset=None,
                  kv_length=None, scale: float | None = None) -> jax.Array:
    """q: (B,Lq,H,hd); k,v: (B,Lk,H,hd). Returns (B,Lq,H,hd)."""
    b, lq, h, hd = q.shape
    lk = k.shape[1]
    scale = _scale(hd, scale)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(lk)
    neg = jnp.finfo(jnp.float32).min
    if causal:
        qpos = jnp.arange(lq)
        if q_offset is not None:
            qpos = qpos + q_offset[..., None] if q_offset.ndim else \
                qpos + q_offset
        mask = kpos[None, :] <= qpos[:, None]
        scores = jnp.where(mask[None, None], scores, neg)
    if kv_length is not None:
        valid = kpos[None, :] < kv_length[:, None]
        scores = jnp.where(valid[:, None, None, :], scores, neg)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def dot_attention_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                          causal: bool, block_k: int = 1024,
                          kv_length=None,
                          scale: float | None = None) -> jax.Array:
    """Flash-style online-softmax attention expressed in XLA (scan over KV
    blocks, f32 running statistics, bf16 score/prob tensors): the (Lq, Lk)
    f32 score tensor is never materialized — the HBM-traffic reduction the
    Pallas kernel realizes on TPU.
    Fully-masked causal blocks are skipped via the score mask (XLA DCEs the
    constant branch under unrolled scans)."""
    bsz, lq, h, hd = q.shape
    lk = k.shape[1]
    block_k = min(block_k, lk)
    while lk % block_k:                 # the largest block that divides
        block_k -= 1
    nb = lk // block_k
    scale = _scale(hd, scale)
    kb = k.reshape(bsz, nb, block_k, h, hd)
    vb = v.reshape(bsz, nb, block_k, h, hd)
    qpos = jnp.arange(lq)
    neg = jnp.float32(-1e30)

    def body(carry, inp):
        m, s_sum, acc = carry
        kc, vc, ib = inp
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kc,
                            preferred_element_type=jnp.float32) * scale
        kpos = ib * block_k + jnp.arange(block_k)
        if causal:
            mask = kpos[None, :] <= qpos[:, None]
            scores = jnp.where(mask[None, None], scores, neg)
        if kv_length is not None:
            valid = kpos[None, :] < kv_length[:, None]
            scores = jnp.where(valid[:, None, None, :], scores, neg)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None]).astype(q.dtype)
        s_sum = s_sum * alpha + jnp.sum(p, axis=-1,
                                        dtype=jnp.float32)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p, vc)
        acc = acc * alpha[..., None] + pv.astype(jnp.float32)
        return (m_new, s_sum, acc), None

    m0 = jnp.full((bsz, h, lq), -1e30, jnp.float32)
    s0 = jnp.zeros((bsz, h, lq), jnp.float32)
    a0 = jnp.zeros((bsz, h, lq, hd), jnp.float32)
    ks = jnp.moveaxis(kb, 1, 0)
    vs = jnp.moveaxis(vb, 1, 0)
    (m, s_sum, acc), _ = jax.lax.scan(
        body, (m0, s0, a0), (ks, vs, jnp.arange(nb)))
    out = acc / jnp.maximum(s_sum, 1e-20)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def grouped_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                             kv_length: jax.Array, *, splits: int = 1,
                             shard=Identity,
                             scale: float | None = None) -> jax.Array:
    """One query position against a cache, query heads grouped by the KV
    head they share. q: (B, 1, H, hd); k, v: (B, S, KV, hd); positions at
    and past ``kv_length`` (B,) are masked. Returns (B, 1, H, hd).

    The cache's positions are taken as ``splits`` equal parts (the parts
    the distribution layer keeps on separate devices). Named scopes
    (``repro.tracing``): ``kv_attend`` around the attention over each
    part, giving its running max, sum and weighted values, and, with more
    than one part, ``kv_combine`` around their combination."""
    b, _, h, hd = q.shape
    s_len, g = k.shape[1], k.shape[2]
    r, n = h // g, splits
    if n > 1:
        q = shard("kv_q", q)                     # every head on every part
    qg = q.reshape(b, g, r, hd)
    kb = shard("kv_split", k.reshape(b, n, s_len // n, g, hd))
    vb = shard("kv_split", v.reshape(b, n, s_len // n, g, hd))
    neg = jnp.float32(-1e30)
    with region("kv_attend"):
        scores = jnp.einsum("bgrd,bnsgd->bngrs", qg, kb,
                            preferred_element_type=jnp.float32)
        scores = scores * _scale(hd, scale)
        kpos = jnp.arange(s_len).reshape(n, s_len // n)
        valid = kpos[None] < kv_length[:, None, None]          # (B, n, s)
        scores = jnp.where(valid[:, :, None, None, :], scores, neg)
        m = jnp.max(scores, axis=-1)                           # (B,n,g,r)
        p = jnp.exp(scores - m[..., None])
        s_sum = jnp.sum(p, axis=-1)
        o = jnp.einsum("bngrs,bnsgd->bngrd", p.astype(q.dtype), vb,
                       preferred_element_type=jnp.float32)
        m, s_sum = shard("kv_split", m), shard("kv_split", s_sum)
        o = shard("kv_split", o)
    if n == 1:
        s_sum, o = s_sum[:, 0], o[:, 0]
    else:
        with region("kv_combine"):
            top = jnp.max(m, axis=1, keepdims=True)
            alpha = jnp.exp(m - top)                # 0 for a part all masked
            s_sum = jnp.sum(s_sum * alpha, axis=1)
            o = jnp.sum(o * alpha[..., None], axis=1)
    out = o / s_sum[..., None]
    return out.astype(q.dtype).reshape(b, 1, h, hd)


#: Positions a decode writes as one window: the TPU's lane width. The
#: device keeps a cache with its sequence axis minor (lanes), where a
#: write narrower than a lane tile, or a scatter, makes XLA hold the
#: whole cache in another layout and copy it in and out of the step.
LANES = 128


def write_position(buf: jax.Array, new: jax.Array, length: jax.Array,
                   layer: jax.Array | None = None, *,
                   window: int) -> jax.Array:
    """``buf`` with ``new[b]`` written at position ``length[b]`` of row b.

    buf: (B, S, KV, X), or (L, B, S, KV, X) with ``layer`` the layer to
    write; new: (B, KV, X). Each row reads the ``window`` positions
    (``window`` divides S) that hold its position, selects the new value
    into the one position and writes the window back where it was read,
    so a donated ``buf`` is updated in place. A position outside [0, S)
    matches no position of its window: that write is dropped."""
    lead = () if layer is None else (layer,)
    s_len = buf.shape[-3]
    start = jnp.clip(length // window * window, 0, s_len - window)
    size = (1,) * len(lead) + (1, window) + buf.shape[-2:]
    for row in range(buf.shape[len(lead)]):
        at = lead + (row, start[row], 0, 0)
        old = jax.lax.dynamic_slice(buf, at, size)
        hit = (start[row] + jnp.arange(window) == length[row])[:, None, None]
        val = jnp.where(hit, new[row].astype(buf.dtype), old.reshape(
            old.shape[-3:]))
        buf = jax.lax.dynamic_update_slice(buf, val.reshape(size), at)
    return buf


@region("attn")
def attention(params: dict, x: jax.Array, *, n_heads: int, n_kv_heads: int,
              head_dim: int, rope_theta: float, causal: bool = True,
              positions: jax.Array | None = None,
              cache: KVCache | None = None, layer: jax.Array | None = None,
              shard=Identity, use_flash: bool = False,
              rope_dim: int = 0, rope_interleaved: bool = False,
              scale: float | None = None, adapters: dict | None = None):
    """Returns (out, new_cache). Prefill: cache=None, full seq; new_cache
    holds the sequence's K/V. Decode: x is (B, 1, D) and cache holds past
    K/V, either one layer's (``layer`` None) or the whole layer stack
    (leaves (L, B, ...), ``layer`` the index of this one). The decode
    writes the new K and V at each row's ``length`` (``write_position``;
    on each device's part where the cache's sequence axis is split), in
    place where the caller donated the cache, and advances that layer's
    ``length``; a write past the cache's end is dropped while ``length``
    still rises. It then attends over the positions before
    ``length + 1``, and returns the cache it was given with those writes.
    ``scale`` is the softmax scale (None: ``1/sqrt(head_dim)``).
    ``adapters`` maps any of ``q``, ``k``, ``v`` to a low-rank adapter
    whose output is added to that projection.
    Named scopes (``repro.tracing``): ``attn`` around it all, ``adapter``
    around the adapters, ``rope`` around the rotary, ``kv_update`` around
    the cache write, and ``grouped_decode_attention``'s."""
    b, l, _ = x.shape

    def project(name, heads):
        t = dense(params["w" + name], x)
        if adapters and name in adapters:
            with region("adapter"):
                t = t + lora(adapters[name], x)
        return t.reshape(b, l, heads, head_dim)

    q = project("q", n_heads)
    k = project("k", n_kv_heads)
    v = project("v", n_kv_heads)
    q = shard("attn_q", q)
    rep = n_heads // n_kv_heads

    def rope(t, pos):
        return apply_rope(t, pos, rope_theta, rope_dim, rope_interleaved)

    if cache is None:
        pos = positions if positions is not None else jnp.arange(l)
        if rope_theta:
            with region("rope"):
                q, k = rope(q, pos), rope(k, pos)
        kf, vf = _repeat_kv(k, rep), _repeat_kv(v, rep)
        if use_flash and causal and l >= 512:
            from repro.kernels.flash_attention.ops import flash_attention
            out = flash_attention(q, kf, vf, causal=True, sm_scale=scale)
        elif l >= 2048:
            out = dot_attention_chunked(q, kf, vf, causal=causal,
                                        scale=scale)
        else:
            out = dot_attention(q, kf, vf, causal=causal, scale=scale)
        new_cache = KVCache(k=k, v=v, length=jnp.full((b,), l, jnp.int32))
    else:
        # single-token decode against the cache: write the new position,
        # then attend over the positions before length + 1
        length = cache.length if layer is None else cache.length[layer]
        if rope_theta:
            with region("rope"):
                q, k = rope(q, length[:, None]), rope(k, length[:, None])
        splits = kv_splits(shard)
        put = partial(write_position,
                      window=math.gcd(cache.k.shape[-3] // splits, LANES))
        lead = () if layer is None else (layer,)

        def write(buf, new):
            if splits > 1:            # each device writes its own part
                return shard.kv_local(put, buf, new[:, 0], length, *lead)
            return put(buf, new[:, 0], length, *lead)

        with region("kv_update"):
            new_cache = cache._replace(
                k=write(cache.k, k), v=write(cache.v, v),
                length=cache.length + 1 if layer is None
                else cache.length.at[layer].add(1))

        def this_layer(t):
            return t if layer is None else t[layer]

        kf, vf = this_layer(new_cache.k), this_layer(new_cache.v)
        if rep == 1 and splits == 1:
            out = dot_attention(q, kf, vf, causal=False,
                                kv_length=length + 1, scale=scale)
        else:
            out = grouped_decode_attention(q, kf, vf, length + 1,
                                           splits=splits, shard=shard,
                                           scale=scale)
    out = shard("attn_out", out)
    out = out.reshape(b, l, n_heads * head_dim)
    return dense(params["wo"], out), new_cache


def init_kv_cache(n_layers: int, batch: int, max_seq: int, n_kv_heads: int,
                  head_dim: int, dtype=jnp.bfloat16) -> KVCache:
    """The empty decode cache of ``n_layers`` layers: K and V of shape
    (n_layers, batch, max_seq, n_kv_heads, head_dim) in ``dtype``, and
    lengths (n_layers, batch) of 0."""
    shape = (n_layers, batch, max_seq, n_kv_heads, head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   length=jnp.zeros((n_layers, batch), jnp.int32))
