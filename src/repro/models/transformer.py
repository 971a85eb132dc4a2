"""Unified model assembly for every assigned architecture family.

One ``init_model`` / ``forward`` pair covers:
  dense / vlm  : pre-norm GQA blocks + (gated) MLP        (scan over layers)
  moe          : GQA blocks + routed experts (+ shared / dense-residual)
  ssm          : Mamba2 (SSD) blocks, attention-free
  hybrid       : Mamba2 layers; each of ``hybrid_layer_ids`` first applies
                 one of ``num_mem_blocks`` parameter-shared transformer
                 blocks, in turn, to concat(hidden, embedding), and adds
                 its output, through a linear of its own, to that layer's
                 Mamba input (Zamba2)
  encdec       : bidirectional encoder + causal decoder w/ cross-attention
                 (Seamless backbone; audio frontend stubbed)

Layers are stacked and driven by ``jax.lax.scan`` (small HLO, fast
compile), but for the hybrid's, which are unrolled (``_hybrid_stack``);
training wraps the block in ``jax.checkpoint``. Modes:
  "train"   tokens -> logits                  (full seq, causal)
  "prefill" tokens -> logits + caches         (the scan's ys)
  "decode"  one token + caches -> logits + caches
Decode of the attention stacks carries the layer-stacked KV cache through
the scan and writes each layer's one new position into it in place; no
layer's cache is sliced out, rebuilt or stacked back. The hybrid updates
its stacked SSM states and its KV cache (one entry per application) in
place the same way.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.attention import attention, init_attention
from repro.models.layers import (Identity, dense, embed, init_dense,
                                 init_embedding, init_lora, init_mlp,
                                 init_rmsnorm, lora, mlp, rms_norm, unembed)
from repro.models.moe import init_moe, moe
from repro.models.ssm import (SSMState, init_mamba2, init_ssm_state,
                              mamba2_block)
from repro.tracing import region


def _stack_init(init_fn, key, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


# ---------------------------------------------------------------------------
# Block initializers
# ---------------------------------------------------------------------------

def _init_attn_block(key, cfg: ModelConfig, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    hd = cfg.resolved_head_dim
    p = {
        "ln1": init_rmsnorm(cfg.d_model),
        "attn": init_attention(k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                               hd, dtype, cfg.qkv_bias),
        "ln2": init_rmsnorm(cfg.d_model),
    }
    if cfg.n_experts and cfg.family == "moe":
        p["moe"] = init_moe(k2, cfg.d_model, cfg.moe_d_ff, cfg.n_experts,
                            cfg.n_shared_experts, cfg.gated_mlp, dtype)
        if cfg.dense_residual:
            p["mlp"] = init_mlp(k3, cfg.d_model, cfg.d_ff, cfg.gated_mlp,
                                dtype)
    else:
        p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.gated_mlp, dtype)
    return p


def _init_mamba_block(key, cfg: ModelConfig, dtype) -> dict:
    return {
        "ln": init_rmsnorm(cfg.d_model),
        "mamba": init_mamba2(key, cfg.d_model, expand=cfg.ssm_expand,
                             head_dim=cfg.ssm_head_dim, groups=cfg.ssm_groups,
                             state=cfg.ssm_state, conv=cfg.ssm_conv,
                             dtype=dtype),
    }


def _init_shared_block(key, cfg: ModelConfig, dtype) -> dict:
    """A hybrid's parameter-shared transformer block: attention from
    concat(hidden, embedding), twice ``d_model`` wide, back to
    ``d_model``, then the (gated) MLP."""
    k1, k2 = jax.random.split(key)
    d = cfg.d_model
    return {
        "ln_in": init_rmsnorm(2 * d),
        "attn": init_attention(k1, 2 * d, cfg.n_heads, cfg.n_kv_heads,
                               cfg.resolved_head_dim, dtype, cfg.qkv_bias,
                               d_out=d),
        "ln_ff": init_rmsnorm(d),
        "mlp": init_mlp(k2, d, cfg.d_ff, cfg.gated_mlp, dtype),
    }


def _init_application(key, cfg: ModelConfig, dtype) -> dict:
    """What one application of a shared block holds alone: the linear
    into its layer's Mamba input, and its adapters."""
    kl, kq, kk, kv, km = jax.random.split(key, 5)
    d, hd, r = cfg.d_model, cfg.resolved_head_dim, cfg.adapter_rank
    p = {"linear": init_dense(kl, d, d, dtype)}
    if cfg.shared_attn_adapter:
        p["attn_adapters"] = {
            n: init_lora(k, 2 * d, heads * hd, r, dtype)
            for n, k, heads in (("q", kq, cfg.n_heads),
                                ("k", kk, cfg.n_kv_heads),
                                ("v", kv, cfg.n_kv_heads))}
    if cfg.shared_mlp_adapter:
        p["mlp_adapter"] = init_lora(km, d, 2 * cfg.d_ff, r, dtype)
    return p


def _init_cross_block(key, cfg: ModelConfig, dtype) -> dict:
    """Decoder block with cross-attention (encdec family)."""
    p = _init_attn_block(key, cfg, dtype)
    k = jax.random.fold_in(key, 7)
    hd = cfg.resolved_head_dim
    p["ln_x"] = init_rmsnorm(cfg.d_model)
    p["xattn"] = init_attention(k, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                hd, dtype, cfg.qkv_bias)
    return p


def init_model(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    ke, kb, ks, kf = jax.random.split(key, 4)
    params: dict[str, Any] = {
        "embed": init_embedding(ke, cfg.padded_vocab(), cfg.d_model, dtype),
        "ln_f": init_rmsnorm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = init_embedding(
            jax.random.fold_in(ke, 1), cfg.padded_vocab(), cfg.d_model, dtype)
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        params["blocks"] = _stack_init(
            lambda k: _init_attn_block(k, cfg, dtype), kb, cfg.n_layers)
    elif fam == "ssm":
        params["blocks"] = _stack_init(
            lambda k: _init_mamba_block(k, cfg, dtype), kb, cfg.n_layers)
    elif fam == "hybrid":
        params["blocks"] = _stack_init(
            lambda k: _init_mamba_block(k, cfg, dtype), kb, cfg.n_layers)
        if cfg.hybrid_layer_ids:
            params["shared_blocks"] = _stack_init(
                lambda k: _init_shared_block(k, cfg, dtype), ks,
                cfg.num_mem_blocks)
            params["app_blocks"] = _stack_init(
                lambda k: _init_application(k, cfg, dtype),
                jax.random.fold_in(ks, 1), len(cfg.hybrid_layer_ids))
    elif fam == "encdec":
        params["enc_blocks"] = _stack_init(
            lambda k: _init_attn_block(k, cfg, dtype), kb, cfg.encoder_layers)
        params["blocks"] = _stack_init(
            lambda k: _init_cross_block(k, cfg, dtype),
            jax.random.fold_in(kb, 5), cfg.n_layers)
        params["ln_enc"] = init_rmsnorm(cfg.d_model)
    else:
        raise ValueError(fam)
    return params


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ForwardOut:
    logits: jax.Array
    caches: Any = None
    aux_loss: jax.Array | None = None


def _attn_block_apply(blk, x, cfg: ModelConfig, cache, *, causal, shard,
                      use_flash, memory=None, mem_cross_kv=None, layer=None):
    hd = cfg.resolved_head_dim
    h = rms_norm(blk["ln1"], x, cfg.norm_eps)
    attn_out, new_cache = attention(
        blk["attn"], h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=hd, rope_theta=cfg.rope_theta, causal=causal, cache=cache,
        layer=layer, shard=shard, use_flash=use_flash, rope_dim=cfg.rope_dim,
        rope_interleaved=cfg.rope_interleaved)
    x = x + attn_out
    aux = jnp.zeros((), jnp.float32)
    cross_kv = None
    if memory is not None or mem_cross_kv is not None:
        # cross-attention (encdec decoder)
        hx = rms_norm(blk["ln_x"], x, cfg.norm_eps)
        from repro.models.attention import dot_attention
        from repro.models.layers import dense
        b, l, _ = hx.shape
        q = dense(blk["xattn"]["wq"], hx).reshape(b, l, cfg.n_heads, hd)
        if mem_cross_kv is None:
            m = memory
            k = dense(blk["xattn"]["wk"], m).reshape(
                b, m.shape[1], cfg.n_kv_heads, hd)
            v = dense(blk["xattn"]["wv"], m).reshape(
                b, m.shape[1], cfg.n_kv_heads, hd)
            cross_kv = (k, v)
        else:
            k, v = mem_cross_kv
            cross_kv = mem_cross_kv
        rep = cfg.n_heads // cfg.n_kv_heads
        from repro.models.attention import _repeat_kv
        o = dot_attention(q, _repeat_kv(k, rep), _repeat_kv(v, rep),
                          causal=False)
        x = x + dense(blk["xattn"]["wo"], o.reshape(b, l, -1))
    h = rms_norm(blk["ln2"], x, cfg.norm_eps)
    with region("mlp"):
        if "moe" in blk:
            mo, aux = moe(blk["moe"], h, n_experts=cfg.n_experts,
                          top_k=cfg.top_k, gated=cfg.gated_mlp, shard=shard)
            if "mlp" in blk:            # arctic dense residual
                mo = mo + mlp(blk["mlp"], h, cfg.gated_mlp, shard)
            x = x + mo
        else:
            x = x + mlp(blk["mlp"], h, cfg.gated_mlp, shard)
    return x, new_cache, aux, cross_kv


def _scan_attn_layers(params_stack, x, cfg, caches, *, causal, shard,
                      use_flash, remat):
    """caches: the stacked per-layer KVCache for decode, or None (train /
    prefill / encode: prefill collects fresh caches from the scan's ys).
    Decode carries the stack through the scan: each layer writes its new
    position into it in place (``attention``'s ``layer``), so the scan's
    xs are the layer parameters and their index, and its ys are empty."""
    def body(carry, layer_in):
        x, aux, stack = carry
        blk, layer = layer_in
        x, cache, aux_l, _ = _attn_block_apply(
            blk, x, cfg, stack, layer=layer, causal=causal, shard=shard,
            use_flash=use_flash)
        if stack is None:
            return (x, aux + aux_l, None), cache
        return (x, aux + aux_l, cache), None

    fn = jax.checkpoint(body) if remat else body
    n = jax.tree.leaves(params_stack)[0].shape[0]
    (x, aux, stack), ys = jax.lax.scan(
        fn, (x, jnp.zeros((), jnp.float32), caches),
        (params_stack, jnp.arange(n)))
    return x, aux, ys if caches is None else stack


def forward(params: dict, cfg: ModelConfig, tokens: jax.Array, *,
            mode: str = "train", caches: Any = None,
            frontend_embeds: jax.Array | None = None,
            shard=Identity, use_flash: bool = False,
            remat: bool = False, compute_dtype=jnp.bfloat16) -> ForwardOut:
    """tokens: (B, L) int32. frontend_embeds: (B, S_front, D) for
    audio/vision modalities (precomputed stub embeddings). Named scopes
    (``repro.tracing``): ``embed``, ``lm_head`` (final norm and unembed),
    and per block ``attn`` and ``mlp``."""
    fam = cfg.family
    b, l = tokens.shape
    with region("embed"):
        x = embed(params["embed"], tokens, compute_dtype)
    if frontend_embeds is not None and fam in ("vlm",) and mode != "decode":
        x = jnp.concatenate([frontend_embeds.astype(compute_dtype), x],
                            axis=1)
    x = shard("hidden", x)
    causal = mode != "encode"
    is_decode = mode == "decode"
    aux = jnp.zeros((), jnp.float32)

    if fam in ("dense", "moe", "vlm"):
        x, aux, new_caches = _scan_attn_layers(
            params["blocks"], x, cfg, caches if is_decode else None,
            causal=True, shard=shard, use_flash=use_flash,
            remat=remat and mode == "train")
    elif fam == "ssm":
        x, new_caches, aux = _ssm_stack(params["blocks"], x, cfg, caches,
                                        shard, remat and mode == "train",
                                        is_decode)
    elif fam == "hybrid":
        x, new_caches = _hybrid_stack(params, x, cfg, caches, mode=mode,
                                      shard=shard, use_flash=use_flash,
                                      remat=remat and mode == "train",
                                      compute_dtype=compute_dtype)
    elif fam == "encdec":
        x, new_caches, aux = _encdec_stack(params, x, cfg, caches,
                                           frontend_embeds, shard,
                                           remat and mode == "train",
                                           is_decode, compute_dtype,
                                           use_flash)
    else:
        raise ValueError(fam)

    with region("lm_head"):
        x = rms_norm(params["ln_f"], x, cfg.norm_eps)
        table = params["embed"] if cfg.tie_embeddings else params["unembed"]
        if frontend_embeds is not None and fam == "vlm" and mode != "decode":
            x = x[:, frontend_embeds.shape[1]:]
        logits = unembed(table, x)
        logits = shard("logits", logits)
    return ForwardOut(logits=logits, caches=new_caches, aux_loss=aux)


# ---------------------------------------------------------------------------
# family-specific stacks
# ---------------------------------------------------------------------------

def _ssm_stack(stack, x, cfg, states, shard, remat, is_decode):
    b = x.shape[0]
    if states is None:
        proto = init_ssm_state(b, cfg, cfg.d_model)
        states = jax.tree.map(
            lambda t: jnp.zeros((cfg.n_layers,) + t.shape, t.dtype), proto)

    def body(carry, layer_in):
        x = carry
        blk, st = layer_in
        h = rms_norm(blk["ln"], x, cfg.norm_eps)
        out, new_st = mamba2_block(blk["mamba"], h, cfg, state=st if
                                   is_decode else None, shard=shard)
        if not is_decode:
            new_st = SSMState(h=new_st.h, conv=new_st.conv)
        return x + out, new_st

    fn = jax.checkpoint(body) if remat else body
    x, new_states = jax.lax.scan(fn, x, (stack, states))
    return x, new_states, jnp.zeros((), jnp.float32)


def _shared_block(params, app, x, emb, cfg, kv, *, shard, use_flash):
    """Application ``app`` of shared block ``app % num_mem_blocks`` to
    (x, emb): ``linear(down(act(g) * u))`` with ``(g, u) = gate_up(h) +
    adapter(h)``, ``h = rms_norm(o_proj(attn(rms_norm(concat(x,
    emb)))))``, no residual inside. ``kv``, in decode, is the KV cache
    stacked over applications, in which the attention writes its new
    position at entry ``app`` in place. Returns (out, the cache ``kv``
    with that write, or this application's fresh one without ``kv``)."""
    blk = jax.tree.map(lambda t: t[app % cfg.num_mem_blocks],
                       params["shared_blocks"])
    mine = jax.tree.map(lambda t: t[app], params["app_blocks"])
    h = rms_norm(blk["ln_in"], jnp.concatenate([x, emb], axis=-1),
                 cfg.norm_eps)
    a, cache = attention(
        blk["attn"], h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
        cache=kv, layer=app, shard=shard, use_flash=use_flash,
        rope_dim=cfg.rope_dim, rope_interleaved=cfg.rope_interleaved,
        scale=cfg.attn_scale or None, adapters=mine.get("attn_adapters"))
    h = rms_norm(blk["ln_ff"], a, cfg.norm_eps)
    with region("mlp"):
        delta = None
        if "mlp_adapter" in mine:
            with region("adapter"):
                gu = lora(mine["mlp_adapter"], h)
            delta = (gu[..., :cfg.d_ff], gu[..., cfg.d_ff:])
        out = mlp(blk["mlp"], h, cfg.gated_mlp, shard, act=cfg.mlp_act,
                  gate_up_delta=delta)
    return dense(mine["linear"], out), cache


def _hybrid_stack(params, x, cfg, caches, *, mode, shard, use_flash, remat,
                  compute_dtype):
    """Zamba2's layers: for each layer i, ``x += mamba_i(rms_norm_i(x +
    t_i))``, where ``t_i`` is ``_shared_block``'s output on a hybrid
    layer and 0 elsewhere. ``caches`` (decode): (SSMState stacked over
    layers, KVCache stacked over applications); each layer updates its
    own entry of the states, and each application its own entry of the
    cache, in place. Prefill returns them fresh; training returns None.

    The layers are unrolled, not scanned: a scan cannot place a shared
    block between two of its steps without a ``cond``, and across a
    ``cond`` or from one scan to the next XLA changes the layout of the
    carried cache or states and copies the whole of them. Named scopes
    (``repro.tracing``): ``shared_block`` around each application, with
    ``attn``, ``rope``, ``kv_update``, ``mlp`` and ``adapter`` inside;
    ``mamba``, ``conv`` and ``ssd`` in each layer."""
    b = x.shape[0]
    ids = cfg.hybrid_layer_ids
    is_decode = mode == "decode"
    emb = x
    if is_decode:
        states, kv = caches
    elif mode == "prefill":
        proto = init_ssm_state(b, cfg, cfg.d_model, compute_dtype)
        states = jax.tree.map(
            lambda t: jnp.zeros((cfg.n_layers,) + t.shape, t.dtype), proto)
        kv = None
    else:                               # training keeps no state
        states = kv = None
    fresh = []                          # prefill: each application's cache

    def layer(blk, x, st):
        h = rms_norm(blk["ln"], x, cfg.norm_eps)
        return mamba2_block(blk["mamba"], h, cfg, state=st, shard=shard,
                            use_kernel=use_flash)

    fn = jax.checkpoint(layer) if remat else layer
    for i in range(cfg.n_layers):
        x_in = x
        if i in ids:
            with region("shared_block"):
                t, cache = _shared_block(params, ids.index(i), x, emb, cfg,
                                         kv, shard=shard,
                                         use_flash=use_flash)
            kv = cache if is_decode else None
            fresh.append(cache)
            x_in = x + t
        blk = jax.tree.map(lambda w: w[i], params["blocks"])
        st = jax.tree.map(lambda s: s[i], states) if is_decode else None
        out, new_st = fn(blk, x_in, st)
        if states is not None:
            states = jax.tree.map(
                lambda s, n: s.at[i].set(n.astype(s.dtype)), states, new_st)
        x = x + out
    if mode == "train":
        return x, None
    if not is_decode and fresh:
        kv = jax.tree.map(lambda *t: jnp.stack(t), *fresh)
    return x, (states, kv)


def _encdec_stack(params, x, cfg, caches, frontend_embeds, shard, remat,
                  is_decode, compute_dtype, use_flash):
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    if is_decode:
        kv, cross_kvs, memory = caches
        enc_out = None
    else:
        # encode the (stubbed) frontend embeddings bidirectionally
        assert frontend_embeds is not None, "encdec needs frontend embeds"
        m = frontend_embeds.astype(compute_dtype)
        m, _, _ = _scan_attn_layers(
            params["enc_blocks"], m, cfg, None, causal=False, shard=shard,
            use_flash=False, remat=remat)
        memory = rms_norm(params["ln_enc"], m, cfg.norm_eps)
        kv, cross_kvs = None, None

    # decoder with cross-attention — layer loop unrolled via python for
    # cross-KV handling (cross K/V shapes differ from self K/V); n_layers is
    # modest for the encdec arch (24) and the blocks still share code.
    n = cfg.n_layers
    blocks = params["blocks"]
    new_kv_list, new_ckv_list = [], []
    aux = jnp.zeros((), jnp.float32)
    for i in range(n):
        blk = jax.tree.map(lambda t: t[i], blocks)
        cache_i = jax.tree.map(lambda t: t[i], kv) if kv is not None else None
        ckv_i = jax.tree.map(lambda t: t[i], cross_kvs) \
            if cross_kvs is not None else None
        x, new_cache, aux_l, new_ckv = _attn_block_apply(
            blk, x, cfg, cache_i, causal=True, shard=shard,
            use_flash=use_flash,
            memory=memory if ckv_i is None else None,
            mem_cross_kv=ckv_i)
        aux = aux + aux_l
        new_kv_list.append(new_cache)
        new_ckv_list.append(new_ckv if new_ckv is not None else ckv_i)
    new_kv = jax.tree.map(lambda *ts: jnp.stack(ts), *new_kv_list)
    new_ckvs = jax.tree.map(lambda *ts: jnp.stack(ts), *new_ckv_list)
    return x, (new_kv, new_ckvs, memory), aux
