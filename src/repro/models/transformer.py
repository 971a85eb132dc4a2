"""Unified model assembly for every assigned architecture family.

One ``init_model`` / ``forward`` pair covers:
  dense / vlm  : pre-norm GQA blocks + (gated) MLP        (scan over layers)
  moe          : GQA blocks + routed experts (+ shared / dense-residual)
  ssm          : Mamba2 (SSD) blocks, attention-free
  hybrid       : Mamba2 backbone + parameter-shared attention block every
                 ``attn_every`` layers (Zamba2)
  encdec       : bidirectional encoder + causal decoder w/ cross-attention
                 (Seamless backbone; audio frontend stubbed)

Layers are stacked and driven by ``jax.lax.scan`` (small HLO, fast
compile); training wraps the block in ``jax.checkpoint``. Modes:
  "train"   tokens -> logits                  (full seq, causal)
  "prefill" tokens -> logits + caches         (the scan's ys)
  "decode"  one token + caches -> logits + caches
Decode of the attention stacks carries the layer-stacked KV cache through
the scan and writes each layer's one new position into it in place; no
layer's cache is sliced out, rebuilt or stacked back.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.attention import (attention, init_attention,
                                    init_kv_cache)
from repro.models.layers import (Identity, embed, init_embedding, init_mlp,
                                 init_rmsnorm, mlp, rms_norm, unembed)
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
        n_groups = cfg.n_layers // cfg.attn_every
        grouped = n_groups * cfg.attn_every
        params["blocks"] = _stack_init(
            lambda k: _init_mamba_block(k, cfg, dtype), kb, grouped)
        params["tail"] = _stack_init(
            lambda k: _init_mamba_block(k, cfg, dtype),
            jax.random.fold_in(kb, 3), cfg.n_layers - grouped) \
            if cfg.n_layers - grouped else None
        params["shared_attn"] = _init_attn_block(ks, cfg, dtype)
    elif fam == "encdec":
        params["enc_blocks"] = _stack_init(
            lambda k: _init_attn_block(k, cfg, dtype), kb, cfg.encoder_layers)
        params["blocks"] = _stack_init(
            lambda k: _init_cross_block(k, cfg, dtype),
            jax.random.fold_in(kb, 5), cfg.n_layers)
        params["ln_enc"] = init_rmsnorm(cfg.d_model)
    else:
        raise ValueError(fam)
    if fam in ("hybrid",) and params.get("tail") is None:
        params.pop("tail")
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
        x, new_caches, aux = _hybrid_stack(params, x, cfg, caches, shard,
                                           remat and mode == "train",
                                           is_decode, compute_dtype,
                                           use_flash)
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


def _hybrid_stack(params, x, cfg, caches, shard, remat, is_decode,
                  compute_dtype, use_flash):
    b = x.shape[0]
    n_groups = cfg.n_layers // cfg.attn_every
    grouped = n_groups * cfg.attn_every
    tail_n = cfg.n_layers - grouped
    if caches is None:
        proto = init_ssm_state(b, cfg, cfg.d_model)
        ssm_states = jax.tree.map(
            lambda t: jnp.zeros((cfg.n_layers,) + t.shape, t.dtype), proto)
        kv = None
    else:
        ssm_states, kv = caches
    max_seq = x.shape[1] if kv is None else kv.k.shape[2]
    main_states = jax.tree.map(lambda t: t[:grouped], ssm_states)
    grouped_states = jax.tree.map(
        lambda t: t.reshape((n_groups, cfg.attn_every) + t.shape[1:]),
        main_states)

    def mamba_body(carry, layer_in):
        x = carry
        blk, st = layer_in
        h = rms_norm(blk["ln"], x, cfg.norm_eps)
        out, new_st = mamba2_block(blk["mamba"], h, cfg,
                                   state=st if is_decode else None,
                                   shard=shard)
        return x + out, new_st

    mamba_fn = jax.checkpoint(mamba_body) if remat else mamba_body
    grouped_params = jax.tree.map(
        lambda t: t.reshape((n_groups, cfg.attn_every) + t.shape[1:]),
        params["blocks"])

    def group_body(carry, layer_in):
        x = carry
        blocks_g, states_g, kv_g = layer_in
        x, new_states_g = jax.lax.scan(mamba_fn, x, (blocks_g, states_g))
        # parameter-shared attention block
        x, new_kv, aux, _ = _attn_block_apply(
            params["shared_attn"], x, cfg,
            kv_g if is_decode else None, causal=True, shard=shard,
            use_flash=use_flash)
        return x, (new_states_g, new_kv)

    if kv is None:
        kv_stack = init_kv_cache(n_groups, b, max_seq, cfg.n_kv_heads,
                                 cfg.resolved_head_dim, compute_dtype)
    else:
        kv_stack = kv
    x, (new_grouped_states, new_kv_stack) = jax.lax.scan(
        group_body, x, (grouped_params, grouped_states, kv_stack))
    new_main = jax.tree.map(
        lambda t: t.reshape((grouped,) + t.shape[2:]), new_grouped_states)
    if tail_n:
        tail_states = jax.tree.map(lambda t: t[grouped:], ssm_states)
        x, new_tail = jax.lax.scan(mamba_fn, x,
                                   (params["tail"], tail_states))
        new_states = jax.tree.map(
            lambda a, c: jnp.concatenate([a, c], axis=0), new_main, new_tail)
    else:
        new_states = new_main
    return x, (new_states, new_kv_stack), jnp.zeros((), jnp.float32)


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
