"""Jittable train / serve step builders for every architecture.

``make_train_step``: cross-entropy LM loss (+ MoE aux), grad, AdamW update,
optional microbatch gradient accumulation (lax.scan) and cross-pod int8
gradient compression with error feedback. ``make_prefill_step`` /
``make_decode_step``: serving counterparts carrying KV caches / SSM states.

All steps are pure functions of (state, batch) so they pjit cleanly.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSpec
from repro.models import transformer
from repro.models.attention import init_kv_cache
from repro.models.ssm import SSMState, init_ssm_state, ssd_dims
from repro.runtime.compression import (compress_grads_with_feedback,
                                       init_residuals)
from repro.train.optimizer import (AdamWState, OptimizerConfig, adamw_update,
                                   init_adamw)


class TrainState(NamedTuple):
    params: Any
    opt: AdamWState
    residuals: Any | None       # error-feedback state (pod-compression)
    rng: jax.Array


@dataclasses.dataclass(frozen=True)
class StepConfig:
    microbatches: int = 1
    remat: bool = True
    #: the Pallas kernels in the step: flash attention for prompts of 512
    #: or more, and ``ssd_scan`` for the chunked SSD's intra-chunk term
    use_flash: bool = False
    compress_pod_grads: bool = False
    compute_dtype: Any = jnp.bfloat16
    aux_loss_weight: float = 0.01


def lm_loss(params, cfg: ModelConfig, tokens, labels, *, shard,
            step_cfg: StepConfig, frontend=None):
    out = transformer.forward(
        params, cfg, tokens, mode="train", shard=shard,
        use_flash=step_cfg.use_flash, remat=step_cfg.remat,
        compute_dtype=step_cfg.compute_dtype, frontend_embeds=frontend)
    logits = out.logits.astype(jnp.float32)        # (B, L, V) vocab-sharded
    # Cross-entropy that keeps the vocab axis sharded: label logit via a
    # one-hot contraction (partitions under TP; take_along_axis would force
    # an all-gather of the full fp32 logits) + stable logsumexp whose
    # max/sum reductions partition into small cross-model collectives.
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    lse = jnp.squeeze(m, -1) + jnp.log(
        jnp.sum(jnp.exp(logits - m), axis=-1))
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    label_logit = jnp.einsum("blv,blv->bl", logits, onehot)
    ll = label_logit - lse
    mask = (labels >= 0).astype(jnp.float32)
    loss = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    total = loss + step_cfg.aux_loss_weight * out.aux_loss
    return total, {"loss": loss, "aux_loss": out.aux_loss}


def make_train_step(cfg: ModelConfig, opt_cfg: OptimizerConfig,
                    step_cfg: StepConfig, shard=None):
    shard = shard or (lambda name, x: x)

    def grads_of(params, tokens, labels, frontend):
        (loss, metrics), grads = jax.value_and_grad(
            lm_loss, has_aux=True)(params, cfg, tokens, labels, shard=shard,
                                   step_cfg=step_cfg, frontend=frontend)
        return grads, loss, metrics

    def train_step(state: TrainState, batch: dict):
        tokens = batch["tokens"]
        labels = batch["labels"]
        frontend = batch.get("frontend")
        mb = step_cfg.microbatches
        if mb > 1:
            def mb_split(x):
                return x.reshape((mb, x.shape[0] // mb) + x.shape[1:])
            mb_tok, mb_lab = mb_split(tokens), mb_split(labels)
            mb_fr = mb_split(frontend) if frontend is not None else None

            def acc_body(carry, xs):
                g_acc, l_acc = carry
                if mb_fr is not None:
                    t, l, fr = xs
                else:
                    (t, l), fr = xs, None
                g, loss, _ = grads_of(state.params, t, l, fr)
                g_acc = jax.tree.map(lambda a, b: a + b, g_acc, g)
                return (g_acc, l_acc + loss), None

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 state.params)
            xs = (mb_tok, mb_lab, mb_fr) if mb_fr is not None \
                else (mb_tok, mb_lab)
            (grads, loss_sum), _ = jax.lax.scan(acc_body, (zeros, 0.0), xs)
            grads = jax.tree.map(lambda g: g / mb, grads)
            metrics = {"loss": loss_sum / mb,
                       "aux_loss": jnp.zeros((), jnp.float32)}
        else:
            grads, loss, metrics = grads_of(state.params, tokens, labels,
                                            frontend)
        residuals = state.residuals
        if step_cfg.compress_pod_grads and residuals is not None:
            grads, residuals = compress_grads_with_feedback(grads, residuals)
        new_params, new_opt, opt_metrics = adamw_update(
            opt_cfg, state.params, grads, state.opt)
        metrics = {**metrics, **opt_metrics}
        new_state = TrainState(params=new_params, opt=new_opt,
                               residuals=residuals,
                               rng=jax.random.fold_in(state.rng, 1))
        return new_state, metrics

    return train_step


def init_train_state(key, cfg: ModelConfig, step_cfg: StepConfig,
                     param_dtype=jnp.float32) -> TrainState:
    params = transformer.init_model(key, cfg, param_dtype)
    return TrainState(
        params=params,
        opt=init_adamw(params),
        residuals=init_residuals(params)
        if step_cfg.compress_pod_grads else None,
        rng=key)


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig, step_cfg: StepConfig, shard=None):
    shard = shard or (lambda name, x: x)

    def prefill(params, batch):
        out = transformer.forward(
            params, cfg, batch["tokens"], mode="prefill", shard=shard,
            use_flash=step_cfg.use_flash,
            compute_dtype=step_cfg.compute_dtype,
            frontend_embeds=batch.get("frontend"))
        last = out.logits[:, -1]
        return last, out.caches

    return prefill


def make_decode_step(cfg: ModelConfig, step_cfg: StepConfig, shard=None):
    shard = shard or (lambda name, x: x)

    def decode(params, batch, caches):
        out = transformer.forward(
            params, cfg, batch["tokens"], mode="decode", caches=caches,
            shard=shard, compute_dtype=step_cfg.compute_dtype)
        return out.logits[:, -1], out.caches

    return decode


def init_caches(cfg: ModelConfig, batch: int, max_seq: int,
                compute_dtype=jnp.bfloat16):
    """Decode-mode cache pytree (zeros), family-dependent."""
    fam = cfg.family
    hd = cfg.resolved_head_dim
    if fam in ("dense", "moe", "vlm"):
        return init_kv_cache(cfg.n_layers, batch, max_seq, cfg.n_kv_heads,
                             hd, compute_dtype)
    if fam == "ssm":
        st = init_ssm_state(batch, cfg, cfg.d_model)
        return jax.tree.map(
            lambda t: jnp.zeros((cfg.n_layers,) + t.shape, t.dtype), st)
    if fam == "hybrid":
        st = init_ssm_state(batch, cfg, cfg.d_model, compute_dtype)
        states = jax.tree.map(
            lambda t: jnp.zeros((cfg.n_layers,) + t.shape, t.dtype), st)
        kv = init_kv_cache(len(cfg.hybrid_layer_ids), batch, max_seq,
                           cfg.n_kv_heads, hd, compute_dtype) \
            if cfg.hybrid_layer_ids else None
        return (states, kv)
    if fam == "encdec":
        kv = init_kv_cache(cfg.n_layers, batch, max_seq, cfg.n_kv_heads, hd,
                           compute_dtype)
        mem = cfg.frontend_seq or 1024
        cross = (jnp.zeros((cfg.n_layers, batch, mem, cfg.n_kv_heads, hd),
                           compute_dtype),
                 jnp.zeros((cfg.n_layers, batch, mem, cfg.n_kv_heads, hd),
                           compute_dtype))
        memory = jnp.zeros((batch, mem, cfg.d_model), compute_dtype)
        return (kv, cross, memory)
    raise ValueError(fam)


def decode_caches(cfg: ModelConfig, prefilled, batch: int, max_seq: int,
                  compute_dtype=jnp.bfloat16):
    """``init_caches`` for ``max_seq`` positions holding a prefill's caches.

    The prefill step returns caches as long as its prompt; the decode step
    writes one position per call at each row's length, in place, and
    drops a write past the cache's end without a word. Every prefilled
    leaf is written at the origin of its zero-initialized counterpart (and
    keeps the prefill's dtype, which is what decode returns), so only the
    KV sequence axis grows. Jit it to keep the caches where the prefill
    put them."""
    return jax.tree.map(
        lambda z, p: jax.lax.dynamic_update_slice(
            z.astype(p.dtype), p, (0,) * p.ndim),
        init_caches(cfg, batch, max_seq, compute_dtype), prefilled)
