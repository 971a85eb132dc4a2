"""AdamW + LR schedules (cosine, and MiniCPM's WSD warmup-stable-decay),
hand-rolled on pytrees (no optax in this environment).

Optimizer state shards exactly like parameters (FSDP): the step functions
pass the params' shardings through to m/v.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jax.Array
    m: dict
    v: dict


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    lr: float = 3e-4
    betas: tuple[float, float] = (0.9, 0.95)
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    schedule: str = "cosine"          # cosine | wsd | constant
    wsd_stable_frac: float = 0.8      # WSD: fraction of steps at peak LR
    min_lr_frac: float = 0.1


def schedule_lr(cfg: OptimizerConfig, step: jax.Array) -> jax.Array:
    s = step.astype(jnp.float32)
    warm = jnp.minimum(s / max(cfg.warmup_steps, 1), 1.0)
    t = jnp.clip((s - cfg.warmup_steps) /
                 max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    if cfg.schedule == "cosine":
        decay = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * \
            (1 + jnp.cos(math.pi * t))
    elif cfg.schedule == "wsd":
        # MiniCPM warmup-stable-decay: hold peak LR, then fast 1-cos decay
        stable_end = cfg.wsd_stable_frac
        d = jnp.clip((t - stable_end) / max(1 - stable_end, 1e-6), 0.0, 1.0)
        decay = jnp.where(t < stable_end, 1.0,
                          cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 *
                          (1 + jnp.cos(math.pi * d)))
    else:
        decay = jnp.ones_like(t)
    return cfg.lr * warm * decay


def init_adamw(params) -> AdamWState:
    """Zero moments in each parameter's shape and dtype, at step 0."""
    zeros = lambda: jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype),
                                 params)
    return AdamWState(step=jnp.zeros((), jnp.int32), m=zeros(), v=zeros())


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def adamw_update(cfg: OptimizerConfig, params, grads,
                 state: AdamWState):
    """Returns (new_params, new_state, metrics)."""
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9)) \
        if cfg.grad_clip else 1.0
    step = state.step + 1
    lr = schedule_lr(cfg, step)
    b1, b2 = cfg.betas
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g
        v32 = b2 * v.astype(jnp.float32) + (1 - b2) * jnp.square(g)
        mhat = m32 / bc1
        vhat = v32 / bc2
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
        if p.ndim >= 2:  # decoupled weight decay on matrices only
            delta = delta + cfg.weight_decay * p.astype(jnp.float32)
        return ((p.astype(jnp.float32) - lr * delta).astype(p.dtype),
                m32.astype(m.dtype), v32.astype(v.dtype))

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.m)
    flat_v = treedef.flatten_up_to(state.v)
    out = [upd(p, g, m, v) for p, g, m, v in
           zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    return new_p, AdamWState(step=step, m=new_m, v=new_v), \
        {"lr": lr, "grad_norm": gnorm}
