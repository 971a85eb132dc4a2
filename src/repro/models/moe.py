"""Mixture-of-Experts layer: top-k routing with fixed expert capacity,
plus optional shared experts (Qwen-MoE) and a dense residual branch
(Arctic). Tokens reach their experts' buffers by an indexed scatter and
come back by a gather, in O(T·K + E·C·D) memory; a one-hot dispatch's
(T, E, C) tensors would be ruinous at arctic's scale."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import Identity, init_dense, init_mlp, mlp


def init_moe(key, d_model: int, d_ff: int, n_experts: int,
             n_shared: int, gated: bool, dtype=jnp.float32) -> dict:
    kr, ke, ks = jax.random.split(key, 3)
    def expert_bank(key, n):
        kk = jax.random.split(key, 3)
        mult = 1.0 / jnp.sqrt(d_model)
        p = {
            "up": {"w": mult * jax.random.normal(
                kk[0], (n, d_model, d_ff), jnp.float32).astype(dtype)},
            "down": {"w": (1.0 / jnp.sqrt(d_ff)) * jax.random.normal(
                kk[1], (n, d_ff, d_model), jnp.float32).astype(dtype)},
        }
        if gated:
            p["gate"] = {"w": mult * jax.random.normal(
                kk[2], (n, d_model, d_ff), jnp.float32).astype(dtype)}
        return p
    p = {"router": init_dense(kr, d_model, n_experts, dtype),
         "experts": expert_bank(ke, n_experts)}
    if n_shared:
        p["shared"] = expert_bank(ks, n_shared)
    return p


def _expert_ffn(bank: dict, x: jax.Array, gated: bool) -> jax.Array:
    """x: (E, C, D) -> (E, C, D) with per-expert weights (E, D, F)."""
    up = jnp.einsum("ecd,edf->ecf", x, bank["up"]["w"].astype(x.dtype))
    if gated:
        g = jnp.einsum("ecd,edf->ecf", x, bank["gate"]["w"].astype(x.dtype))
        h = jax.nn.silu(g) * up
    else:
        h = jax.nn.gelu(up)
    return jnp.einsum("ecf,efd->ecd", h, bank["down"]["w"].astype(x.dtype))


def moe(params: dict, x: jax.Array, *, n_experts: int, top_k: int,
        gated: bool, capacity_factor: float = 1.25,
        shard=Identity) -> tuple[jax.Array, jax.Array]:
    """x: (B, L, D). Returns (out, aux_loss)."""
    b, l, d = x.shape
    tokens = x.reshape(b * l, d)
    n_tok = b * l
    logits = tokens @ params["router"]["w"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)        # (T, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    capacity = max(1, int(capacity_factor * n_tok * top_k / n_experts))
    # position of each token within its expert's buffer, per routing slot
    onehot = jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.int32)  # T,K,E
    flat = onehot.reshape(n_tok * top_k, n_experts)
    pos_in_expert = (jnp.cumsum(flat, axis=0) - flat)
    pos_in_expert = jnp.sum(
        pos_in_expert.reshape(n_tok, top_k, n_experts) * onehot, axis=-1)
    keep = pos_in_expert < capacity                             # (T, K)
    gate_vals = gate_vals * keep

    # indexed scatter/gather: no (T, E, C) tensors
    dest = expert_idx * capacity + jnp.minimum(pos_in_expert,
                                               capacity - 1)    # (T,K)
    dest = jnp.where(keep, dest, n_experts * capacity)          # dropped
    flat_dest = dest.reshape(-1)                                # (T*K,)
    src = jnp.repeat(jnp.arange(n_tok), top_k)
    buf = jnp.zeros((n_experts * capacity + 1, d), x.dtype)
    expert_in = buf.at[flat_dest].add(tokens[src])[:-1]
    expert_in = expert_in.reshape(n_experts, capacity, d)
    expert_in = shard("moe_expert_in", expert_in)
    expert_out = _expert_ffn(params["experts"], expert_in, gated)
    expert_out = shard("moe_expert_out", expert_out)
    flat_out = expert_out.reshape(n_experts * capacity, d)
    flat_out = jnp.concatenate(
        [flat_out, jnp.zeros((1, d), x.dtype)], axis=0)
    picked = flat_out[flat_dest].reshape(n_tok, top_k, d)
    out = jnp.sum(picked * gate_vals[..., None].astype(x.dtype), axis=1)

    if "shared" in params:
        n_sh = params["shared"]["up"]["w"].shape[0]
        sh_in = jnp.broadcast_to(tokens[None], (n_sh, n_tok, d))
        out = out + jnp.sum(_expert_ffn(params["shared"], sh_in, gated),
                            axis=0)

    # load-balancing aux loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jax.nn.one_hot(expert_idx[:, 0], n_experts, dtype=jnp.float32),
        axis=0)
    aux = n_experts * jnp.sum(me * ce)
    return out.reshape(b, l, d), aux
