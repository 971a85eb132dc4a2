"""Optimized formulations must match their plain references numerically."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import dot_attention, dot_attention_chunked
from repro.models.moe import init_moe, moe


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("lq,lk,bk", [(4096, 4096, 1024),
                                      (2048, 2048, 512)])
def test_chunked_attention_matches_naive(causal, lq, lk, bk):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, lq, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, lk, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, lk, 2, 32)), jnp.float32)
    ref = dot_attention(q, k, v, causal=causal)
    out = dot_attention_chunked(q, k, v, causal=causal, block_k=bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def one_hot_moe(params, x, *, n_experts, top_k, capacity_factor):
    """The textbook MoE of gated experts: top-k routing, and each expert's
    buffer of ``capacity`` slots filled in token order, a token routed to
    a full expert dropped. Dispatch and combine are one-hot (T, E, C)
    einsums. Returns (out, aux_loss, slots dropped)."""
    b, l, d = x.shape
    tokens = x.reshape(b * l, d)
    n_tok = b * l
    probs = jax.nn.softmax(tokens @ params["router"]["w"], axis=-1)
    gate, idx = jax.lax.top_k(probs, top_k)                     # (T, K)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    capacity = max(1, int(capacity_factor * n_tok * top_k / n_experts))
    expert_oh = jax.nn.one_hot(idx, n_experts)                  # (T, K, E)
    # slots taken in each expert before this (token, slot), in token order
    before = jnp.cumsum(expert_oh.reshape(-1, n_experts), axis=0) \
        .reshape(n_tok, top_k, n_experts) - expert_oh
    pos = jnp.sum(before * expert_oh, axis=-1).astype(jnp.int32)
    keep = pos < capacity
    slot_oh = jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity)
    disp = jnp.einsum("tke,tkc->tec", expert_oh, slot_oh)       # (T, E, C)
    combine = jnp.einsum("tke,tkc,tk->tec", expert_oh, slot_oh,
                         gate * keep)
    w = params["experts"]
    xe = jnp.einsum("td,tec->ecd", tokens, disp)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w["gate"]["w"])) \
        * jnp.einsum("ecd,edf->ecf", xe, w["up"]["w"])
    ye = jnp.einsum("ecf,efd->ecd", h, w["down"]["w"])
    out = jnp.einsum("ecd,tec->td", ye, combine)
    load = jnp.mean(jax.nn.one_hot(idx[:, 0], n_experts), axis=0)
    aux = n_experts * jnp.sum(jnp.mean(probs, axis=0) * load)
    return out.reshape(b, l, d), aux, int(jnp.sum(~keep))


@pytest.mark.parametrize("capacity_factor", [8.0, 1.0, 0.5])
def test_scatter_moe_matches_one_hot(capacity_factor):
    """The scatter/gather dispatch gives the one-hot dispatch's output,
    with room for every token (8.0) and with tokens dropped at full
    experts (1.0, 0.5)."""
    d, ff, e, k = 32, 64, 8, 2
    params = init_moe(jax.random.PRNGKey(0), d, ff, e, 0, True)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 16, d)),
                    jnp.float32)
    out, aux = moe(params, x, n_experts=e, top_k=k, gated=True,
                   capacity_factor=capacity_factor)
    ref, ref_aux, dropped = one_hot_moe(params, x, n_experts=e, top_k=k,
                                        capacity_factor=capacity_factor)
    assert (dropped > 0) == (capacity_factor < 8.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(aux), float(ref_aux), rtol=1e-5)
