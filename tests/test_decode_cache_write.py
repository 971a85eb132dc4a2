"""The decode step's KV-cache write, against the one-hot update it replaced.

The decode writes each row's new K and V (and, with an int8 cache, their
scales) at that row's ``length`` only, into the layer stack the layer scan
carries. The reference below is the update the program used before: a
one-hot(length) multiply-add over every position of the layer's cache,
which drops a write past the cache's end. Swapped in for the program's
write, it gives the reference decode; both run from the same caches, with
rows at different lengths and one row already full, and must give the
same logits and the same caches bit for bit.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.attention as attn_mod
from repro.configs import get_config
from repro.train.steps import (StepConfig, decode_caches, init_caches,
                               init_train_state, make_decode_step,
                               make_prefill_step)

CONFIGS = {
    "minicpm-2b": get_config("minicpm-2b").reduced(),      # MHA
    # grouped heads, QKV bias, rotary on half of each head in pairs
    "glm4-9b": dataclasses.replace(get_config("glm4-9b").reduced(),
                                   n_heads=8, n_kv_heads=2),
}
STEP = StepConfig(remat=False, compute_dtype=jnp.float32)


def one_hot_write(buf, new, length, layer=None, *, window):
    """The replaced update: ``buf + one_hot(length) * new`` over the whole
    of the layer's cache, in the cache's dtype."""
    del window
    cache = buf if layer is None else buf[layer]
    oh = jax.nn.one_hot(length, cache.shape[1], dtype=jnp.float32)
    add = oh[:, :, None, None] * new[:, None].astype(jnp.float32)
    cache = cache + add.astype(cache.dtype)
    return cache if layer is None else buf.at[layer].set(cache)


def _caches(cfg, params, prompt, lengths, max_seq):
    """Prefilled caches of ``max_seq`` positions whose row b holds
    ``lengths[b]`` positions of the prompt and zeros after them."""
    _, caches = jax.jit(make_prefill_step(cfg, STEP))(
        params, {"tokens": prompt})
    caches = decode_caches(cfg, caches, prompt.shape[0], max_seq,
                           STEP.compute_dtype)
    lengths = jnp.asarray(lengths, jnp.int32)
    keep = jnp.arange(max_seq)[None, :] < lengths[:, None]      # (B, S)
    zero = lambda t: None if t is None else jnp.where(
        keep[None, :, :, None, None], t, jnp.zeros((), t.dtype))
    return caches._replace(
        k=zero(caches.k), v=zero(caches.v),
        k_scale=zero(caches.k_scale), v_scale=zero(caches.v_scale),
        length=jnp.broadcast_to(lengths, caches.length.shape))


def _decode(cfg, params, caches, tokens):
    decode = jax.jit(make_decode_step(cfg, STEP))
    out = []
    for t in range(tokens.shape[1]):
        logits, caches = decode(params, {"tokens": tokens[:, t:t + 1]},
                                caches)
        out.append(np.asarray(logits))
    return np.stack(out, 1), caches


@pytest.fixture(params=[False, True], ids=["f32", "int8"])
def kv_quant(request, monkeypatch):
    monkeypatch.setattr(attn_mod, "KV_QUANT", request.param)
    return request.param


# max_seq 24: 8-position windows; 256: two 128-position windows, with a
# row written across the boundary between them
@pytest.mark.parametrize("max_seq,lengths", [
    (24, [5, 12, 7, 24]),
    (256, [126, 9, 128, 256]),
])
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_decode_write_matches_one_hot(name, max_seq, lengths, kv_quant,
                                      monkeypatch):
    cfg = CONFIGS[name]
    params = init_train_state(jax.random.PRNGKey(0), cfg, STEP).params
    rng = np.random.default_rng(1)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, max(lengths))),
                         jnp.int32)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 3)), jnp.int32)
    start = _caches(cfg, params, prompt, lengths, max_seq)
    assert (start.k.dtype == jnp.int8) == kv_quant

    got, got_caches = _decode(cfg, params, start, tokens)
    monkeypatch.setattr(attn_mod, "write_position", one_hot_write)
    want, want_caches = _decode(cfg, params, start, tokens)

    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    for a, b in zip(jax.tree.leaves(got_caches), jax.tree.leaves(want_caches)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(got_caches.length),
                                  np.asarray(start.length) + 3)
    # the full row's write is dropped: its cache is as it was
    full = lengths.index(max_seq)
    for a, b in zip(jax.tree.leaves(got_caches), jax.tree.leaves(start)):
        if a.ndim == 5:
            np.testing.assert_array_equal(np.asarray(a)[:, full],
                                          np.asarray(b)[:, full])
    # every other row wrote its three positions
    rows = [b for b in range(4) if b != full]
    k = np.asarray(got_caches.k)
    for b in rows:
        assert np.all(np.any(k[:, b, lengths[b]:lengths[b] + 3] != 0,
                             axis=(-1, -2)))


def _scans(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_layer_scan_carries_the_cache(name):
    """The decode's layer scan takes the stacked cache only in its carry:
    no xs input and no ys output has a cache leaf's shape."""
    cfg = CONFIGS[name]
    params = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), cfg, STEP).params)
    caches = jax.eval_shape(lambda: init_caches(cfg, 2, 32, jnp.float32))
    tok = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    jaxpr = jax.make_jaxpr(make_decode_step(cfg, STEP))(
        params, {"tokens": tok}, caches)
    shape = caches.k.shape
    layer_scans = [e for e in _scans(jaxpr.jaxpr)
                   if e.params["length"] == cfg.n_layers]
    assert len(layer_scans) == 1
    eqn, = layer_scans
    n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
    carry = [v.aval.shape for v in eqn.invars[n_consts:n_consts + n_carry]]
    xs = [v.aval.shape for v in eqn.invars[n_consts + n_carry:]]
    ys = [v.aval.shape for v in eqn.outvars[n_carry:]]
    assert carry.count(shape) == 2                  # K and V
    assert shape not in xs and shape not in ys
    assert ys == []
