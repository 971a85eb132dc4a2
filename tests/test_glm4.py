"""GLM-4's block at a small size on the CPU: QKV bias, rotary on half of
each head in adjacent pairs, and 2 KV heads shared by 8 query heads.

The program's prefill, and its prefill followed by decode through the KV
cache, are compared with a plain NumPy float64 forward pass of GLM-4's
equations (arXiv:2406.12793; ``modeling_chatglm.py``) on seeded random
weights, biases included. The rotary is compared with its formula on a
known vector.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.layers import apply_rope
from repro.models.transformer import init_model
from repro.train.steps import (StepConfig, decode_caches, make_decode_step,
                               make_prefill_step)

CFG = dataclasses.replace(get_config("glm4-9b").reduced(), n_heads=8,
                          n_kv_heads=2, rope_theta=100.0)
#: The program runs in float32 against a float64 reference; the two sum
#: in different orders, which leaves about 1e-6 of the logits' scale
#: after two layers. A wrong rotary, mask or bias moves them by O(1).
TOL = 1e-4


def _params(seed: int = 0) -> dict:
    shapes = jax.eval_shape(lambda k: init_model(k, CFG, jnp.float32),
                            jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = []
    for key, (path, sd) in zip(keys, leaves):
        name = getattr(path[-1], "key", "")
        z = jax.random.normal(key, sd.shape, jnp.float32)
        if name == "scale":
            z = 1.0 + 0.1 * z
        elif name == "w":
            z = z / np.sqrt(sd.shape[-2])
        elif name == "b":
            z = 0.5 * z                     # large enough to matter
        out.append(z)
    return jax.tree_util.tree_unflatten(treedef, out)


def _rotary(x, rope_dim, theta):
    """GLM's rotary over (L, H, hd) in float64: pairs (2i, 2i+1) of the
    first ``rope_dim`` dims at angle t * theta ** (-2i / rope_dim)."""
    out = x.copy()
    for t in range(x.shape[0]):
        for i in range(rope_dim // 2):
            a = t * theta ** (-2.0 * i / rope_dim)
            c, s = np.cos(a), np.sin(a)
            e, o = x[t, :, 2 * i], x[t, :, 2 * i + 1]
            out[t, :, 2 * i], out[t, :, 2 * i + 1] = e * c - o * s, \
                o * c + e * s
    return out


def _reference(params, tokens) -> np.ndarray:
    """Logits (B, L, V) of GLM-4's forward pass, in float64."""
    p = jax.tree.map(lambda t: np.asarray(t, np.float64), params)
    cfg = CFG
    hd, nh, nkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads

    def rms(x, scale):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) +
                           cfg.norm_eps) * scale

    def lin(q, x):
        return x @ q["w"] + q.get("b", 0.0)

    out = []
    for seq in np.asarray(tokens):
        x = p["embed"]["table"][seq]
        length = len(seq)
        mask = np.tril(np.ones((length, length), bool))
        for i in range(cfg.n_layers):
            blk = jax.tree.map(lambda t: t[i], p["blocks"])
            at = blk["attn"]
            h = rms(x, blk["ln1"]["scale"])
            q = _rotary(lin(at["wq"], h).reshape(length, nh, hd),
                        cfg.rope_dim, cfg.rope_theta)
            k = _rotary(lin(at["wk"], h).reshape(length, nkv, hd),
                        cfg.rope_dim, cfg.rope_theta)
            v = lin(at["wv"], h).reshape(length, nkv, hd)
            o = np.zeros((length, nh, hd))
            for head in range(nh):
                g = head // (nh // nkv)           # the KV head it shares
                s = q[:, head] @ k[:, g].T / np.sqrt(hd)
                s = np.where(mask, s, -np.inf)
                w = np.exp(s - s.max(-1, keepdims=True))
                o[:, head] = (w / w.sum(-1, keepdims=True)) @ v[:, g]
            x = x + lin(at["wo"], o.reshape(length, nh * hd))
            h = rms(x, blk["ln2"]["scale"])
            gate = lin(blk["mlp"]["gate"], h)
            x = x + lin(blk["mlp"]["down"], gate / (1 + np.exp(-gate)) *
                        lin(blk["mlp"]["up"], h))
        out.append(rms(x, p["ln_f"]["scale"]) @ p["unembed"]["table"].T)
    return np.stack(out)


def _rel(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float64) - want)) /
                 np.max(np.abs(want)))


def test_prefill_logits_match_reference():
    params = _params()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                CFG.vocab_size)
    prefill = jax.jit(make_prefill_step(
        CFG, StepConfig(remat=False, compute_dtype=jnp.float32)))
    last, caches = prefill(params, {"tokens": tokens})
    want = _reference(params, tokens)
    assert _rel(last, want[:, -1]) < TOL
    assert caches.k.shape[-2] == CFG.n_kv_heads       # no repeated cache


def test_decode_through_cache_matches_reference():
    params = _params(2)
    prompt, gen = 9, 5
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, prompt + gen), 0,
                                CFG.vocab_size)
    step_cfg = StepConfig(remat=False, compute_dtype=jnp.float32)
    last, caches = jax.jit(make_prefill_step(CFG, step_cfg))(
        params, {"tokens": tokens[:, :prompt]})
    caches = decode_caches(CFG, caches, batch=2, max_seq=prompt + gen,
                           compute_dtype=jnp.float32)
    decode = jax.jit(make_decode_step(CFG, step_cfg))
    got = [last]
    for t in range(prompt, prompt + gen - 1):
        logits, caches = decode(params, {"tokens": tokens[:, t:t + 1]},
                                caches)
        got.append(logits)
    want = _reference(params, tokens[:, :prompt + gen - 1])
    assert _rel(np.stack(got, 1), want[:, prompt - 1:]) < TOL


def _half_formula(x, t, theta):
    """Rotate-half over the whole head: pair (i, i + hd/2) at angle
    t * theta ** (-2i / hd)."""
    hd = x.size
    out = x.copy()
    for i in range(hd // 2):
        a = t * theta ** (-2.0 * i / hd)
        c, s = np.cos(a), np.sin(a)
        out[i] = x[i] * c - x[i + hd // 2] * s
        out[i + hd // 2] = x[i] * s + x[i + hd // 2] * c
    return out


@pytest.mark.parametrize("rope_dim,interleaved", [(8, True), (0, False)])
def test_rotary_matches_its_formula(rope_dim, interleaved):
    """A known vector at position 3, head size 16: GLM's rotary (8 dims,
    adjacent pairs) and the default (whole head, rotate-half) each match
    their own formula and not the other's. Float32 sines and products
    are good to about 1e-6 of the vector's size (16)."""
    x = np.arange(1.0, 17.0)
    t, theta = 3, 10.0
    half = _half_formula(x, t, theta)
    glm = _rotary(np.tile(x, (t + 1, 1))[:, None], 8, theta)[t, 0]
    x32 = jnp.asarray(x, jnp.float32)[None, None, None]
    got = np.asarray(apply_rope(x32, jnp.array([[t]]), theta, rope_dim,
                                interleaved))[0, 0, 0]
    want, other = (glm, half) if interleaved else (half, glm)
    np.testing.assert_allclose(got, want, atol=1e-4)
    assert np.max(np.abs(got - other)) > 1.0
    if interleaved:
        np.testing.assert_array_equal(got[8:], x[8:])   # passed through
