"""Per-architecture smoke tests: reduced config of the same family, one
forward/train step + one decode step on CPU; asserts shapes + finiteness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import transformer
from repro.train.optimizer import OptimizerConfig
from repro.train.steps import (StepConfig, decode_caches, init_train_state,
                               make_decode_step, make_prefill_step,
                               make_train_step)

BATCH, SEQ = 2, 16


def _batch(cfg, seq=SEQ):
    rng = np.random.default_rng(0)
    b = {
        "tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (BATCH, seq)), jnp.int32),
        "labels": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (BATCH, seq)), jnp.int32),
    }
    if cfg.modality in ("audio", "vision"):
        b["frontend"] = jnp.asarray(
            rng.standard_normal((BATCH, cfg.frontend_seq, cfg.d_model)),
            jnp.float32)
    return b


@pytest.fixture(scope="module", params=ARCH_IDS)
def arch_id(request):
    return request.param


def test_train_step(arch_id):
    cfg = get_config(arch_id).reduced()
    step_cfg = StepConfig(remat=False, compute_dtype=jnp.float32)
    state = init_train_state(jax.random.PRNGKey(0), cfg, step_cfg)
    step = make_train_step(cfg, OptimizerConfig(warmup_steps=2,
                                                total_steps=10), step_cfg)
    batch = _batch(cfg)
    new_state, metrics = jax.jit(step)(state, batch)
    assert np.isfinite(float(metrics["loss"])), metrics
    assert float(metrics["loss"]) > 0
    # params actually changed
    p0 = jax.tree.leaves(state.params)[0]
    p1 = jax.tree.leaves(new_state.params)[0]
    assert not np.allclose(np.asarray(p0), np.asarray(p1))


def test_prefill_then_decode(arch_id):
    cfg = get_config(arch_id).reduced()
    step_cfg = StepConfig(remat=False, compute_dtype=jnp.float32)
    state = init_train_state(jax.random.PRNGKey(1), cfg, step_cfg)
    batch = _batch(cfg)
    prefill = make_prefill_step(cfg, step_cfg)
    logits, caches = jax.jit(prefill)(state.params, batch)
    assert logits.shape == (BATCH, cfg.padded_vocab())
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))

    decode = make_decode_step(cfg, step_cfg)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    logits2, caches2 = jax.jit(decode)(
        state.params, {"tokens": tok}, caches)
    assert logits2.shape == (BATCH, cfg.padded_vocab())
    assert np.all(np.isfinite(np.asarray(logits2, np.float32)))


def test_decode_matches_prefill(arch_id):
    """Teacher-forced decode must reproduce prefill logits step by step —
    the KV-cache / SSM-state path is consistent with the parallel path."""
    cfg = get_config(arch_id).reduced()
    if cfg.family == "vlm":
        pytest.skip("vlm prefix changes token positions; covered above")
    if cfg.n_experts:
        pytest.skip("capacity-based MoE drops tokens differently at "
                    "prefill vs decode batch sizes (known serving "
                    "discrepancy); finiteness covered above")
    step_cfg = StepConfig(remat=False, compute_dtype=jnp.float32)
    state = init_train_state(jax.random.PRNGKey(2), cfg, step_cfg)
    batch = _batch(cfg, seq=8)
    prefill = make_prefill_step(cfg, step_cfg)
    decode = jax.jit(make_decode_step(cfg, step_cfg))

    full_logits, _ = jax.jit(prefill)(
        state.params, batch)                      # logits at last position
    # replay: prefill on the first 4 tokens, then decode tokens 4..7
    import dataclasses
    short = dict(batch)
    short["tokens"] = batch["tokens"][:, :4]
    short.pop("labels", None)
    _, caches = jax.jit(prefill)(state.params, short)
    # grow caches to full seq for decode writes
    caches = decode_caches(cfg, caches, BATCH, 8, step_cfg.compute_dtype)
    logits = None
    for t in range(4, 8):
        tok = batch["tokens"][:, t:t + 1]
        logits, caches = decode(state.params, {"tokens": tok}, caches)
    np.testing.assert_allclose(
        np.asarray(logits, np.float32),
        np.asarray(full_logits, np.float32), rtol=2e-2, atol=2e-2)
