"""Optimizer schedules, fault-tolerance policies, gradient compression."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime.compression import (compress_grads_with_feedback,
                                       init_residuals)
from repro.runtime.fault_tolerance import (ElasticPlan, HeartbeatMonitor,
                                           RetryPolicy, StragglerPolicy,
                                           plan_elastic_mesh)
from repro.train.optimizer import (AdamWState, OptimizerConfig, adamw_update,
                                   init_adamw, schedule_lr)


def test_wsd_schedule_shape():
    cfg = OptimizerConfig(lr=1.0, warmup_steps=10, total_steps=100,
                          schedule="wsd", wsd_stable_frac=0.8,
                          min_lr_frac=0.1)
    lrs = [float(schedule_lr(cfg, jnp.asarray(s))) for s in range(101)]
    assert lrs[0] == 0.0
    assert abs(lrs[10] - 1.0) < 1e-6          # warmed up
    assert abs(lrs[50] - 1.0) < 1e-6          # stable plateau (WSD)
    assert lrs[99] < 0.3                      # fast decay at the end
    cfg_cos = OptimizerConfig(lr=1.0, warmup_steps=10, total_steps=100,
                              schedule="cosine")
    assert float(schedule_lr(cfg_cos, jnp.asarray(50))) < 0.95  # no plateau


def test_adamw_optimizes_quadratic():
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = init_adamw(params)
    cfg = OptimizerConfig(lr=0.2, warmup_steps=0, total_steps=200,
                          weight_decay=0.0, schedule="constant")
    for _ in range(150):
        grads = {"w": 2 * params["w"]}
        params, state, m = adamw_update(cfg, params, grads, state)
    assert float(jnp.max(jnp.abs(params["w"]))) < 0.1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_adamw_moments_keep_param_dtype(dtype):
    """Moments are zeros of each parameter's shape and dtype, and an update
    keeps the parameters' and the moments' dtypes."""
    params = {"w": jnp.ones((3, 4), dtype), "b": jnp.ones((4,), dtype)}
    state = init_adamw(params)
    for tree in (state.m, state.v):
        for p, t in zip(jax.tree.leaves(params), jax.tree.leaves(tree)):
            assert t.shape == p.shape and t.dtype == dtype
            assert not np.any(np.asarray(t, np.float32))
    cfg = OptimizerConfig(warmup_steps=0, total_steps=10)
    grads = jax.tree.map(jnp.ones_like, params)
    new, state, _ = adamw_update(cfg, params, grads, state)
    for t in jax.tree.leaves((new, state.m, state.v)):
        assert t.dtype == dtype
    assert int(state.step) == 1


def test_grad_clip_reported():
    params = {"w": jnp.ones((4,))}
    state = init_adamw(params)
    cfg = OptimizerConfig(grad_clip=1.0, warmup_steps=0, total_steps=10)
    _, _, metrics = adamw_update(cfg, params, {"w": 100 * jnp.ones((4,))},
                                 state)
    assert float(metrics["grad_norm"]) > 100


def test_compression_error_feedback_converges():
    """With error feedback, the accumulated decompressed signal tracks the
    accumulated true gradient (bias-free compression)."""
    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.standard_normal((64,)) * 0.01)
    grads = {"g": g_true}
    residuals = init_residuals(grads)
    acc_true = np.zeros(64)
    acc_comp = np.zeros(64)
    for _ in range(50):
        comp, residuals = compress_grads_with_feedback(grads, residuals)
        acc_true += np.asarray(g_true)
        acc_comp += np.asarray(comp["g"])
    rel = np.linalg.norm(acc_comp - acc_true) / np.linalg.norm(acc_true)
    assert rel < 0.02, rel


def test_heartbeat():
    hb = HeartbeatMonitor(n_hosts=3, deadline_s=10)
    for h in range(3):
        hb.beat(h, now=100.0)
    assert hb.dead_hosts(now=105.0) == []
    assert hb.dead_hosts(now=111.0) == [0, 1, 2]
    hb.beat(1, now=112.0)
    assert hb.dead_hosts(now=115.0) == [0, 2]


def test_straggler_eviction():
    sp = StragglerPolicy(threshold=1.5, patience=2)
    for step in range(4):
        for h in range(4):
            sp.record(h, 1.0 if h != 3 else 3.0)
    assert sp.evictions() == [3]


def test_elastic_plan():
    p = plan_elastic_mesh(512, model_axis=16)
    assert (p.data, p.model) == (32, 16)
    p = plan_elastic_mesh(240, model_axis=16)     # lost a host
    assert (p.data, p.model) == (15, 16)
    p = plan_elastic_mesh(8, model_axis=16)       # deep degradation
    assert p.model <= 8 and p.n_devices <= 8


def test_retry_backoff():
    delays = list(RetryPolicy(max_retries=4, base_s=1.0, cap_s=5.0).delays())
    assert delays == [1.0, 2.0, 4.0, 5.0]
