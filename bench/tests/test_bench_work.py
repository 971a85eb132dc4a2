"""Work counts against hand counts, and shares that cannot pass 100%."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from bench import peaks, shares
from bench.work import dense, flash_attention, least_s, matmul_int8

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
V5E = peaks.peaks("TPU v5 lite")


def _model(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def test_matmul_hand_count():
    w = matmul_int8.work(2048, 2304, 5760)
    assert w["ops"] == 2 * 2048 * 2304 * 5760
    assert w["bytes"] == 2048 * 2304 + 2304 * 5760 + 2 * 2048 * 5760
    assert w["precision"] == "int8"


@pytest.mark.parametrize("length", [1, 2, 7, 512, 2048])
def test_causal_pairs_are_a_triangle(length):
    assert flash_attention.causal_pairs(length, length) == \
        length * (length + 1) // 2


def test_flash_hand_count():
    w = flash_attention.work(1, 2048, 2048, 36, 64, True)
    assert w["ops"] == 4 * 64 * 36 * 2048 * 2049 // 2
    assert w["bytes"] == 2 * 64 * 4 * 36 * 2048
    full = flash_attention.work(2, 1, 100, 4, 64, False)
    assert full["ops"] == 4 * 64 * 2 * 4 * 100
    # a decode query sees every cached position: causal or not alike
    assert flash_attention.causal_pairs(1, 100) == 100


@pytest.mark.parametrize("name,fam", [("minicpm-2b", dense)])
def test_weight_bytes_equal_the_models(name, fam):
    import jax
    import jax.numpy as jnp

    from bench.model import model_config
    from repro.models.transformer import init_model
    cfg = _model(name)
    shapes = jax.eval_shape(
        lambda k: init_model(k, model_config(cfg), jnp.bfloat16),
        jax.random.PRNGKey(0))
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert fam.weight_bytes(cfg["model"]) == held


def test_dense_decode_and_prefill_by_hand():
    m = _model("minicpm-2b")["model"]
    d, ff, v = 2304, 5760, 122753
    per_tok = 40 * 2 * (4 * d * d + 3 * d * ff)
    w = dense.decode(m, 8, 1025)
    assert w["ops"] == 8 * per_tok + 40 * 8 * 36 * 4 * 64 * 1025 + \
        2 * 8 * d * v
    p = dense.prefill(m, 1, 2048)
    assert p["ops"] == 2048 * per_tok + 40 * 36 * 4 * 64 * 2048 * 2049 // 2 \
        + 2 * d * v
    # a decode step reads every weight but the embedding rows it gathers
    # (the head's logical rows included) and the KV cache it attends
    kv = 40 * 8 * 1025 * 36 * 64 * 2 * 2
    weights = 40 * ((4 * d * d + 3 * d * ff) * 2 + 2 * d * 4) + d * 4 \
        + v * d * 2
    assert w["bytes"] == weights + 8 * d * 2 + kv + 8 * v * 2


def _rec(step=None, kernels=None, seconds=1.0, steps=1):
    fam = {k: {"events": len(v), "seconds": seconds}
           for k, v in (kernels or {}).items()}
    return {"work": {"step": step, "kernels": kernels or {}},
            "families": fam, "steps": steps, "window_s": seconds,
            "busy_s": seconds, "peaks": V5E}


def test_no_share_passes_100_at_the_least_time():
    rng = np.random.default_rng(0)
    for _ in range(200):
        m, k, n = (int(x) for x in rng.integers(1, 8192, 3))
        b, l, h = (int(x) for x in rng.integers(1, 4096, 3))
        calls = {"matmul_int8": [matmul_int8.work(m, k, n)],
                 "flash_attention": [flash_attention.work(1, l, l, h, 64)]}
        for fam, ws in calls.items():
            t = least_s(ws[0], V5E) * (1 + rng.uniform(0, 3))
            assert shares.kernel_roofline(_rec(kernels={fam: ws},
                                               seconds=t), fam) <= 100.0
        step = dense.decode(_model("minicpm-2b")["model"],
                            int(rng.integers(1, 64)), l)
        t = least_s(step, V5E)
        rec = _rec(step=step, seconds=t)
        assert shares.step_roofline(rec) == pytest.approx(100.0)
        assert shares.step_mfu(rec) <= 100.0
        plan = _rec(kernels=calls, seconds=sum(least_s(w[0], V5E)
                                               for w in calls.values()))
        assert shares.plan_mfu(plan) <= 100.0 + 1e-9


def test_a_kernel_the_step_called_but_the_trace_lacks_fails():
    rec = _rec(kernels={"matmul_int8": [matmul_int8.work(8, 8, 8)]})
    rec["families"] = {}
    with pytest.raises(RuntimeError):
        shares.kernel_roofline(rec, "matmul_int8")
    assert shares.kernel_roofline(rec, "flash_attention") is None


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError):
        peaks.peaks("TPU v9 imaginary")
    assert peaks.rate(peaks.peaks("TPU v5 lite"), "int8") == 393e12
