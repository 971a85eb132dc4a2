"""The decode cell's comparison, at a size a CPU test can hold: a sound
run is correct; the float8 control and each fault the cell can have are
not."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from bench.paths import serve_decode
from bench.tests.tiny import run_tiny

CELL = "minicpm-2b.decode-b8-1k"
REAL = serve_decode.make_decode_step


def _state_unchanged(cfg, step_cfg, shard=None):
    real = REAL(cfg, step_cfg, shard)
    return lambda params, batch, caches: (real(params, batch, caches)[0],
                                          caches)


def _token_altered(cfg, step_cfg, shard=None):
    real = REAL(cfg, step_cfg, shard)

    def step(params, batch, caches):
        logits, caches = real(params, batch, caches)
        return logits.at[0].set(-logits[0]), caches
    return step


def _half_batch(cfg, step_cfg, shard=None):
    real = REAL(cfg, step_cfg, shard)

    def step(params, batch, caches):
        h = batch["tokens"].shape[0] // 2
        first = jax.tree.map(lambda t: t[:, :h], caches)
        logits, new = real(params, {"tokens": batch["tokens"][:h]}, first)
        both = jax.tree.map(lambda t: jnp.concatenate([t, t], 1), new)
        return jnp.concatenate([logits, logits]), both
    return step


def test_sound_run_is_correct():
    out = run_tiny(CELL)
    assert out["correct"], out["check"]
    assert out["metrics"]["decode_step_ms"]["value"] > 0


def test_control_is_not_correct():
    assert not run_tiny(CELL, control=True)["correct"]


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered,
                                   _half_batch])
def test_fault_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(serve_decode, "make_decode_step", fault)
    out = run_tiny(CELL)
    assert not out["correct"], out["check"]


def test_restore_gives_back_the_prefilled_caches():
    import copy

    import numpy as np

    from bench.model import batch_concat
    from bench.tests.tiny import SEED, cell
    from repro.train.steps import StepConfig, decode_caches
    ctx = copy.deepcopy(cell(CELL))
    ctx.seed, ctx.interpret = SEED, True
    path = serve_decode.setup(ctx)
    t = ctx.traffic
    prefill = jax.jit(serve_decode.make_prefill_step(
        path.cfg, StepConfig(remat=False, use_flash=t["prefill_flash"])))
    pb = t["prefill_batch"]
    want = decode_caches(path.cfg, batch_concat(
        [prefill(path.params, {"tokens": path.prompts[i:i + pb]})[1]
         for i in range(0, path.batch, pb)]),
        batch=path.batch, max_seq=path.prompt + path.turn)
    for _ in range(path.turn):              # a whole turn from the restore
        path.issue()
    assert (jax.tree.leaves(path.caches)[-1] == path.prompt + path.turn).all()
    got = path.restore(path.caches)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
