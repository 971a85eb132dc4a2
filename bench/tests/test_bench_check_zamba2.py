"""The Zamba2 cells' comparisons, cut to a size a CPU test can run (a sound
run is correct; the float8 control and the faults the decode cell can
have are not), the hybrid decode's restore, and the work counts and
kernel families the cells' readers use.

The cut keeps what Zamba2 adds (two shared blocks over concat(hidden,
embedding), the MLP adapter, two SSM groups, a hybrid layer before the
last) at widths of 64. The CPU cannot run a Pallas kernel inside a step,
so the cut runs the step's jnp paths (the kernels' own tests interpret
them). Limits lie between readings at this size over three seeds (CPU):
decode gap 0 to 0.060 sound, 0.63 to 1.02 in float8; prefill logits_rel
0.018 to 0.039 sound, 0.40 to 0.47 in float8.
"""

from __future__ import annotations

import copy
import json
import os
import types

import jax
import numpy as np
import pytest

from bench import run
from bench.paths import serve_decode, serve_decode_hybrid, \
    serve_prefill_hybrid
from bench.tests.tiny import CPU, ROOT, SEED
from bench.work import flash_attention, ssd_scan, zamba2

DECODE = "zamba2-7b.decode-b16-2k"
PREFILL = "zamba2-7b.prefill-1x4k"
MODEL = {"n_layers": 5, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
         "head_dim": 32, "d_ff": 128, "vocab_size": 256, "ssm_state": 16,
         "ssm_head_dim": 16, "hybrid_layer_ids": [1, 3], "adapter_rank": 8}
CUTS = {
    DECODE: ({"batch": 4, "prompt": 64, "turn": 8, "prefill_batch": 2,
              "prefill_flash": False, "check_sequences": 4}, {"gap": 0.3}),
    PREFILL: ({"prompt": 300, "prompts": 4, "check_prompts": 4,
               "use_flash": False}, {"logits_rel": 0.15}),
}


def _cell(name):
    traffic, limits = CUTS[name]
    ctx = run.load_cell(ROOT, name)
    ctx.config["model"].update(MODEL)
    ctx.traffic.update(traffic)
    ctx.limits = dict(limits)
    return ctx


def _run(name, control=False):
    return run.run_cell(copy.deepcopy(_cell(name)), SEED, 0.05, False,
                        control=control, interpret=True, device=CPU)


@pytest.mark.parametrize("name", [DECODE, PREFILL])
def test_sound_run_is_correct(name):
    out = _run(name)
    assert out["correct"], out["check"]


@pytest.mark.parametrize("name", [DECODE, PREFILL])
def test_control_is_not_correct(name):
    out = _run(name, control=True)
    assert not out["correct"], out["check"]


def _states_kept(caches, saved, prompt):
    """A restore that zeroes the KV positions but keeps the SSM states the
    last turn left."""
    return caches[0], serve_decode._restore(caches[1], prompt)


REAL = serve_decode_hybrid.make_decode_step


def _state_unchanged(cfg, step_cfg, shard=None):
    real = REAL(cfg, step_cfg, shard)
    return lambda params, batch, caches: (real(params, batch, caches)[0],
                                          caches)


def test_restore_without_the_states_is_not_correct(monkeypatch):
    monkeypatch.setattr(serve_decode_hybrid, "_restore", _states_kept)
    out = _run(DECODE)
    assert not out["correct"], out["check"]


def test_decode_that_keeps_its_states_is_not_correct(monkeypatch):
    monkeypatch.setattr(serve_decode_hybrid, "make_decode_step",
                        _state_unchanged)
    out = _run(DECODE)
    assert not out["correct"], out["check"]


def test_restore_gives_back_the_prefilled_states_and_caches():
    from bench.model import batch_concat
    from repro.train.steps import (StepConfig, decode_caches,
                                   make_prefill_step)
    ctx = _cell(DECODE)
    ctx.seed, ctx.interpret = SEED, True
    path = serve_decode_hybrid.setup(ctx)
    pb = ctx.traffic["prefill_batch"]
    prefill = jax.jit(make_prefill_step(path.cfg, StepConfig(remat=False)))
    want = decode_caches(path.cfg, batch_concat(
        [prefill(path.params, {"tokens": path.prompts[i:i + pb]})[1]
         for i in range(0, path.batch, pb)]),
        batch=path.batch, max_seq=path.prompt + path.turn)
    for _ in range(path.turn):              # a whole turn from the restore
        path.issue()
    got = path.restore(path.caches)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _config():
    with open(os.path.join(ROOT, "bench", "configs",
                           "zamba2-7b.json")) as f:
        return json.load(f)


def test_weight_bytes_equal_the_models():
    import jax.numpy as jnp

    from bench.model import model_config
    from repro.models.transformer import init_model
    cfg = _config()
    shapes = jax.eval_shape(
        lambda k: init_model(k, model_config(cfg), jnp.bfloat16),
        jax.random.PRNGKey(0))
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert zamba2.weight_bytes(cfg["model"]) == held
    assert held == 5_472_187_904


def test_decode_and_prefill_by_hand():
    m = _config()["model"]
    d, d_in, ff, v = 3584, 7168, 14336, 32000
    mamba = d * (2 * d_in + 2 * 2 * 64 + 112) + d_in * d
    shared = 2 * d * 224 * 96 + 32 * 224 * d + 3 * d * ff
    app = d * d + 128 * (d + 2 * ff)
    per_tok = 2 * (24 * (mamba + 4 * (d_in + 256)) + 4 * (shared + app))
    w = zamba2.decode(m, 16, 2049)
    state = 112 * 64 * 64
    assert w["ops"] == 16 * per_tok + 24 * 16 * 4 * state + \
        4 * 16 * 32 * 4 * 224 * 2049 + 2 * 16 * d * v
    layer = mamba * 2 + 5 * (d_in + 256) * 2 + 3 * 112 * 4 + d_in * 4 + d * 4
    weights = 24 * layer + 4 * ((shared + app) * 2 + 3 * d * 4) + d * 4 + \
        v * d * 2
    kv = 4 * 16 * 2049 * 32 * 224 * 2 * 2
    states = 24 * 16 * 2 * (state * 4 + 3 * (d_in + 256) * 2)
    assert w["bytes"] == weights + 16 * d * 2 + kv + states + 16 * v * 2
    p = zamba2.prefill(m, 1, 4096)
    ssd = 112 * (2 * 128 * 16 * 256 * 257 // 2 + 4 * 4096 * 64 * 64)
    assert p["ops"] == 4096 * per_tok + 24 * ssd + \
        4 * 32 * 4 * 224 * 4096 * 4097 // 2 + 2 * d * v


def test_ssd_scan_hand_count():
    w = ssd_scan.work(1, 4096, 112, 2, 64, 64, 256)
    assert w["ops"] == 2 * 128 * 16 * (256 * 257 // 2) * 112
    assert w["bytes"] == 4096 * (2 * 2 * 64 * 2 + 2 * 112 * 4
                                 + 2 * 112 * 64 * 2)
    # a partial last chunk counts only its own pairs
    assert ssd_scan.chunk_pairs(300, 256) == 256 * 257 // 2 + 44 * 45 // 2


def test_prefill_work_names_both_kernels():
    ctx = types.SimpleNamespace(config=_config())
    from bench.model import model_config
    path = serve_prefill_hybrid.Path.__new__(serve_prefill_hybrid.Path)
    path.ctx, path.cfg = ctx, model_config(ctx.config)
    path.batch, path.prompt, path.use_flash = 1, 4096, True
    work = path.work()
    flash = work["kernels"]["flash_attention"]
    assert flash == [flash_attention.work(1, 4096, 4096, 32, 224, True,
                                          32)] * 4
    ssd = work["kernels"][serve_prefill_hybrid.SSD_FAMILY]
    assert ssd == [ssd_scan.work(1, 4096, 112, 2, 64, 64, 256)] * 24
    assert work["step"] == zamba2.prefill(ctx.config["model"], 1, 4096)


def test_ssd_scan_roofline_reads_the_kernels_events():
    from bench import peaks, trace
    read = run.reader(ROOT, "ssd_scan_roofline")
    one = ssd_scan.work(1, 4096, 112, 2, 64, 64, 256)
    fam = serve_prefill_hybrid.SSD_FAMILY
    rec = {"work": {"kernels": {fam: [one] * 24}}, "steps": 2,
           "peaks": peaks.peaks("TPU v5 lite"),
           "families": {fam: {"events": 48, "seconds": 0.1}}}
    least = one["bytes"] / 819e9 * 24 * 2
    assert read(rec) == pytest.approx(100 * least / 0.1)
    assert read(dict(rec, work={"kernels": {}})) is None
    with pytest.raises(RuntimeError):
        read(dict(rec, families={}))
    # the Pallas call's events are named for its jitted wrapper
    ev = types.SimpleNamespace(name="%ssd_intra_chunk_bh.7 = bf16[448,256,64]"
                                    "{2,1,0} custom-call(...)")
    assert trace._family(ev, [fam, "flash_attention"]) == fam
