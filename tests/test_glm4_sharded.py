"""GLM-4's block sharded over four devices (a (data=1, model=4) mesh, as
on one 4-chip host), in a child process: the virtual CPU device count
must be set before JAX starts.

With 2 KV heads on a model axis of 4 the KV cache is split on its
sequence axis, and each device attends its quarter of the positions for
every query head. The sharded prefill and decode must give one device's
logits, and the compiled decode step must move no KV cache and no weight
matrix between devices: its only collectives are activation-sized.
"""

from __future__ import annotations

import json
import math
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: Batch, prompt and turn of the cut: 64 positions, 16 a device.
B, PROMPT, GEN = 4, 60, 4
MAX_SEQ = PROMPT + GEN
#: A prompt whose decode writes positions 14, 15 and 16: from the first
#: device's part of the sequence into the second's.
CROSSING = 14
#: The same float32 arithmetic as on one device, with partial sums
#: reduced across devices in another order: about 1e-6 of the logits.
TOL = 1e-5
COLLECTIVE = re.compile(
    r"= (\S+) (all-gather|all-to-all|collective-permute|reduce-scatter)"
    r"(-start)?\(")


def sharded_vs_one_device(prompt: int = PROMPT) -> dict:
    """Prefill ``prompt`` tokens and decode to ``prompt + GEN`` on one
    device and on a (1, 4) mesh, in caches of ``MAX_SEQ`` positions; the
    largest gaps of their logits and their final caches, and the compiled
    sharded decode step's text."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.launch.mesh import make_host_mesh
    from repro.models.transformer import init_model
    from repro.sharding.rules import make_plan
    from repro.train.steps import (StepConfig, decode_caches,
                                   make_decode_step, make_prefill_step)

    cfg = dataclasses.replace(get_config("glm4-9b").reduced(), n_heads=8,
                              n_kv_heads=2)
    mesh = make_host_mesh()
    plan = make_plan(mesh, cfg, ShapeSpec("serve", MAX_SEQ, B, "decode"))
    shard = plan.shard_fn()
    step_cfg = StepConfig(remat=False, compute_dtype=jnp.float32)
    one = init_model(jax.random.PRNGKey(0), cfg, jnp.float32)
    one = jax.tree.map(lambda t: t + 0.1 * jax.random.normal(
        jax.random.PRNGKey(t.size), t.shape), one)     # biases not zero
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, prompt + GEN), 0,
                                cfg.vocab_size)

    def serve(params, shard, place):
        last, caches = jax.jit(make_prefill_step(cfg, step_cfg, shard))(
            params, {"tokens": tokens[:, :prompt]})
        caches = place(decode_caches(cfg, caches, batch=B, max_seq=MAX_SEQ,
                                     compute_dtype=jnp.float32))
        decode = jax.jit(make_decode_step(cfg, step_cfg, shard))
        out = [last]
        for t in range(prompt, prompt + GEN - 1):
            logits, caches = decode(params, {"tokens": tokens[:, t:t + 1]},
                                    caches)
            out.append(logits)
        text = decode.lower(params, {"tokens": tokens[:, :1]},
                            caches).compile().as_text()
        return np.stack([np.asarray(o) for o in out], 1), caches, text

    kv = NamedSharding(mesh, plan.cache_spec("kv"))
    length = NamedSharding(mesh, plan.cache_spec("kv_len"))
    want, want_caches, _ = serve(one, None, lambda c: c)
    got, got_caches, text = serve(
        jax.device_put(one, plan.params_shardings(one)), shard,
        lambda c: jax.device_put(c, c._replace(k=kv, v=kv, length=length)))
    hd = cfg.resolved_head_dim
    cache_gap = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                    for a, b in zip(jax.tree.leaves(got_caches),
                                    jax.tree.leaves(want_caches)))
    return {"gap": float(np.max(np.abs(got - want)) / np.max(np.abs(want))),
            "cache_gap": cache_gap, "kv_splits": shard.kv_splits,
            "length": np.asarray(got_caches.length).tolist(), "hlo": text,
            "cache_shard": B * MAX_SEQ // 4 * cfg.n_kv_heads * hd,
            "least_weight": min(cfg.d_model * cfg.n_kv_heads * hd,
                                cfg.d_model * cfg.d_ff)}


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(REPO, "src"),
                                           os.path.join(REPO, "tests")]))
    code = ("import json; from test_glm4_sharded import "
            "sharded_vs_one_device as f; "
            f"print(json.dumps([f(), f({CROSSING})]))")
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def out(runs):
    return runs[0]


def test_sharded_decode_matches_one_device(out):
    assert out["kv_splits"] == 4
    assert out["gap"] < TOL, out["gap"]


def test_sharded_decode_writes_across_parts(runs):
    """Decode writes that cross from one device's part of the sequence
    into the next land where one device puts them."""
    crossing = runs[1]
    assert crossing["gap"] < TOL, crossing["gap"]
    assert crossing["cache_gap"] < TOL, crossing["cache_gap"]
    assert {n for row in crossing["length"] for n in row} == \
        {CROSSING + GEN - 1}


def _elements(shape: str) -> int:
    dims = re.findall(r"\[([0-9,]*)\]", shape)
    return sum(math.prod(int(n) for n in d.split(",") if n) for d in dims)


def test_decode_moves_no_cache_and_no_weight(out):
    """Every all-gather, all-to-all, collective permute or reduce-scatter
    of the compiled step yields fewer elements than one device's part of
    one layer's K cache or the smallest weight matrix."""
    limit = min(out["cache_shard"], out["least_weight"])
    moved = [(m.group(2), m.group(1))
             for m in COLLECTIVE.finditer(out["hlo"])]
    assert ("all-gather", "f32[4,1,8,16]") in [
        (k, re.sub(r"\{.*", "", s)) for k, s in moved]    # q, gathered
    big = [(k, s) for k, s in moved if _elements(s) >= limit]
    assert not big, big
