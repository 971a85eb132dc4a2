"""The program's regions, counters and compile counter (``repro.tracing``).

Regions must reach both places a trace is read from: the host spans of a
``jax.profiler`` trace, for eager calls, and the ``op_name`` of the ops a
jitted step compiles to. The flash prefill step and the quantized
matmul's program are lowered for a TPU (Mosaic lowering needs no chip);
their op names are the lowered module's locations, which become the
compiled ops' ``op_name``.
"""

import dataclasses
import functools
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro import tracing
from repro.configs.registry import get_config
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.matmul_int8.ops import DISPATCH_SPAN, quantized_matmul
from repro.models.transformer import init_model
from repro.train.steps import (StepConfig, init_caches, make_decode_step,
                               make_prefill_step)


def _host_spans(trace_dir) -> list[tuple[int, int, str]]:
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(files[0])
    return sorted((e.start_ns, e.end_ns, e.name)
                  for p in pd.planes if p.name.startswith("/host:")
                  for line in p.lines for e in line.events
                  if e.name == "outer" or e.name.startswith("matmul_int8."))


def test_regions_nest_as_host_spans(tmp_path):
    """An eager call is one dispatch of a compiled program: one host span,
    inside the caller's; the program's ops carry the three regions as
    scopes. 40 x 200 @ 200 x 160 at blocks of (32, 128, 128) pads M, K and
    N, so all three regions are in the program."""
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 200))
    w = jax.random.normal(jax.random.PRNGKey(1), (200, 160))
    call = functools.partial(quantized_matmul, block_shapes=(32, 128, 128))
    call(x, w, interpret=True)
    with jax.profiler.trace(str(tmp_path)):
        with tracing.region("outer"):
            jax.block_until_ready(call(x, w, interpret=True))
    spans = _host_spans(tmp_path)
    assert [n for _, _, n in spans] == ["outer", DISPATCH_SPAN]
    (o0, o1, _), (s, e, _) = spans
    assert o0 <= s < e <= o1
    lowered = jax.jit(call).trace(x, w).lower(lowering_platforms=("tpu",))
    text = lowered.as_text(debug_info=True)
    assert "tpu_custom_call" in text
    names = set(re.findall(r'loc\("([^"]*)"', text))
    for scope in ("matmul_int8.quantize", "matmul_int8.pad",
                  "matmul_int8.kernel"):
        assert _under(names, scope), scope


def test_counters_count_eager_calls_only():
    x = jnp.ones((16, 32))
    w = jnp.ones((32, 16))
    q = jnp.ones((1, 16, 2, 8))
    before = tracing.counters()
    quantized_matmul(x, w, block_shapes=(16, 32, 16), interpret=True)
    flash_attention(q, q, q, block_q=8, block_k=8, interpret=True)
    jax.jit(lambda a, b: quantized_matmul(
        a, b, block_shapes=(16, 32, 16), interpret=True))(x, w)
    jax.jit(lambda a: flash_attention(a, a, a, block_q=8, block_k=8,
                                      interpret=True))(q)
    after = tracing.counters()
    for name in ("matmul_int8.calls", "flash_attention.calls"):
        assert after[name] - before.get(name, 0) == 1, name


def test_compile_counter_rises_on_a_new_shape_only():
    f = jax.jit(lambda a: a * 3 + 1)
    a, b = jnp.ones(7), jnp.ones(9)
    c0 = tracing.counters().get("jax.compiles", 0)
    f(a).block_until_ready()
    c1 = tracing.counters()["jax.compiles"]
    f(a).block_until_ready()
    c2 = tracing.counters()["jax.compiles"]
    f(b).block_until_ready()
    c3 = tracing.counters()["jax.compiles"]
    assert (c1 - c0, c2 - c1, c3 - c2) == (1, 0, 1)


@pytest.fixture(scope="module")
def tiny_dense():
    cfg = dataclasses.replace(get_config("minicpm-2b"), n_layers=2,
                              d_model=64, n_heads=2, n_kv_heads=2,
                              head_dim=32, d_ff=128, vocab_size=256)
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    return cfg, params


def _op_names(hlo_text: str) -> set[str]:
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


def _under(names, scope: str) -> bool:
    return any(scope in n.split("/") for n in names)


def test_decode_step_ops_carry_the_scopes(tiny_dense):
    cfg, params = tiny_dense
    caches = jax.eval_shape(lambda: init_caches(cfg, batch=2, max_seq=16))
    tok = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    step = make_decode_step(cfg, StepConfig(remat=False))
    text = jax.jit(step).lower(params, {"tokens": tok}, caches) \
        .compile().as_text()
    names = _op_names(text)
    for scope in ("embed", "attn", "kv_update", "mlp", "lm_head"):
        assert _under(names, scope), scope
    # the cache update is inside attention
    assert any("/attn/kv_update/" in n for n in names)


def test_flash_prefill_step_ops_carry_the_scopes(tiny_dense):
    cfg, params = tiny_dense
    step = make_prefill_step(cfg, StepConfig(remat=False, use_flash=True))
    tok = jax.ShapeDtypeStruct((1, 512), jnp.int32)
    lowered = jax.jit(step).trace(params, {"tokens": tok}).lower(
        lowering_platforms=("tpu",))
    text = lowered.as_text(debug_info=True)
    assert "tpu_custom_call" in text
    names = set(re.findall(r'loc\("([^"]*)"', text))
    for scope in ("attn", "flash_attention.layout", "flash_attention.kernel",
                  "mlp", "lm_head"):
        assert _under(names, scope), scope
    assert "attn/flash_attention.layout/transpose" in names
