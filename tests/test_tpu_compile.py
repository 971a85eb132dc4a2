"""Compile the main path's Pallas kernels for one described TPU v5e chip.

Nothing here needs a chip: the TPU compiler is installed with JAX and
compiles for a topology that is described, not attached. What it refuses
(an illegal block shape, too much scoped VMEM, an unsupported dot) the chip
would refuse too, and interpret mode does not check any of it. Shapes are
zamba2-1.2b's at its published widths, the model whose plan runs all three
kernels (`chip_smoke.py`).

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.tpu_bridge import select_flash_blocks, select_matmul_blocks
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.matmul_int8.ops import quantized_matmul
from repro.kernels.ssd_scan.ops import ssd_intra_chunk


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# (M, K, N, (bm, bk, bn)): zamba2-1.2b's in_proj (2048 x 8384) at prefill
# (2048 tokens) and batch-16 decode, with 128-wide blocks; N = 8384 is not
# a multiple of 128, so the op pads it
MATMUL_CASES = {
    "in_proj_prefill": (2048, 2048, 8384, (128, 128, 128)),
    "in_proj_decode": (16, 2048, 8384, (16, 128, 128)),
    # rank-1 (bm,) scale blocks were illegal here: bm < 128 and bm != M
    "scale_block_bm64": (2048, 2048, 2048, (64, 128, 128)),
    # the executor's whole-K reduction at minicpm-2b's ffn_down
    "whole_k_ffn_down": (2048, 5760, 2304, (512, 5760, 256)),
}


@pytest.mark.parametrize("case", sorted(MATMUL_CASES))
def test_matmul_int8_compiles(one_chip, case):
    m, k, n, blocks = MATMUL_CASES[case]
    fn = functools.partial(quantized_matmul, block_shapes=blocks,
                           out_dtype=jnp.float32)
    _compile(fn, one_chip, ((m, k), jnp.float32), ((k, n), jnp.float32))


def test_matmul_int8_compiles_at_bridge_blocks(one_chip):
    """The largest blocks the bridge MIP picks (zamba2's ffn_up shape) fit
    the scoped VMEM the kernel requests."""
    c = select_matmul_blocks(2048, 2048, 16384)
    assert c.status != "fallback"
    fn = functools.partial(quantized_matmul, block_shapes=(c.bm, c.bk, c.bn),
                           out_dtype=jnp.float32)
    _compile(fn, one_chip, ((2048, 2048), jnp.float32),
             ((2048, 16384), jnp.float32))


@pytest.mark.parametrize("case", ["prefill", "decode"])
def test_flash_attention_compiles(one_chip, case):
    # 32 heads of 64; prefill is the causal 2048 square, decode one query
    # row per sequence against a 512-entry cache (executor.DECODE_KV_CAP)
    b, lq, lk, causal = (1, 2048, 2048, True) if case == "prefill" \
        else (16, 1, 512, False)
    bq, bk = select_flash_blocks(lq, lk, 64)
    fn = functools.partial(flash_attention, causal=causal, block_q=bq,
                           block_k=bk)
    _compile(fn, one_chip, ((b, lq, 32, 64), jnp.float32),
             ((b, lk, 32, 64), jnp.float32), ((b, lk, 32, 64), jnp.float32))


def test_ssd_scan_compiles(one_chip):
    """2048 tokens in chunks of 256 over 64 heads: BCH = 8 * 64 = 512 grid
    steps, chunk q = 256, state n = 64, head dim p = 64."""
    big = (1, 8, 256, 64)
    _compile(ssd_intra_chunk, one_chip, (big + (64,), jnp.float32),
             (big + (64,), jnp.float32), (big, jnp.float32),
             (big, jnp.float32), (big + (64,), jnp.float32))


def test_decode_step_writes_the_cache_in_place(one_chip):
    """minicpm-2b's decode step at its published widths (batch 8, 1152
    positions, the cache donated) needs no temporary of even one layer's
    K cache: the one new position is written into the cache where it
    lies, and the cache keeps the layout the device gave it."""
    from repro.configs import get_config
    from repro.models.transformer import init_model
    from repro.train.steps import StepConfig, init_caches, make_decode_step

    cfg = get_config("minicpm-2b")
    batch, max_seq = 8, 1152
    put = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=one_chip)
    params = jax.tree.map(put, jax.eval_shape(
        lambda k: init_model(k, cfg, jnp.bfloat16), jax.random.PRNGKey(0)))
    caches = jax.tree.map(put, jax.eval_shape(
        lambda: init_caches(cfg, batch, max_seq)))
    tok = put(jax.ShapeDtypeStruct((batch, 1), jnp.int32))
    step = make_decode_step(cfg, StepConfig(remat=False))
    compiled = jax.jit(step, donate_argnums=(2,)).lower(
        params, {"tokens": tok}, caches).compile()
    layer_k = caches.k.size * caches.k.dtype.itemsize // cfg.n_layers
    assert compiled.memory_analysis().temp_size_in_bytes < layer_k
