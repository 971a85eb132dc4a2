"""Per-kernel correctness: shape/dtype sweeps, Pallas interpret=True vs the
pure-jnp ref oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.kernels.flash_attention.kernel import flash_attention_bh
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.matmul_int8.kernel import matmul_int8
from repro.kernels.matmul_int8.ops import quantized_matmul
from repro.kernels.matmul_int8.ref import matmul_int8_ref, quantize_rowwise
from repro.kernels.ssd_scan.ops import ssd_intra_chunk
from repro.kernels.ssd_scan.ref import ssd_intra_chunk_ref


# ---------------------------------------------------------------------------
# matmul_int8
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(32, 64, 32), (64, 128, 64),
                                   (128, 256, 128), (32, 512, 16)])
@pytest.mark.parametrize("bm,bk,bn", [(16, 32, 16), (32, 64, 32)])
def test_matmul_int8_shapes(m, k, n, bm, bk, bn):
    if m % bm or k % bk or n % bn:
        pytest.skip("non-divisible")
    rng = np.random.default_rng(0)
    x_q = rng.integers(-127, 128, (m, k)).astype(np.int8)
    w_q = rng.integers(-127, 128, (k, n)).astype(np.int8)
    sx = rng.uniform(0.01, 0.1, (m,)).astype(np.float32)
    sw = rng.uniform(0.01, 0.1, (n,)).astype(np.float32)
    out = matmul_int8(jnp.asarray(x_q), jnp.asarray(w_q), jnp.asarray(sx),
                      jnp.asarray(sw), bm=bm, bk=bk, bn=bn,
                      out_dtype=jnp.float32, interpret=True)
    ref = matmul_int8_ref(x_q, w_q, sx, sw, jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantized_matmul_close_to_fp(dtype):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((64, 128)), dtype)
    w = jnp.asarray(rng.standard_normal((128, 96)) * 0.1, dtype)
    out = quantized_matmul(x, w, interpret=True,
                           out_dtype=jnp.float32)
    exact = (x.astype(jnp.float32) @ w.astype(jnp.float32))
    # int8 quantization error bound (~1%)
    rel = np.linalg.norm(np.asarray(out) - np.asarray(exact)) / \
        np.linalg.norm(np.asarray(exact))
    assert rel < 0.03, rel


def test_quantized_matmul_bridge_padded_blocks():
    """Regression: bridge blocks for dims with no MXU-aligned divisor
    (n=360 -> bn=384 padded) must run through the kernel via zero-padding
    instead of tripping the divisibility assert."""
    from repro.core.tpu_bridge import select_matmul_blocks
    c = select_matmul_blocks(512, 256, 360)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((512, 256)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((256, 360)) * 0.1, jnp.float32)
    out = quantized_matmul(x, w, block_shapes=(c.bm, c.bk, c.bn),
                           interpret=True, out_dtype=jnp.float32)
    assert out.shape == (512, 360)
    exact = x @ w
    rel = np.linalg.norm(np.asarray(out) - np.asarray(exact)) / \
        np.linalg.norm(np.asarray(exact))
    assert rel < 0.03, rel


@pytest.mark.parametrize("m,k,n", [(100, 200, 360), (8, 72, 100),
                                   (130, 24, 1000)])
def test_matmul_bridge_candidate_blocks_padded(m, k, n):
    """Golden numerics on dims with no MXU-aligned divisor: every bridge
    candidate pick must run through the kernel's zero-padding path and
    match the fp oracle (the executor's matmul dispatch contract)."""
    from repro.core.tpu_bridge import select_matmul_blocks
    c = select_matmul_blocks(m, k, n)
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)) * 0.1, jnp.float32)
    out = quantized_matmul(x, w, block_shapes=(c.bm, c.bk, c.bn),
                           interpret=True, out_dtype=jnp.float32)
    assert out.shape == (m, n)
    exact = x @ w
    rel = np.linalg.norm(np.asarray(out) - np.asarray(exact)) / \
        np.linalg.norm(np.asarray(exact))
    assert rel < 0.03, rel


def test_matmul_mapping_derived_blocks():
    """Blocks derived from an optimized CIM mapping
    (`tpu_bridge.select_blocks_from_mapping`) are MXU-legal, reduce over
    the whole K, and are numerically exact vs the int8 oracle on identical
    quantized operands."""
    from repro.core.arch import default_arch
    from repro.core.baselines import greedy_mapping
    from repro.core.tpu_bridge import select_blocks_from_mapping
    from repro.core.workload import gemm
    from repro.kernels.matmul_int8.ops import quantized_matmul_and_ref
    arch = default_arch()
    layer = gemm("t.g", 96, 360, 200)       # (96 x 200) @ (200 x 360)
    mp = greedy_mapping(layer, arch)
    c = select_blocks_from_mapping(mp, layer, arch)
    assert c.bm % 8 == 0 and c.bk % 128 == 0 and c.bn % 128 == 0
    assert c.bk == 256                      # K = 200 padded to the lane
    assert 2 * c.vmem_bytes <= 64 * 1024 * 1024
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((96, 200)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((200, 360)) * 0.1, jnp.float32)
    out, ref = quantized_matmul_and_ref(x, w,
                                        block_shapes=(c.bm, c.bk, c.bn),
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# (M, K, N, VMEM budget in bytes, whole K expected): minicpm-2b's four plan
# GEMMs at prefill 1 x 2048 (wq/wk/wv/wo, ffn_up, ffn_down, the LM head),
# a K whose whole reduction overflows the budget (the mapping's C tile
# instead), and a budget so small that the mapping's tiles are halved too
BRIDGE_CASES = {
    "wq": (2048, 2304, 2304, None, True),
    "ffn_up": (2048, 2304, 11520, None, True),
    "ffn_down": (2048, 5760, 2304, None, True),
    "lm_head": (1, 2304, 122880, None, True),
    "k_over_vmem": (2048, 1 << 17, 2304, None, False),
    "halved": (2048, 2304, 2304, 1 << 19, False),
}


@pytest.mark.parametrize("case", sorted(BRIDGE_CASES))
def test_mapping_blocks_reduce_whole_k(case):
    """The bridge gives the whole (lane-padded) K as one reduction step
    wherever the double-buffered eq. 9 working set fits, and otherwise
    the mapping's C tile, halving the blocks until eq. 9 holds."""
    from repro.core.arch import default_arch
    from repro.core.baselines import greedy_mapping
    from repro.core.tpu_bridge import VMEM_BYTES, select_blocks_from_mapping
    from repro.core.workload import gemm
    m, k, n, vmem, whole = BRIDGE_CASES[case]
    vmem = vmem or VMEM_BYTES
    arch = default_arch()
    layer = gemm(f"t.{case}", m, n, k)
    mp = greedy_mapping(layer, arch)
    c = select_blocks_from_mapping(mp, layer, arch, vmem_bytes=vmem)
    assert c.bm % 8 == 0 and c.bk % 128 == 0 and c.bn % 128 == 0
    assert c.vmem_bytes == c.bm * c.bk + c.bk * c.bn + 4 * c.bm * c.bn
    assert 2 * c.vmem_bytes <= vmem
    k_steps = -(-k // c.bk)
    assert (k_steps == 1) == whole, (c.bm, c.bk, c.bn)
    if not whole:
        assert c.bk <= arch.macro_rows      # the mapping's wordline tile
    if vmem < VMEM_BYTES:                   # halved below the mapping's
        full = select_blocks_from_mapping(mp, layer, arch)
        assert c.bm * c.bn < full.bm * full.bn


# (M, K, N, blocks): the LM head's shape class, one row against a vocabulary
# that no bn divides (M, K and N padded), one that needs no padding, and
# the bridge's whole-K reduction (K = 200 padded to one 256 block)
PROGRAM_CASES = {"lm_head_padded": (1, 64, 200, (8, 32, 128)),
                 "aligned": (32, 64, 48, (16, 32, 16)),
                 "whole_k": (24, 200, 136, (8, 256, 128))}


def _case_operands(m, k, n, seed):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((m, k)), jnp.float32),
            jnp.asarray(rng.standard_normal((k, n)) * 0.1, jnp.float32))


@pytest.mark.parametrize("case", sorted(PROGRAM_CASES))
def test_quantized_matmul_is_the_eager_composition(case):
    """The one compiled program gives, bit for bit, what the eager ops it
    replaces give: quantize both operands, zero-pad to block multiples,
    the kernel, then slice."""
    m, k, n, (bm, bk, bn) = PROGRAM_CASES[case]
    x, w = _case_operands(m, k, n, 10)
    out = quantized_matmul(x, w, block_shapes=(bm, bk, bn), interpret=True,
                           out_dtype=jnp.float32)
    x_q, x_s = quantize_rowwise(x, axis=1)
    w_q, w_s = quantize_rowwise(w, axis=0)
    mp, kp, np_ = (-(-d // b) * b for d, b in ((m, bm), (k, bk), (n, bn)))
    x_q = jnp.pad(x_q, ((0, mp - m), (0, kp - k)))
    w_q = jnp.pad(w_q, ((0, kp - k), (0, np_ - n)))
    x_s = jnp.pad(x_s, (0, mp - m))
    w_s = jnp.pad(w_s, (0, np_ - n))
    eager = matmul_int8(x_q, w_q, x_s, w_s, bm=bm, bk=bk, bn=bn,
                        out_dtype=jnp.float32, interpret=True)[:m, :n]
    assert out.shape == (m, n)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(eager))


@pytest.mark.parametrize("case", sorted(PROGRAM_CASES))
def test_quantized_matmul_matches_ref(case):
    """The kernel and the plain int8 oracle, on the same quantized
    operands, agree bit for bit whatever the blocks: the int32 sum is
    exact and both apply the scales in one order."""
    from repro.kernels.matmul_int8.ops import quantized_matmul_and_ref
    m, k, n, blocks = PROGRAM_CASES[case]
    x, w = _case_operands(m, k, n, 12)
    out, ref = quantized_matmul_and_ref(x, w, block_shapes=blocks,
                                        interpret=True)
    assert out.shape == ref.shape == (m, n)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("case", sorted(PROGRAM_CASES))
def test_quantized_matmul_traces_once_per_shape(case):
    """A new shape builds one program; calling it again with the same
    shapes traces and compiles nothing."""
    m, k, n, blocks = PROGRAM_CASES[case]
    k = 3 * k                   # a shape no other test calls
    x, w = _case_operands(m, k, n, 11)
    call = lambda: jax.block_until_ready(quantized_matmul(
        x, w, block_shapes=blocks, interpret=True, out_dtype=jnp.float32))
    got = lambda name: tracing.counters().get(name, 0)
    t0 = got("matmul_int8.traces")
    call()
    t1, c1 = got("matmul_int8.traces"), got("jax.compiles")
    call()
    assert (t1 - t0, got("matmul_int8.traces") - t1,
            got("jax.compiles") - c1) == (1, 0, 0)


def test_quantize_roundtrip():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    q, s = quantize_rowwise(x, axis=1)
    back = q.astype(jnp.float32) * s[:, None]
    np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                               atol=float(jnp.max(jnp.abs(x))) / 100)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("l,hd,bq,bk", [(128, 64, 32, 32), (256, 64, 64, 64),
                                        (128, 128, 64, 32)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_vs_ref(l, hd, bq, bk, causal):
    rng = np.random.default_rng(3)
    b, h = 2, 2
    q = jnp.asarray(rng.standard_normal((b, l, h, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, l, h, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, l, h, hd)), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), dtype)
    k = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), dtype)
    v = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol)


def test_flash_attention_legal_block_clamp():
    """Sequence lengths that are not 128-multiples (VLM prefill = text +
    patch tokens) must clamp the requested blocks to exact divisors instead
    of tripping the kernel's tiling assert."""
    from repro.kernels.flash_attention.ops import legal_block
    assert legal_block(264, 256) == 88           # largest 8-aligned divisor
    assert legal_block(96, 128) == 96
    assert legal_block(1, 128) == 1              # decode step (lq = 1)
    assert legal_block(7, 256) == 7              # no aligned divisor at all
    rng = np.random.default_rng(10)
    q = jnp.asarray(rng.standard_normal((1, 264, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 264, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 264, 2, 16)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_decode_step_vs_cache():
    """The executor's decode dispatch: one query step (lq=1) against a
    longer KV cache, non-causal."""
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((4, 1, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((4, 256, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((4, 256, 2, 16)), jnp.float32)
    out = flash_attention(q, k, v, causal=False, interpret=True)
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# ssd intra-chunk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q,h,n,p", [(32, 2, 16, 16), (64, 4, 32, 32),
                                     (128, 2, 64, 64)])
def test_ssd_intra_chunk_vs_ref(q, h, n, p):
    rng = np.random.default_rng(5)
    b, nc = 2, 2
    c = jnp.asarray(rng.standard_normal((b, nc, q, h, n)), jnp.float32)
    bb = jnp.asarray(rng.standard_normal((b, nc, q, h, n)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (b, nc, q, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 4.0, (h,)), jnp.float32)
    s = jnp.cumsum(dt * a, axis=2)
    x = jnp.asarray(rng.standard_normal((b, nc, q, h, p)), jnp.float32)
    out = ssd_intra_chunk(c, bb, s, dt, x, interpret=True)
    ref = ssd_intra_chunk_ref(c, bb, s, dt, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ssd_intra_chunk_and_ref_helper():
    """The executor's fused SSD dispatch (`ssd_intra_chunk_and_ref`) on an
    odd chunk length: kernel and oracle on identical inputs."""
    from repro.kernels.ssd_scan.ops import ssd_intra_chunk_and_ref
    rng = np.random.default_rng(12)
    b, nc, q, h, n, p = 1, 1, 24, 1, 8, 8
    c = jnp.asarray(rng.standard_normal((b, nc, q, h, n)), jnp.float32)
    bb = jnp.asarray(rng.standard_normal((b, nc, q, h, n)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (b, nc, q, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 4.0, (h,)), jnp.float32)
    s = jnp.cumsum(dt * a, axis=2)
    x = jnp.asarray(rng.standard_normal((b, nc, q, h, p)), jnp.float32)
    out, ref = ssd_intra_chunk_and_ref(c, bb, s, dt, x, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ssd_chunked_matches_sequential():
    """End-to-end SSD (chunked algorithm incl. inter-chunk recurrence) vs
    the step-by-step recurrence oracle."""
    from repro.kernels.ssd_scan.ref import ssd_sequential_ref
    from repro.models.ssm import ssd_chunked
    rng = np.random.default_rng(6)
    b, l, h, p, g, n = 2, 64, 4, 16, 1, 16
    x = jnp.asarray(rng.standard_normal((b, l, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.2, (b, l, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 4.0, (h,)), jnp.float32)
    bm = jnp.asarray(rng.standard_normal((b, l, g, n)), jnp.float32)
    cm = jnp.asarray(rng.standard_normal((b, l, g, n)), jnp.float32)
    d = jnp.asarray(rng.standard_normal((h,)), jnp.float32)
    y, hf = ssd_chunked(x, dt, a, bm, cm, d, chunk=16)
    y_ref, h_ref = ssd_sequential_ref(x, dt, a, bm, cm, d)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(h_ref),
                               rtol=2e-3, atol=2e-3)


def test_ssd_kernel_path_in_chunked():
    from repro.models.ssm import ssd_chunked
    rng = np.random.default_rng(7)
    b, l, h, p, g, n = 1, 64, 2, 16, 1, 16
    x = jnp.asarray(rng.standard_normal((b, l, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.2, (b, l, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 4.0, (h,)), jnp.float32)
    bm = jnp.asarray(rng.standard_normal((b, l, g, n)), jnp.float32)
    cm = jnp.asarray(rng.standard_normal((b, l, g, n)), jnp.float32)
    d = jnp.asarray(rng.standard_normal((h,)), jnp.float32)
    y0, _ = ssd_chunked(x, dt, a, bm, cm, d, chunk=32, use_kernel=False)
    y1, _ = ssd_chunked(x, dt, a, bm, cm, d, chunk=32, use_kernel=True,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=2e-3, atol=2e-3)
