"""Zamba2's hybrid stack against the plain reference ``bench/refs/zamba2.py``,
and the reference against transformers' ``Zamba2ForCausalLM``.

The model is cut to a CPU size that keeps what Zamba2 adds: two shared
blocks applied in turn to concat(hidden, embedding), both adapter kinds,
two SSM groups, a hybrid layer that is not the last one, the GELU gate and
the softmax scale ``(head_dim / 2) ** -0.5``. Program and reference both
run in float32 at HIGHEST precision, so they differ only in the order of
their sums: the program's chunked SSD against the reference's sequential
recurrence, its cached decode against the reference's full forward. The
tolerance, a relative L2 distance of 1e-4 over a row of logits, is over
twenty times the gap that ordering leaves (1.7e-6 to 4.4e-6 here, and
3.2e-6 between the reference and transformers) and under a thousandth
of what float8 products give (0.27).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.refs import zamba2 as ref
from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.models.ssm import gated_rms_norm, ssd_chunked
from repro.models.transformer import init_model
from repro.train.steps import (StepConfig, decode_caches, init_caches,
                               make_decode_step, make_prefill_step)

CFG = dataclasses.replace(
    get_config("zamba2-7b").reduced(), n_layers=5, hybrid_layer_ids=(1, 3),
    shared_attn_adapter=True)
STEP = StepConfig(remat=False, compute_dtype=jnp.float32)
TOL = 1e-4


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.linalg.norm(a - b, axis=-1)
                        / np.linalg.norm(b, axis=-1)))


def _params(cfg, seed=7, dtype=jnp.float32):
    shapes = jax.eval_shape(lambda k: init_model(k, cfg, dtype),
                            jax.random.PRNGKey(0))
    return weights.params(seed, shapes)


def _tokens(cfg, shape, seed=7):
    return np.asarray(weights.tokens(seed, shape, cfg.vocab_size))


def test_config_keeps_what_zamba2_adds():
    assert CFG.num_mem_blocks == 2 and CFG.ssm_groups == 2
    assert CFG.shared_attn_adapter and CFG.shared_mlp_adapter
    assert CFG.hybrid_layer_ids[-1] < CFG.n_layers - 1
    assert CFG.n_heads * CFG.resolved_head_dim == 2 * CFG.d_model


# 40 positions: one chunk; 320: two chunks of 256, the second padded
@pytest.mark.parametrize("length", [40, 320])
def test_prefill_matches_reference(length):
    params = _params(CFG)
    toks = _tokens(CFG, (2, length))
    step = jax.jit(make_prefill_step(CFG, STEP))
    with jax.default_matmul_precision("highest"):
        got, _ = step(params, {"tokens": jnp.asarray(toks)})
    want = ref.logits(params, dataclasses.asdict(CFG), toks,
                      length - 1)[:, 0]
    assert _rel(got, want) < TOL


def test_prefill_then_decode_matches_reference():
    """Prefill 24 positions, then decode 8 through the caches (teacher
    forced): each step's logits against the reference's full forward at
    that position."""
    params = _params(CFG)
    prompt, steps, batch = 24, 8, 2
    toks = _tokens(CFG, (batch, prompt + steps))
    prefill = jax.jit(make_prefill_step(CFG, STEP))
    decode = jax.jit(make_decode_step(CFG, STEP), donate_argnums=(2,))
    got = []
    with jax.default_matmul_precision("highest"):
        first, caches = prefill(params, {"tokens": jnp.asarray(
            toks[:, :prompt])})
        caches = decode_caches(CFG, caches, batch, prompt + steps,
                               STEP.compute_dtype)
        got.append(np.asarray(first))
        for t in range(prompt, prompt + steps - 1):
            logits, caches = decode(
                params, {"tokens": jnp.asarray(toks[:, t:t + 1])}, caches)
            got.append(np.asarray(logits))
    want = ref.logits(params, dataclasses.asdict(CFG), toks, prompt - 1)
    assert _rel(np.stack(got, 1), np.asarray(want)[:, :steps]) < TOL
    states, kv = caches
    assert kv.k.shape[0] == len(CFG.hybrid_layer_ids)
    np.testing.assert_array_equal(np.asarray(kv.length),
                                  prompt + steps - 1)


def test_float8_control_is_far():
    """The control the chip cells compare against: float8 products move
    the reference's logits by far more than the tolerance above."""
    params = _params(CFG)
    toks = _tokens(CFG, (2, 40))
    m = dataclasses.asdict(CFG)
    exact = ref.logits(params, m, toks, 39)
    low = ref.logits(params, m, toks, 39, "fp8")
    assert _rel(low, exact) > 1000 * TOL


def test_grouped_gated_norm():
    """``y * silu(z)`` normalised over each group on its own: scaling one
    group leaves the other's output as it was."""
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.standard_normal((3, 16)), jnp.float32)
    z = jnp.asarray(rng.standard_normal((3, 16)), jnp.float32)
    p = {"scale": jnp.asarray(1 + 0.1 * rng.standard_normal(16),
                              jnp.float32)}
    out = np.asarray(gated_rms_norm(p, y, z, 2))
    g = np.asarray(y * jax.nn.silu(z)).reshape(3, 2, 8)
    want = g / np.sqrt(np.mean(g * g, -1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(out, want.reshape(3, 16) * np.asarray(
        p["scale"]), rtol=1e-5, atol=1e-6)
    y2 = y.at[:, :8].multiply(10.0)
    out2 = np.asarray(gated_rms_norm(p, y2, z, 2))
    np.testing.assert_allclose(out2[:, 8:], out[:, 8:], rtol=1e-6)
    assert not np.allclose(np.asarray(gated_rms_norm(p, y2, z, 1))[:, 8:],
                           out[:, 8:])


@pytest.mark.parametrize("sm_scale", [None, 0.5 / math.sqrt(2)])
def test_flash_attention_scale(sm_scale):
    """The flash kernel (interpreted) at the default scale and at
    Zamba2's ``(hd / 2) ** -0.5`` matches the plain attention at that
    scale."""
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 256, 2, 16)),
                           jnp.float32) for _ in range(3))
    got = flash_attention(q, k, v, block_q=128, block_k=128,
                          sm_scale=sm_scale, interpret=True)
    want = attention_ref(q, k, v, sm_scale=sm_scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    if sm_scale is not None:
        other = attention_ref(q, k, v)
        assert not np.allclose(np.asarray(got), np.asarray(other),
                               atol=1e-3)


def test_ssd_kernel_path_matches_jnp():
    """The chunked SSD with its intra-chunk term in ``ssd_scan``
    (interpreted) against the jnp path: 2 chunks, heads over 2 groups."""
    rng = np.random.default_rng(2)
    b, l, h, p, g, n = 1, 64, 4, 8, 2, 8
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    x, bm, cm = f(b, l, h, p), f(b, l, g, n), f(b, l, g, n)
    dt = jax.nn.softplus(f(b, l, h))
    a = -jnp.exp(f(h) * 0.1)
    d = f(h)
    with jax.default_matmul_precision("highest"):
        y0, h0 = ssd_chunked(x, dt, a, bm, cm, d, chunk=32)
        y1, h1 = ssd_chunked(x, dt, a, bm, cm, d, chunk=32, use_kernel=True,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0), rtol=1e-5,
                               atol=1e-5)


def _hf_model(cfg: ModelConfig, params):
    """transformers' Zamba2ForCausalLM with ``params`` copied in."""
    torch = pytest.importorskip("torch")
    tr = pytest.importorskip("transformers")
    ids = cfg.hybrid_layer_ids
    hcfg = tr.Zamba2Config(
        vocab_size=cfg.padded_vocab(), hidden_size=cfg.d_model,
        num_hidden_layers=cfg.n_layers,
        layers_block_type=["hybrid" if i in ids else "mamba"
                           for i in range(cfg.n_layers)],
        mamba_d_state=cfg.ssm_state, mamba_d_conv=cfg.ssm_conv,
        mamba_expand=cfg.ssm_expand, mamba_ngroups=cfg.ssm_groups,
        n_mamba_heads=cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim,
        intermediate_size=cfg.d_ff, hidden_act=cfg.mlp_act,
        num_attention_heads=cfg.n_heads, num_mem_blocks=cfg.num_mem_blocks,
        use_shared_attention_adapter=cfg.shared_attn_adapter,
        adapter_rank=cfg.adapter_rank, use_mem_rope=True,
        rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps,
        chunk_size=256, tie_word_embeddings=True)
    assert hcfg.attention_head_dim == cfg.resolved_head_dim
    model = tr.Zamba2ForCausalLM(hcfg).eval()
    t = lambda a: torch.from_numpy(np.asarray(a, np.float32).copy())
    w = lambda p: t(np.asarray(p["w"]).T)
    take = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
    sd = {"model.embed_tokens.weight": t(params["embed"]["table"]),
          "model.final_layernorm.weight": t(params["ln_f"]["scale"])}
    for i in range(cfg.n_layers):
        blk = take(params["blocks"], i)
        pre = f"model.layers.{i}."
        if i in ids:
            j = ids.index(i)
            app = take(params["app_blocks"], j)
            sh = take(params["shared_blocks"], j % cfg.num_mem_blocks)
            st = pre + "shared_transformer."
            sd[pre + "linear.weight"] = w(app["linear"])
            for n in "qkvo":
                sd[st + f"self_attn.{n}_proj.weight"] = w(sh["attn"]["w" + n])
            for n, ad in app.get("attn_adapters", {}).items():
                lst = st + f"self_attn.linear_{n}_adapter_list.{j}."
                sd[lst + "0.weight"] = w(ad["a"])
                sd[lst + "1.weight"] = w(ad["b"])
            mlp = sh["mlp"]
            sd[st + "feed_forward.gate_up_proj.weight"] = t(np.concatenate(
                [np.asarray(mlp["gate"]["w"]), np.asarray(mlp["up"]["w"])],
                1).T)
            sd[st + "feed_forward.down_proj.weight"] = w(mlp["down"])
            lst = st + f"feed_forward.gate_up_proj_adapter_list.{j}."
            sd[lst + "0.weight"] = w(app["mlp_adapter"]["a"])
            sd[lst + "1.weight"] = w(app["mlp_adapter"]["b"])
            sd[st + "input_layernorm.weight"] = t(sh["ln_in"]["scale"])
            sd[st + "pre_ff_layernorm.weight"] = t(sh["ln_ff"]["scale"])
            pre += "mamba_decoder."
        mb = blk["mamba"]
        sd[pre + "input_layernorm.weight"] = t(blk["ln"]["scale"])
        sd[pre + "mamba.in_proj.weight"] = w(mb["in_proj"])
        sd[pre + "mamba.out_proj.weight"] = w(mb["out_proj"])
        sd[pre + "mamba.conv1d.weight"] = t(np.asarray(mb["conv_w"]).T[:,
                                                                       None])
        sd[pre + "mamba.conv1d.bias"] = t(mb["conv_b"])
        sd[pre + "mamba.dt_bias"] = t(mb["dt_bias"])
        sd[pre + "mamba.A_log"] = t(mb["a_log"])
        sd[pre + "mamba.D"] = t(mb["d_skip"])
        sd[pre + "mamba.norm.weight"] = t(mb["norm"]["scale"])
    own = model.state_dict()
    with torch.no_grad():
        for k, v in sd.items():
            assert own[k].shape == v.shape, (k, own[k].shape, v.shape)
            own[k].copy_(v)
    model.load_state_dict(own)
    return model


def test_reference_matches_transformers():
    """The reference against ``transformers.Zamba2ForCausalLM`` (its
    pure-PyTorch path, float32 on the CPU) with the same weights. dt
    stays above ``time_step_min``, which that path clamps to and the
    reference does not (its docstring), so the clamp never acts."""
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    cfg = dataclasses.replace(
        CFG, d_model=32, head_dim=16, ssm_head_dim=16, ssm_state=8,
        d_ff=48, adapter_rank=4, attn_scale=8 ** -0.5)
    params = _params(cfg, seed=11)
    toks = _tokens(cfg, (2, 12), seed=11)
    model = _hf_model(cfg, params)
    with torch.no_grad():
        want = model(torch.from_numpy(toks.astype(np.int64)),
                     use_cache=False).logits.numpy()
    got = ref.logits(params, dataclasses.asdict(cfg), toks, 0)
    assert _rel(got, want) < TOL


def _scopes(lowered) -> set[str]:
    import re
    return set(re.findall(r'loc\("([^"]*)"', lowered.as_text(
        debug_info=True)))


def _under(names, path: str) -> bool:
    return any(path in n for n in names)


def test_steps_carry_the_hybrid_scopes():
    """The prefill step with the kernels, lowered for the TPU, and the
    decode step name their ops for the spans the hybrid adds: each
    application under ``shared_block`` (its attention, rotary, MLP and
    adapters inside), each mixer under ``mamba`` with ``conv`` and
    ``ssd``, and the ``ssd_scan`` kernel under ``ssd``."""
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), CFG,
                                               jnp.bfloat16))
    prefill = make_prefill_step(CFG, StepConfig(remat=False, use_flash=True))
    tok = jax.ShapeDtypeStruct((1, 512), jnp.int32)
    names = _scopes(jax.jit(prefill).trace(params, {"tokens": tok}).lower(
        lowering_platforms=("tpu",)))
    for path in ("shared_block/attn/adapter", "shared_block/attn/rope",
                 "shared_block/attn/flash_attention.kernel",
                 "shared_block/mlp/adapter", "mamba/conv",
                 "mamba/ssd/ssd_scan.kernel"):
        assert _under(names, path), path
    caches = jax.eval_shape(lambda: init_caches(CFG, 2, 32))
    decode = make_decode_step(CFG, StepConfig(remat=False))
    names = _scopes(jax.jit(decode).trace(
        params, {"tokens": jax.ShapeDtypeStruct((2, 1), jnp.int32)},
        caches).lower())
    for path in ("shared_block/attn/kv_update", "shared_block/mlp",
                 "mamba/ssd", "mamba/conv"):
        assert _under(names, path), path
