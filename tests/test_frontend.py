"""Model frontend (core/frontend.py + core/lm_workloads.py): per-family
analytic MAC checks, GQA/MoE/SSD lowering rules, scenario M-dim semantics,
and a full registry sweep."""

import dataclasses

import pytest

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import SHAPES, ShapeSpec, applicable_shapes
from repro.core.frontend import extract_all, extract_workload
from repro.core.network import dedup_layers

DECODE = SHAPES["decode_32k"]
PREFILL = SHAPES["prefill_32k"]


def _layers_named(work, suffix):
    out = [(l, c) for l, c in zip(work.layers, work.counts)
           if l.name.endswith(suffix)]
    assert out, (suffix, [l.name for l in work.layers])
    return out


# ---------------------------------------------------------------------------
# Dense: closed-form MAC accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch_id", ["glm4-9b", "starcoder2-7b"])
def test_dense_decode_macs_match_param_count(arch_id):
    """A decode step does exactly one MAC per weight-matrix parameter per
    token: total extracted MACs == batch x matmul-param count (so FLOPs are
    the classic 2x active params per token). Embedding gather contributes
    no MACs and is excluded on both sides."""
    cfg = get_config(arch_id)
    work = extract_workload(cfg, DECODE)
    d, hd = cfg.d_model, cfg.resolved_head_dim
    attn = d * hd * (cfg.n_heads + 2 * cfg.n_kv_heads) + cfg.n_heads * hd * d
    ffn = d * cfg.d_ff * ((2 if cfg.gated_mlp else 1) + 1)
    matmul_params = cfg.n_layers * (attn + ffn) + cfg.padded_vocab() * d
    assert work.total_macs == DECODE.global_batch * matmul_params


def test_prefill_vs_decode_differ_only_in_m():
    """Decode-vs-prefill GEMMs share all weight dims (K, C) and differ only
    in the token dim M — the property that makes whole-zoo solves cheap."""
    cfg = get_config("glm4-9b")
    pre = extract_workload(cfg, PREFILL)
    dec = extract_workload(cfg, DECODE)
    pre_by_suffix = {l.name.split(".")[-1]: l for l in pre.layers}
    for l in dec.layers:
        p = pre_by_suffix[l.name.split(".")[-1]]
        assert (l.bound("K"), l.bound("C")) == (p.bound("K"), p.bound("C"))
        assert l.bound("N") == DECODE.global_batch
        # prefill only materializes last-position logits for the LM head
        assert p.bound("N") == \
            (1 if l.name.endswith(".lm_head") else PREFILL.seq_len)


def test_lm_head_m_per_scenario():
    """Train: logits at every position; prefill: last position only;
    decode: one position per sequence, batched into M."""
    cfg = get_config("glm4-9b")
    heads = {s: _layers_named(w, ".lm_head")[0]
             for s, w in extract_all(cfg).items()}
    assert heads["train_4k"][0].bound("N") == SHAPES["train_4k"].seq_len
    assert heads["prefill_32k"][0].bound("N") == 1
    assert heads["decode_32k"][0].bound("N") == DECODE.global_batch


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def test_gqa_kv_projections_use_n_kv_heads():
    cfg = get_config("glm4-9b")          # extreme GQA: kv=2 of 32 heads
    work = extract_workload(cfg, DECODE)
    hd = cfg.resolved_head_dim
    (wk, _), = _layers_named(work, ".wk")
    (wv, _), = _layers_named(work, ".wv")
    (wq, _), = _layers_named(work, ".wq")
    assert wk.bound("K") == wv.bound("K") == cfg.n_kv_heads * hd == 2 * hd
    assert wq.bound("K") == cfg.n_heads * hd
    # K and V projections are structurally identical -> one dedup solve
    assert dedup_layers([wk, wv])[1][0] == dedup_layers([wk, wv])[1][1]


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def _routed_macs(work):
    return sum(l.macs * c for l, c in zip(work.layers, work.counts)
               if ".exp." in l.name or l.name.endswith(
                   (".exp.ffn_up", ".exp.ffn_down")))


def test_moe_routed_macs_scale_with_top_k():
    cfg = get_config("qwen2-moe-a2.7b")
    base = _routed_macs(extract_workload(cfg, PREFILL))
    doubled = _routed_macs(extract_workload(
        dataclasses.replace(cfg, top_k=2 * cfg.top_k), PREFILL))
    assert doubled / base == pytest.approx(2.0, rel=0.01)
    # ...and are independent of the expert count (same active compute)
    spread = _routed_macs(extract_workload(
        dataclasses.replace(cfg, n_experts=2 * cfg.n_experts), PREFILL))
    assert spread / base == pytest.approx(1.0, rel=0.01)


def test_moe_shared_and_dense_residual_paths():
    # qwen: 4 shared experts see every token
    qwen = get_config("qwen2-moe-a2.7b")
    work = extract_workload(qwen, PREFILL)
    shared = _layers_named(work, ".shared.ffn_up")
    (l, c), = shared
    assert l.bound("N") == PREFILL.seq_len
    assert c == qwen.n_layers * PREFILL.instance_count * \
        qwen.n_shared_experts
    # arctic: dense-residual MLP in parallel with the routed experts
    arctic = get_config("arctic-480b")
    res = _layers_named(extract_workload(arctic, PREFILL), ".res.ffn_up")
    (l, _), = res
    assert l.bound("N") == PREFILL.seq_len and l.bound("C") == arctic.d_model


def test_moe_decode_expert_rows_floor_at_one():
    """A decode microbatch routed over many experts must never emit a
    zero-row GEMM (arctic: 128 tokens x top-2 over 128 experts -> 2)."""
    cfg = get_config("arctic-480b")
    work = extract_workload(cfg, DECODE)
    (l, c), = _layers_named(work, ".exp.ffn_up")
    assert l.bound("N") >= 1
    assert c == cfg.n_layers * cfg.n_experts


# ---------------------------------------------------------------------------
# SSD / hybrid
# ---------------------------------------------------------------------------

def test_ssd_block_decomposition_prefill():
    cfg = get_config("mamba2-1.3b")
    work = extract_workload(cfg, PREFILL)
    d_inner = cfg.ssm_expand * cfg.d_model
    nh = d_inner // cfg.ssm_head_dim
    d_proj = 2 * d_inner + 2 * cfg.ssm_groups * cfg.ssm_state + nh
    (inp, _), = _layers_named(work, ".in_proj")
    assert (inp.bound("K"), inp.bound("C")) == (d_proj, cfg.d_model)
    (sc, c_sc), = _layers_named(work, ".ssd_scores")
    assert (sc.bound("N"), sc.bound("K"), sc.bound("C")) == \
        (256, 256, cfg.ssm_state)                  # Q x Q x N duality form
    n_chunks = PREFILL.seq_len // 256
    assert c_sc == cfg.n_layers * PREFILL.instance_count * n_chunks * nh


def test_ssd_decode_is_rank1_state_update():
    cfg = get_config("mamba2-1.3b")
    work = extract_workload(cfg, DECODE)
    (upd, c), = _layers_named(work, ".ssd_state_upd")
    assert (upd.bound("N"), upd.bound("K"), upd.bound("C")) == \
        (cfg.ssm_state, cfg.ssm_head_dim, 1)
    nh = cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim
    assert c == cfg.n_layers * DECODE.global_batch * nh


#: The lowered GEMM of each shared-block weight: its name's suffix, and the
#: path of the ``init_model`` parameter (in, out) it multiplies by.
SHARED_GEMMS = {
    "shared.wq": ("shared_blocks", "attn", "wq"),
    "shared.wk": ("shared_blocks", "attn", "wk"),
    "shared.wv": ("shared_blocks", "attn", "wv"),
    "shared.wo": ("shared_blocks", "attn", "wo"),
    "shared.ffn_down": ("shared_blocks", "mlp", "down"),
    "shared.linear": ("app_blocks", "linear"),
    "shared.adapter_q_a": ("app_blocks", "attn_adapters", "q", "a"),
    "shared.adapter_q_b": ("app_blocks", "attn_adapters", "q", "b"),
    "shared.adapter_k_a": ("app_blocks", "attn_adapters", "k", "a"),
    "shared.adapter_k_b": ("app_blocks", "attn_adapters", "k", "b"),
    "shared.adapter_v_a": ("app_blocks", "attn_adapters", "v", "a"),
    "shared.adapter_v_b": ("app_blocks", "attn_adapters", "v", "b"),
    "shared.adapter_mlp_a": ("app_blocks", "mlp_adapter", "a"),
    "shared.adapter_mlp_b": ("app_blocks", "mlp_adapter", "b"),
}


def test_hybrid_shared_attention_multiplicity():
    """Zamba2's shared blocks are parameter-shared but *executed* once per
    hybrid layer: every shared-block GEMM counts applications, not layers
    or blocks; each is the shape of the weight ``init_model`` gives it
    (QKV from concat(hidden, embedding), O back to d_model, the fused
    gate and up, the per-application linear and both adapter kinds); and
    every layer has an SSD block."""
    import jax
    import jax.numpy as jnp

    from repro.models.transformer import init_model
    cfg = get_config("zamba2-1.2b")
    assert cfg.shared_attn_adapter and cfg.shared_mlp_adapter
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg,
                                               jnp.bfloat16))
    work = extract_workload(cfg, PREFILL)
    apps = len(cfg.hybrid_layer_ids) * PREFILL.instance_count
    for suffix, path in SHARED_GEMMS.items():
        (l, c), = _layers_named(work, suffix)
        w = params
        for k in path:
            w = w[k]
        w = w["w"]
        lead = cfg.num_mem_blocks if path[0] == "shared_blocks" else \
            len(cfg.hybrid_layer_ids)
        assert w.shape == (lead, l.bound("C"), l.bound("K")), suffix
        assert c == apps, suffix
    (up, c), = _layers_named(work, "shared.ffn_up")
    mlp = params["shared_blocks"]["mlp"]
    assert (up.bound("C"), up.bound("K")) == (
        cfg.d_model, mlp["gate"]["w"].shape[-1] + mlp["up"]["w"].shape[-1])
    assert c == apps
    (_, c), = _layers_named(work, ".in_proj")
    assert c == cfg.n_layers * PREFILL.instance_count


# ---------------------------------------------------------------------------
# Enc-dec / VLM scenario semantics
# ---------------------------------------------------------------------------

def test_encdec_cross_attention_kv_cached_at_decode():
    cfg = get_config("seamless-m4t-large-v2")
    pre = extract_workload(cfg, PREFILL)
    dec = extract_workload(cfg, DECODE)
    # prefill: cross K/V project the encoder memory (frontend_seq rows)
    (xk, _), = _layers_named(pre, "xattn.wk")
    assert xk.bound("N") == cfg.frontend_seq
    # decode: encoder not re-run, cross K/V served from cache
    names = [l.name for l in dec.layers]
    assert not any(n.endswith(("xattn.wk", "xattn.wv")) for n in names)
    assert not any(".enc." in n for n in names)
    assert any(n.endswith("xattn.wq") for n in names)


def test_vlm_prefill_prepends_patch_tokens():
    cfg = get_config("pixtral-12b")
    pre = extract_workload(cfg, PREFILL)
    dec = extract_workload(cfg, DECODE)
    (wq_p, _), = _layers_named(pre, ".wq")
    (wq_d, _), = _layers_named(dec, ".wq")
    assert wq_p.bound("N") == PREFILL.seq_len + cfg.frontend_seq
    assert wq_d.bound("N") == DECODE.global_batch


# ---------------------------------------------------------------------------
# Registry sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_registry_sweep_extracts_valid_workloads(arch_id):
    """Every config extracts a non-empty, positive-dims, all-GEMM workload
    for every one of its applicable ShapeSpecs."""
    cfg = get_config(arch_id)
    shapes = {n for n, s in applicable_shapes(cfg).items() if s is not None}
    works = extract_all(cfg)
    assert set(works) == shapes
    for sname, work in works.items():
        assert len(work) > 0, (arch_id, sname)
        assert work.total_macs > 0
        for l, c in zip(work.layers, work.counts):
            assert c >= 1
            assert l.is_gemm, l.name
            for d in ("N", "K", "C"):
                assert l.bound(d) >= 1, (l.name, d)
        assert any(l.name.endswith(".lm_head") for l in work.layers)


def test_registry_sweep_dedup_beats_extraction_count():
    """Pooled across the zoo, structural dedup must need fewer solves than
    extracted layers (the acceptance property of the lm benchmark)."""
    pool = []
    for aid in ARCH_IDS:
        for work in extract_all(get_config(aid),
                                ("prefill_32k", "decode_32k")).values():
            pool += list(work.layers)
    unique, _ = dedup_layers(pool)
    assert 0 < len(unique) < len(pool)


def test_reduced_configs_extract_too():
    """The CI smoke path: reduced configs stay extractable everywhere."""
    for aid in ARCH_IDS:
        cfg = get_config(aid).reduced()
        for work in extract_all(cfg).values():
            assert len(work) > 0 and work.total_macs > 0


def test_custom_serving_spec():
    """serve_lm.py-style ad-hoc ShapeSpec (decode batch 4)."""
    spec = ShapeSpec("serve", seq_len=1, global_batch=4, kind="decode")
    work = extract_workload(get_config("glm4-9b").reduced(), spec)
    assert all(l.bound("N") == 4 for l in work.layers
               if not l.name.endswith(("ssd_state_upd", "ssd_readout")))
