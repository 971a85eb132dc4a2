"""Zamba2-7B [arXiv:2411.15242; hf:Zyphra/Zamba2-7B-Instruct config.json]:
81 Mamba2 layers (112 heads of 64, state 64, 2 groups); 13 of them first
apply one of two parameter-shared transformer blocks, in turn, to
concat(hidden, embedding) (7168 wide, 32 heads of 224, scale
(224/2)^-0.5, rotary over the whole head; a gated-GELU MLP of 14336 with
a rank-128 LoRA on gate and up per application), whose output passes a
per-application linear into that layer's Mamba input."""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="zamba2-7b",
    family="hybrid",
    n_layers=81,
    d_model=3584,
    n_heads=32,
    n_kv_heads=32,
    head_dim=224,
    d_ff=14336,
    vocab_size=32000,
    ssm_state=64,
    ssm_conv=4,
    ssm_expand=2,
    ssm_head_dim=64,
    ssm_groups=2,
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    num_mem_blocks=2,
    adapter_rank=128,
    shared_mlp_adapter=True,
    mlp_act="gelu",
    attn_scale=112 ** -0.5,
    rope_theta=10_000.0,
    norm_eps=1e-5,
    tie_embeddings=True,
    source="arXiv:2411.15242; hf:Zyphra/Zamba2-7B-Instruct",
)
