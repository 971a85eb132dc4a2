"""Zamba2-1.2B [arXiv:2411.15242; hf:Zyphra/Zamba2-1.2B] — hybrid: a
Mamba2 backbone in which every sixth layer first applies one
parameter-shared transformer block to concat(hidden, embedding).

Unverified: every value is recalled, not read from the model's
config.json (which is not at hand): the hybrid layers, one shared block,
heads of 2 x 2048 / 32 = 128, the adapters' rank and kinds, the GELU
gate and the tied head. ``zamba2-7b`` is the checked member of the
family."""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="zamba2-1.2b",
    family="hybrid",
    n_layers=38,
    d_model=2048,
    n_heads=32,
    n_kv_heads=32,
    head_dim=128,
    d_ff=8192,              # shared attn block's MLP
    vocab_size=32000,
    ssm_state=64,
    ssm_conv=4,
    ssm_expand=2,
    ssm_head_dim=64,
    hybrid_layer_ids=(5, 11, 17, 23, 29, 35),
    num_mem_blocks=1,
    adapter_rank=128,
    shared_attn_adapter=True,
    shared_mlp_adapter=True,
    mlp_act="gelu",
    attn_scale=64 ** -0.5,
    tie_embeddings=True,
    source="arXiv:2411.15242; hf:Zyphra/Zamba2-1.2B (recalled)",
)
