"""GLM-4-9B (arXiv:2406.12793; hf:THUDM/glm-4-9b config.json and
modeling_chatglm.py): dense, 2 KV groups of 16 query heads, QKV bias,
rotary on the first 64 of each head's 128 dims in adjacent pairs."""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="glm4-9b",
    family="dense",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=2,
    head_dim=128,
    d_ff=13696,
    vocab_size=151552,
    gated_mlp=True,
    rope_theta=10_000.0,
    rope_dim=64,
    rope_interleaved=True,
    qkv_bias=True,
    norm_eps=1.5625e-7,
    source="arXiv:2406.12793; hf:THUDM/glm-4-9b",
)
