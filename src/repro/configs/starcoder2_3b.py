"""starcoder2-3b — dense, GQA (kv=2), RoPE; LayerNorm, tanh-GELU MLP with
biases, QKV bias, tied embeddings, a 4096-token sliding window
[arXiv:2402.19173; hf:bigcode/starcoder2-3b]."""

from repro.configs.base import ArchConfig

ARCH = ArchConfig(
    name="starcoder2-3b",
    arch_type="dense",
    n_layers=30,
    d_model=3072,
    n_heads=24,
    n_kv_heads=2,
    d_ff=12288,
    vocab=49152,
    qkv_bias=True,
    rope_theta=999_999.4420358813,
    sliding_window=4096,
    norm="layernorm",
    norm_eps=1e-5,
    tie_embeddings=True,
    citation="arXiv:2402.19173",
)

SMOKE = ArchConfig(
    name="starcoder2-smoke",
    arch_type="dense",
    n_layers=2,
    d_model=128,
    n_heads=4,
    n_kv_heads=2,
    d_ff=512,
    vocab=512,
    qkv_bias=True,
    norm="layernorm",
    tie_embeddings=True,
    citation="reduced variant of arXiv:2402.19173",
)
