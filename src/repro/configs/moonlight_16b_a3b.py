"""moonlight-16b-a3b — DeepSeek-V3-style MoE: multi-head latent attention, one
leading dense layer, then 64 sigmoid-routed experts (6 a token, noaux_tc
correction bias) and 2 shared experts [hf:moonshotai/Moonlight-16B-A3B]."""

from repro.configs.base import ArchConfig

ARCH = ArchConfig(
    name="moonlight-16b-a3b",
    arch_type="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    vocab=163840,
    n_experts=64,
    moe_topk=6,
    expert_d_ff=1408,
    n_shared_experts=2,
    router="sigmoid",
    routed_scale=2.446,
    first_dense_layers=1,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=50_000.0,
    norm_eps=1e-5,
    citation="hf:moonshotai/Moonlight-16B-A3B",
)

SMOKE = ArchConfig(
    name="moonlight-smoke",
    arch_type="moe",
    n_layers=3,
    d_model=128,
    n_heads=4,
    n_kv_heads=4,
    d_ff=256,
    vocab=512,
    n_experts=8,
    moe_topk=2,
    expert_d_ff=64,
    n_shared_experts=1,
    router="sigmoid",
    routed_scale=2.446,
    first_dense_layers=1,
    kv_lora_rank=32,
    qk_nope_head_dim=32,
    qk_rope_head_dim=16,
    v_head_dim=32,
    rope_theta=50_000.0,
    norm_eps=1e-5,
    citation="reduced variant of hf:moonshotai/Moonlight-16B-A3B",
)
