"""deepseek-v3-671b — MLA with YaRN rope + MoE (1 shared + 256 routed, top-8,
sigmoid scores, 8 groups of which 4 are kept, scaled by 2.5) + MTP.
First 3 layers dense (d_ff=18432); MoE expert hidden = 2048.
[arXiv:2412.19437; hf:deepseek-ai/DeepSeek-V3 config.json]"""
from repro.configs.base import MLAConfig, ModelConfig, YaRN

FULL = ModelConfig(
    name="deepseek-v3-671b", family="moe",
    n_layers=61, d_model=7168, n_heads=128, n_kv_heads=128,
    d_ff=18432,                  # dense layers
    vocab=129280,
    n_experts=256, experts_per_token=8, moe_d_ff=2048,
    n_shared_experts=1, first_dense_layers=3,
    router_score="sigmoid", n_groups=8, topk_groups=4, routed_scale=2.5,
    mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512,
                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
    mtp_depth=1, rope_theta=1e4,
    rope_scaling=YaRN(factor=40.0, original_max_position=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                      mscale_all_dim=1.0),
)


def smoke() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v3-671b-smoke", family="moe",
        n_layers=4, d_model=64, n_heads=4, n_kv_heads=4,
        d_ff=192, vocab=512,
        n_experts=8, experts_per_token=2, moe_d_ff=64,
        n_shared_experts=1, first_dense_layers=2,
        router_score="sigmoid", n_groups=4, topk_groups=2, routed_scale=2.5,
        mla=MLAConfig(q_lora_rank=32, kv_lora_rank=16,
                      qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16),
        mtp_depth=1, rope_scaling=FULL.rope_scaling,
    )
