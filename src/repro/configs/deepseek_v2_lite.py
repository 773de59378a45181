"""deepseek-v2-lite [moe] — latent attention without query compression,
YaRN rotary scaling, one dense layer then 64 routed experts (top-6,
unnormalised softmax gates) beside 2 shared ones.
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite]
"""

from repro.configs.base import ModelConfig, YarnScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,  # the dense layer's MLP
    vocab_size=102400,
    head_dim=192,  # nope + rope
    block_pattern=("moe",),
    first_k_dense=1,
    attn_kind="mla",
    q_lora_rank=0,  # no query compression: one w_q
    kv_lora_rank=512,
    rope_head_dim=64,
    nope_head_dim=128,
    v_head_dim=128,
    rope_theta=10000.0,
    yarn=YarnScaling(factor=40.0, original_max_position_embeddings=4096, beta_fast=32.0,
                     beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    n_experts=64,
    n_shared_experts=2,
    moe_top_k=6,
    moe_d_ff=1408,
    norm_topk_prob=False,
    norm_eps=1e-6,
    family="moe",
    source="arXiv:2405.04434; hf",
)


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-smoke",
        n_layers=3,
        d_model=64,
        n_heads=4,
        n_kv_heads=4,
        d_ff=160,
        vocab_size=256,
        head_dim=24,
        block_pattern=("moe",),
        first_k_dense=1,
        attn_kind="mla",
        q_lora_rank=0,
        kv_lora_rank=32,
        rope_head_dim=8,
        nope_head_dim=16,
        v_head_dim=16,
        yarn=YarnScaling(factor=40.0, original_max_position_embeddings=64, beta_fast=32.0,
                         beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
        n_experts=16,
        n_shared_experts=2,
        moe_top_k=6,
        moe_d_ff=32,
        norm_topk_prob=False,
        norm_eps=1e-6,
        family="moe",
    )
