"""DeepSeek-V2-Lite — MLA without q_lora, YaRN, one leading dense layer, then
64 routed experts (top-6, not renormalised) and 2 shared experts per layer
[hf:deepseek-ai/DeepSeek-V2-Lite].

``config()`` is one chip's share of a four-way expert-parallel deployment:
every width, the router's 64 outputs and its top-6 as published, and the
routed experts 0-15 of each expert layer held here.
"""
from ..models.config import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite", family="moe",
        n_layers=27, d_model=2048, d_ff=1408, vocab_size=102400,
        n_heads=16, n_kv_heads=16, head_dim=128,
        use_mla=True, q_lora_rank=0, kv_lora_rank=512,
        qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
        rope_theta=10_000.0, norm_eps=1e-6,
        yarn_factor=40.0, yarn_original_len=4096, yarn_beta_fast=32.0,
        yarn_beta_slow=1.0, yarn_mscale=0.707, yarn_mscale_all_dim=0.707,
        n_experts=64, experts_per_token=6, moe_norm_topk=False,
        n_shared_experts=2, n_held_experts=16, first_held_expert=0,
        n_dense_layers=1, dense_d_ff=10944,
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-smoke", family="moe",
        n_layers=4, d_model=64, d_ff=32, vocab_size=512,
        n_heads=4, n_kv_heads=4, head_dim=16,
        use_mla=True, q_lora_rank=0, kv_lora_rank=16,
        qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
        norm_eps=1e-6,
        yarn_factor=4.0, yarn_original_len=8, yarn_beta_fast=32.0,
        yarn_beta_slow=1.0, yarn_mscale=0.707, yarn_mscale_all_dim=0.707,
        n_experts=8, experts_per_token=3, moe_norm_topk=False,
        n_shared_experts=2, n_held_experts=4, first_held_expert=0,
        n_dense_layers=1, dense_d_ff=96, remat=False,
    )
