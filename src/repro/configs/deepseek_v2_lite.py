"""DeepSeek-V2-Lite [hf:deepseek-ai/DeepSeek-V2-Lite; arXiv:2405.04434]:
latent attention with no query low-rank (kv_lora_rank 512, rotary dims 64
under YaRN), one leading dense SwiGLU layer of width 10944, then 26 MoE
layers of 64 routed experts (width 1408, softmax top-6, gates not
renormalised) beside 2 shared experts (one SwiGLU of width 2816).
Inference routes every token: no capacity, nothing dropped."""
from .base import MLAConfig, ModelConfig, MoEConfig, RopeScaling, register


@register("deepseek-v2-lite")
def deepseek_v2_lite() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite", family="moe",
        num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
        head_dim=128, d_ff=10944, vocab_size=102400,
        rope_theta=1e4, tie_embeddings=False, first_k_dense=1,
        rope_scaling=RopeScaling(factor=40.0,
                                 original_max_position_embeddings=4096,
                                 beta_fast=32.0, beta_slow=1.0,
                                 mscale=0.707, mscale_all_dim=0.707),
        mla=MLAConfig(q_lora_rank=0, kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128),
        moe=MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408,
                      capacity_factor=None, num_shared=2,
                      norm_topk_prob=False, routed_scaling_factor=1.0),
    )
