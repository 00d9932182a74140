"""deepseek-v2-lite [moe] — latent attention, 64 experts top-6 + 2 shared.

[hf:deepseek-ai/DeepSeek-V2-Lite, config.json] 27L, d_model=2048, 16 heads,
multi-head latent attention without a query LoRA (kv_lora_rank=512,
qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128), YaRN rope
(theta 1e4, factor 40 over 4096 positions, beta 32/1, mscale 0.707 on
both sides), layer 0 a dense SwiGLU of 10944 (first_k_dense_replace=1),
layers 1-26 64 routed SwiGLU experts of 1408 with softmax scores, greedy
top-6, weights not renormalized, and 2 shared experts; RMSNorm eps 1e-6,
untied LM head, vocab 102400.

The program holds experts 0-15 of each MoE layer: chip 0's share when
experts are parallel over the four chips of a v5e 2x2 host (attention,
the dense layer and the shared experts data-parallel on every chip).
"""
from repro.config import (LayerSpec, MLAConfig, MoEConfig, ModelConfig,
                          YarnScaling, register_arch)


@register_arch("deepseek-v2-lite")
def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite",
        arch_type="moe",
        num_layers=27,
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        head_dim=192,
        d_ff=10944,
        vocab_size=102400,
        pattern=(LayerSpec("mla", "moe"),),
        first_dense_layers=1,
        mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128),
        moe=MoEConfig(
            num_experts=64,
            top_k=6,
            d_expert_ff=1408,
            num_shared_experts=2,
            d_shared_ff=1408,
            norm_topk_prob=False,
            experts_held=16,
        ),
        rope_theta=10_000.0,
        rope_scaling=YarnScaling(factor=40.0,
                                 original_max_position_embeddings=4096,
                                 beta_fast=32.0, beta_slow=1.0,
                                 mscale=0.707, mscale_all_dim=0.707),
        max_seq_len=163_840,
        source="hf:deepseek-ai/DeepSeek-V2-Lite",
        supports_long_context=False,
        notes="experts_held=16 of 64: one chip's share of a four-chip "
              "expert-parallel deployment. The 64 rope dims pair as "
              "rotate-half (i with i+32); the published code pairs "
              "interleaved dims, the same up to a permutation of the rope "
              "columns of wq and wkv_a.",
    )
