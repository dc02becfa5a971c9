"""DeepSeek-V2-Lite [hf:deepseek-ai/DeepSeek-V2-Lite config.json] — MLA + MoE.

Multi-head latent attention without query compression (a 512-wide latent
and a shared 64-wide RoPE key, YaRN-scaled by 40 from 4096 positions), one
leading dense SwiGLU layer, then 26 layers of 64 routed experts (top-6,
softmax scores, not renormalised) beside 2 ungated shared experts.  The
registry entry holds every expert; a serving chip's share of them is set
with `num_experts_held`.
"""

from repro.configs.base import ArchConfig, register


@register
def deepseek_v2_lite() -> ArchConfig:
    return ArchConfig(
        arch_id="deepseek-v2-lite",
        family="moe",
        source="hf:deepseek-ai/DeepSeek-V2-Lite",
        num_layers=27,
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        head_dim=128,
        d_ff=10944,  # the leading dense layer's SwiGLU
        vocab_size=102400,
        rope_theta=10_000.0,
        norm_eps=1e-6,
        num_experts=64,
        num_experts_per_tok=6,
        num_shared_experts=2,
        moe_d_ff=1408,
        first_k_dense=1,
        norm_topk_prob=False,  # routed_scaling_factor 1: the weights as scored
        shared_expert_gate=False,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_scaling_factor=40.0,
        rope_original_max_position=4096,
        rope_beta_fast=32.0,
        rope_beta_slow=1.0,
        rope_mscale=0.707,
        rope_mscale_all_dim=0.707,
    )
