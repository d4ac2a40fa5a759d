"""Moonlight-16B-A3B — the DeepSeek-V3 block: latent attention, two
shared experts and sigmoid top-6 of 64 routed experts.

[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3]: 27
layers, hidden 2048, 16 heads, q one projection (q_lora_rank null) to
16 x (128 nope + 64 rope), kv_lora_rank 512 with an RMSNorm on the
latent, v_head_dim 128; layer 0 a dense SwiGLU of 11264
(first_k_dense_replace 1), layers 1-26 MoE: 64 routed experts of 1408,
top-6 by sigmoid(logits) + e_score_correction_bias (noaux_tc, n_group =
topk_group = 1), gates the unbiased scores normalized over the six and
scaled by 2.446, plus 2 shared experts; vocab 163840, untied head,
rope_theta 50000 on interleaved pairs, rms_norm_eps 1e-5. Every expert
is held here; a deployment's share of them sets ``experts_held``.
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, register

MOONLIGHT_16B_A3B = register(ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    source="hf:moonshotai/Moonlight-16B-A3B",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=11_264,
    vocab_size=163_840,
    head_dim=192,
    rope_theta=50_000.0,
    rope="rope_interleaved",
    norm_eps=1e-5,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=64, top_k=6, d_ff=1408, score="sigmoid",
                  expert_bias=True, norm_topk=True, routed_scale=2.446,
                  n_shared=2, first_dense=1, dropless=True),
))
