"""moonlight-16b-a3b — DeepSeek-V3-style MoE with multi-head latent attention.

[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3]
27 layers at d_model 2048: one dense layer (intermediate_size 11264), then
26 MoE layers of 64 routed experts of width 1408 (6 per token, sigmoid
scores, noaux_tc selection with one group, top-k gates renormalised and
scaled by 2.446) plus 2 shared experts. MLA: 16 heads, kv_lora_rank 512,
no q_lora, qk_nope 128 + qk_rope 64, v 128, rope_theta 50000. Untied
embeddings, vocab 163840, rms_norm_eps 1e-5.

The load-balance settings are not in the config: the bias step 0.001 and
the sequence-wise balance coefficient 1e-4 are DeepSeek-V3's
(arXiv:2412.19437 section 2.1.2). The latent RMSNorm takes the modeling
file's default eps 1e-6.
"""
from repro.configs.base import ArchConfig, register

CONFIG = register(ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,
    dense_d_ff=11_264,
    vocab=163_840,
    pattern_groups=((("mla",), 1), (("mla_moe",), 26)),
    tie_embeddings=False,
    rope_theta=50_000.0,
    norm_eps=1e-5,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    n_experts=64,
    top_k=6,
    n_shared_experts=2,
    routed_scaling=2.446,
    bias_update_rate=0.001,
    aux_coef=1e-4,
    source="hf:moonshotai/Moonlight-16B-A3B",
))
