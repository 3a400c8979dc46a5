"""deepseek-v2-lite-16b: MLA + fine-grained MoE [arXiv:2405.04434].

From the published config.json
(https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json):
27 layers, d_model 2048, 16 heads, vocab 102400, untied embeddings,
rms_norm_eps 1e-6, bfloat16.  MLA with no q compression (q_lora_rank
null), kv_lora_rank 512, qk_nope 128 + qk_rope 64, v 128.  YaRN rope
(theta 1e4, factor 40 over 4096 original positions, beta_fast 32,
beta_slow 1, mscale = mscale_all_dim = 0.707).  Layer 0 dense (SwiGLU
10944); layers 1-26 MoE: 64 routed experts of width 1408 and 2 shared
(one SwiGLU of 2816), softmax scores, greedy top-6, no renormalisation
(norm_topk_prob false, routed_scaling_factor 1), no token dropped, and a
per-sequence balance loss (seq_aux true).

Assumed: the balance-loss coefficient 0.001, the expert-level factor the
DeepSeek-V2 paper gives for V2-Lite (the architecture values above do not
carry it).
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=1408,
    vocab_size=102400,
    rope_scaling=YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                            mscale_all_dim=0.707),
    norm_eps=1e-6,
    attention="mla",
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=64, num_shared_experts=2, top_k=6,
                  expert_d_ff=1408, router_aux_coef=0.001,
                  balance="sequence", norm_topk=False, dispatch="dropless",
                  first_dense_layers=1, first_dense_d_ff=10944),
    source="arXiv:2405.04434 (DeepSeek-V2-Lite: 27L d2048, MLA kv_lora 512, "
           "YaRN x40, 2 shared + 64 routed top-6, dropless)",
)
