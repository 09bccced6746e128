"""DeepSeek-V2-Lite [arXiv:2405.04434; huggingface.co/deepseek-ai/
DeepSeek-V2-Lite config.json]: 27 layers at hidden 2,048, the first dense
(SwiGLU 10,944), 26 DeepSeekMoE layers (64 routed SwiGLU experts of width
1,408, greedy top-6 over softmax scores without renormalization, scaling
factor 1, plus 2 shared experts); MLA with no query compression (16 heads,
128 nope + 64 rope query/key dims, kv_lora_rank 512, v 128) and YaRN RoPE
(factor 40 over 4,096 original positions); vocabulary 102,400, untied."""
from repro.configs.base import ModelConfig, YarnScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,
    vocab_size=102400,
    pattern=("mla_moe",),
    first_k_dense=1,
    n_experts=64,
    top_k=6,
    moe_d_ff=1408,
    n_shared_experts=2,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10000.0,
    rope_scaling=YarnScaling(
        factor=40.0, original_max_position_embeddings=4096, beta_fast=32.0,
        beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707,
    ),
    norm_eps=1e-6,
    source="arXiv:2405.04434",
    long_context_ok=False,  # no MLA decode cache in this repo
)
