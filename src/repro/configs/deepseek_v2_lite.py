"""DeepSeek-V2-Lite (15.7B total, 2.4B active) [arXiv:2405.04434].

Published config (huggingface.co/deepseek-ai/DeepSeek-V2-Lite, config.json):
27 layers, the first dense (``intermediate_size`` 10944); MLA with 16 heads,
no ``q_lora_rank`` (direct query projection), ``kv_lora_rank`` 512, qk
nope/rope head dims 128/64, ``v_head_dim`` 128; YaRN rope (factor 40,
``beta_fast`` 32, ``beta_slow`` 1, ``mscale`` = ``mscale_all_dim`` = 0.707,
original length 4096); 64 routed experts of width 1408, 2 shared, top-6,
softmax gating with ``norm_topk_prob`` false; ``rms_norm_eps`` 1e-6;
vocabulary 102,400. The routed experts use the dropless grouped path.
"""
from repro.configs.base import ModelConfig, YarnScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,           # MLA: all heads decompress from the latent
    head_dim=128,              # qk nope head dim
    v_head_dim=128,
    d_ff=10944,                # the first (dense) layer's MLP
    moe_d_ff=1408,             # per-expert hidden
    vocab_size=102400,
    attention="mla",
    kv_lora_rank=512,
    q_lora_rank=0,
    rope_head_dim=64,
    rope_theta=10000.0,
    rope_scaling=YarnScaling(factor=40.0, original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    num_experts=64,
    num_shared_experts=2,
    experts_per_token=6,
    norm_topk_prob=False,
    moe_impl="ragged",
    first_dense_layers=1,
    norm="rmsnorm",
    rms_norm_eps=1e-6,
    act="swiglu",
    citation="arXiv:2405.04434 (DeepSeek-V2-Lite)",
)
