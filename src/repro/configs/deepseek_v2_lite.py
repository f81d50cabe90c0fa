"""DeepSeek-V2-Lite 15.7B-A2.4B [hf:deepseek-ai/DeepSeek-V2-Lite, arXiv:2405.04434].

27 layers, d_model=2048, vocab=102400, untied head.  Multi-head latent
attention: 16 heads, no query compression, keys and values expanded per
head from a 512-wide normed latent (q/k heads 128 + 64 rope, v heads 128;
the 64-wide rope key is shared by all heads), YaRN rope (factor 40 over
4,096 positions).  Layer 0 is dense (d_ff=10944); the other 26 hold 64
routed experts of width 1408, 6 per token (softmax over all 64, greedy
top-6, not renormalized), beside 2 shared experts (one SwiGLU of width
2816), with the sequence-wise balance loss.
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    citation="hf:deepseek-ai/DeepSeek-V2-Lite",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10944,
    vocab_size=102_400,
    activation="swiglu",
    tie_embeddings=False,
    norm_eps=1e-6,
    rope_theta=10_000.0,
    attn_pattern=("global",),
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    yarn_factor=40.0,
    yarn_original_max_position=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    num_experts=64,
    experts_per_token=6,
    moe_d_ff=1408,
    moe_shared_expert=True,
    shared_expert_d_ff=2816,  # n_shared_experts 2 x 1408
    first_dense_layers=1,
    router_aux_coef=0.001,  # aux_loss_alpha
    moe_aux_per_sequence=True,
    moe_renormalize=False,
    moe_capacity_factor=0.0,  # dropless
)
