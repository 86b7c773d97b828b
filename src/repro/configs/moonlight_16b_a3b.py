"""moonlight-16b-a3b [moe] (hf:moonshotai/Moonlight-16B-A3B, model_type
deepseek_v3).

27 layers at d_model 2048.  Every layer has latent attention (MLA): 16
heads, keys and values compressed to a 512-wide latent (``kv_lora_rank``)
plus one 64-wide rotary key shared by all heads; q/k heads are 128 + 64
wide, v heads 128; queries are not compressed (``q_lora_rank`` null).
Layer 0 has a dense SwiGLU MLP of width 11264 (``first_k_dense_replace``
1); layers 1-26 have 64 routed experts of width 1408, top-6, and 2 shared
experts, routed by sigmoid scores with a selection-only correction bias
(``topk_method`` noaux_tc, one group), renormalised and scaled by 2.446.
rope_theta 50000, rms_norm_eps 1e-5, vocabulary 163840, untied head.
The balance-loss weight (``aux_loss_alpha`` 0.001) is DeepSeek-V2's; it
matters only to training.

``with_expert_share(r, 8)`` gives one chip's share of an 8-way expert-parallel
deployment: experts [8r, 8r + 8) of each MoE layer.
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,
    vocab_size=163840,
    rope_theta=50000.0,
    norm_eps=1e-5,
    n_experts=64,
    top_k=6,
    n_shared_experts=2,
    router="sigmoid",
    routed_scaling=2.446,
    router_aux_weight=0.001,
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    first_k_dense=1,
    dense_d_ff=11264,
    activation="silu",
)
