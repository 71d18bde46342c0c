"""DeepSeek-V2-Lite [hf:deepseek-ai/DeepSeek-V2-Lite, config.json;
arXiv:2405.04434]: 27 layers, the first with a dense MLP (10944 wide),
the rest 64 routed experts (top-6 of a softmax router, gates not
renormalised) plus 2 shared experts of width 1408; multi-head latent
attention (16 heads, kv latent 512, qk 128 + 64 rope, v 128, no q
compression) with YaRN rope scaling (factor 40 over 4096 positions).

``deepseek-v2-lite`` holds every expert.  ``deepseek-v2-lite-tiny`` is
the same architecture at CPU-test size: 1 dense + 2 MoE layers, d 64,
2 of 8 routed experts held (rank 0 of four), top-3, one shared expert.
"""
from repro.configs.base import ModelConfig, Yarn, register

YARN = Yarn(factor=40.0, original_max_position=4096, beta_fast=32.0,
            beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)

CONFIG = register(ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10944,                 # the leading dense layer's MLP
    vocab_size=102400,
    rope_theta=10000.0,
    norm_eps=1e-6,
    num_experts=64,
    num_experts_per_tok=6,
    moe_d_ff=1408,
    shared_expert_d_ff=2 * 1408,
    router_aux_coef=0.001,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    seq_aux=True,
    first_dense_layers=1,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_yarn=YARN,
))

TINY = register(CONFIG.replace(
    name="deepseek-v2-lite-tiny",
    num_layers=3, d_model=64, num_heads=4, num_kv_heads=4, d_ff=128,
    vocab_size=256, num_experts=2, router_experts=8, num_experts_per_tok=3,
    moe_d_ff=32, shared_expert_d_ff=32, kv_lora_rank=64,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16))
