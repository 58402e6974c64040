"""Moonlight-16B-A3B — DeepSeek-V3-style MoE with multi-head latent attention.

[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3] 27L,
d_model=2048; MLA without q compression: 16 heads, kv latent 512, q·k over
128 + 64 rotary dims, values of 128, rope_theta 5e4; one leading dense layer
of MLP width 11264, then 26 MoE layers of 64 routed experts (width 1408,
top-6, sigmoid scores with a selection-only correction bias, gates
normalised and scaled by 2.446) and 2 shared experts; untied 163840 vocab.
"""
import jax.numpy as jnp

from repro.configs.base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=11264,
    vocab_size=163840,
    num_experts=64,
    experts_per_token=6,
    moe_d_ff=1408,
    moe_layer_period=1,
    n_shared_experts=2,
    first_k_dense_replace=1,
    scoring_func="sigmoid",
    topk_method="noaux_tc",
    norm_topk_prob=True,
    routed_scaling_factor=2.446,
    seq_aux=True,
    router_aux_weight=0.001,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=5e4,
    max_position_embeddings=8192,
    model_type="deepseek_v3",
    tie_embeddings=False,
    dtype=jnp.bfloat16,
    source="hf:moonshotai/Moonlight-16B-A3B",
))
