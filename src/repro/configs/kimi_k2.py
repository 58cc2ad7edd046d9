"""kimi-k2-1t-a32b: Kimi-K2-Instruct as published
(https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/config.json),
the DeepSeek-V3 block.

61 layers, d_model 7168, vocab 163840, untied head, RMSNorm eps 1e-6.
Attention: multi-head latent attention, 64 heads, q_lora_rank 1536,
kv_lora_rank 512, qk head dim 128 (no rope) + 64 (rope, one head shared by
k), v head dim 128; YaRN rope (factor 32 over 4096, theta 50000, beta_fast
1, beta_slow 1, mscale 1, mscale_all_dim 1). Layer 0 is dense (SwiGLU
18432); layers 1-60 route each token to 8 of 384 experts (SwiGLU 2048) by
sigmoid scores chosen with the noaux_tc correction bias, weights normalised
and scaled by 2.827, plus one shared expert of width 2048. About 1.04 T
parameters, 32 B active.

The flagship PEFT showcase: with MetaTT the base is frozen bf16 (no grads /
optimizer state / master copy). ``num_kv_heads`` is the head count (MLA
gives every head its own keys and values).
"""
import dataclasses

import jax.numpy as jnp

from repro.config.base import ModelConfig, YaRN

CONFIG = ModelConfig(
    name="kimi-k2-1t-a32b",
    family="moe",
    num_layers=61,
    d_model=7168,
    num_heads=64,
    num_kv_heads=64,
    d_ff=2048,
    vocab_size=163840,
    block_pattern=(("mla", "moe"),),
    norm_eps=1e-6,
    rope_theta=50000.0,
    tie_embeddings=False,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_scaling=YaRN(factor=32.0, original_max_position=4096,
                      beta_fast=1.0, beta_slow=1.0, mscale=1.0,
                      mscale_all_dim=1.0),
    first_dense_layers=1,
    dense_d_ff=18432,
    num_experts=384,
    experts_per_token=8,
    num_shared_experts=1,
    moe_scoring="sigmoid",
    moe_routed_scale=2.827,
    moe_dispatch="dropless",
).validate()


def smoke_config(name: str = "") -> ModelConfig:
    """The same block at a tiny size: one dense layer and two expert
    layers, 8 experts of which 2 a token."""
    return dataclasses.replace(
        CONFIG, name=CONFIG.name + "-smoke", num_layers=3, d_model=64,
        num_heads=4, num_kv_heads=4, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, d_ff=32,
        dense_d_ff=96, vocab_size=128, num_experts=8, experts_per_token=2,
        param_dtype=jnp.float32, compute_dtype=jnp.float32).validate()
