"""LiquidAI LFM2-8B-A1B [moe] — 24 layers: 18 gated short convolutions
(3 taps) and 6 GQA attention layers (32 query / 8 kv heads, per-head q/k
RMSNorm); 2 leading dense SwiGLU layers, then 32-expert top-4 MoE layers
routed by sigmoid scores with a per-expert selection bias
[hf:LiquidAI/LFM2-8B-A1B]."""
from .base import ModelConfig, MoEConfig, register

LAYER_TYPES = (
    "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "conv", "full_attention", "conv",
    "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "full_attention", "conv", "conv")

register(ModelConfig(
    name="lfm2-8b-a1b", family="moe",
    n_layers=24, d_model=2048, n_heads=32, n_kv_heads=8,
    d_ff=7168, vocab=65536, tied_embeddings=True, rope_theta=1e6,
    norm_eps=1e-5, act="silu", layer_types=LAYER_TYPES, conv_kernel=3,
    n_dense_layers=2, qk_norm=True, param_dtype="bfloat16",
    moe=MoEConfig(num_experts=32, top_k=4, d_ff=1792, router="sigmoid",
                  expert_bias=True),
))
