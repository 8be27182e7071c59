"""Granite-4.0-H-Small (32B, 9B active) — 40L d_model=4096, a hybrid of
Mamba2 (128 heads x 64, state 128, conv bias, gated RMSNorm) and NoPE GQA
attention (32H, kv=8, scores x 1/128) at index 5 of each 10-layer period;
every layer's FFN is 72 experts of width 768, top-10, plus a shared expert
of 1536; embeddings x 12, residual branches x 0.22, logits / 16; vocab
100352, tied.  [hf:ibm-granite/granite-4.0-h-small; head_dim 128 =
hidden / heads, the expert width read from intermediate_size]"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=768,                # one expert's width
    vocab_size=100352,
    num_experts=72,
    num_experts_per_tok=10,
    moe_dispatch="ragged",
    shared_expert_ff=1536,
    moe_aux_coef=0.0,        # output_router_logits is off by default
    position_embedding="nope",
    attn_scale=0.0078125,
    attn_every=10,
    attn_offset=5,
    ssm_state=128,
    d_inner=8192,
    ssm_head_dim=64,
    ssm_chunk=256,
    conv_width=4,
    mamba_conv_bias=True,
    mamba_gated_norm=True,
    tie_embeddings=True,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    norm_eps=1e-5,
    source="hf:ibm-granite/granite-4.0-h-small",
)
