"""granite-4.0-h-micro — Mamba-2 with one NoPE GQA layer in ten, a SwiGLU
MLP after every mixer, muP multipliers, tied 100,352-row head.
[hf:ibm-granite/granite-4.0-h-micro config.json]"""
from .base import ArchConfig

CONFIG = ArchConfig(
    name="granite-4.0-h-micro", family="hybrid",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8,
    d_ff=8192, vocab_size=100352, head_dim=64,
    rope=False, attn_scale=0.015625,
    embed_multiplier=12.0, residual_multiplier=0.22, logits_divisor=8.0,
    block_pattern=("mamba2",) * 5 + ("attn",) + ("mamba2",) * 4,
    ssm_state=128, ssm_head_dim=64, ssm_expand=2, conv_width=4,
    mamba_ffn=True,
    tie_embeddings=True, act="silu", ffn_gated=True,
    param_dtype="bfloat16",
    long_context_ok=False,         # four full-attention layers: KV grows
    source="hf:ibm-granite/granite-4.0-h-micro",
)
