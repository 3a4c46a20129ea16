"""Granite-4.0-H-Micro: 40 layers, 36 Mamba-2 mixers and 4 NoPE GQA
attention layers (index 5 of every period of 10), each followed by a
SwiGLU MLP; embedding x12, residual branches x0.22, logits / 8, tied
embeddings.  [https://huggingface.co/ibm-granite/granite-4.0-h-micro,
config.json]"""
from ..models.config import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    arch_type="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    d_ff=8192,
    vocab_size=100352,
    norm="rmsnorm",
    mlp="swiglu",
    layer_pattern="MMMMMAMMMM",
    mamba_heads=64,
    mamba_head_dim=64,
    ssm_state=128,
    mamba_conv=4,
    mamba_chunk=256,
    position_embedding="nope",
    attention_multiplier=0.015625,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    tie_embeddings=True,
    source="https://huggingface.co/ibm-granite/granite-4.0-h-micro",
)
