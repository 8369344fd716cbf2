"""IBM Granite 4.0-H Micro (3B) — a Mamba-2 stack with NoPE GQA attention at
layers 5, 15, 25 and 35, a SwiGLU MLP after every mixer, and muP multipliers
on the embedding, the residual branches, the attention scale and the logits.
[hf:ibm-granite/granite-4.0-h-micro config.json, model_type granitemoehybrid]"""
from repro.configs.base import ArchConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ArchConfig(
    name="granite-4.0-h-micro", family="hybrid",
    num_layers=40, d_model=2048, num_heads=32, num_kv_heads=8,
    d_ff=8192, vocab_size=100352, head_dim=64,
    tie_embeddings=True,
    ssm_state=128, ssm_head_dim=64, ssm_expand=2, ssm_chunk=256,
    ssm_conv_width=4,
    layer_types=_PERIOD[:5] + (_PERIOD[5:] + _PERIOD[:5]) * 3 + _PERIOD[5:],
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.015625, logits_scaling=8.0, norm_eps=1e-5,
    position_embedding="nope",
    source="https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json",
)
