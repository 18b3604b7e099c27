"""granite-4.0-h-micro [interleaved] — Mamba2 and NoPE GQA layers, each with
its own weights and a SwiGLU MLP, muP multipliers, tied embeddings.

[hf:ibm-granite/granite-4.0-h-micro config.json, model_type
granitemoehybrid with no experts]
"""
from repro.configs.base import ModelConfig

# attention at layers 5, 15, 25 and 35: a period of 10
_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="interleaved",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    d_ff=8192,                  # shared_intermediate_size
    vocab_size=100352,
    mlp="swiglu",
    rope_theta=0.0,             # position_embedding_type "nope"
    attn_scale=1 / 64,          # attention_multiplier
    ssm_state=128,
    ssm_head_dim=64,            # 64 heads of 64 at expand 2
    ssm_expand=2,
    ssm_chunk=256,
    layer_pattern=_PERIOD,
    tie_embeddings=True,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    norm_eps=1e-5,
    source="hf:ibm-granite/granite-4.0-h-micro",
)
