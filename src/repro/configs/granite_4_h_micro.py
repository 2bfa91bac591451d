"""granite-4.0-h-micro [granite_hybrid] — Mamba-2 layers with a GQA layer
without positional encoding at every tenth position, each followed by a
SwiGLU MLP (hf:ibm-granite/granite-4.0-h-micro, model_type
granitemoehybrid)."""
from repro.configs.base import ModelConfig, SSMSpec

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4-h-micro",
    family="granite_hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    d_ff=8192,  # shared_intermediate_size; num_local_experts 0
    vocab=100352,
    tie_embeddings=True,
    layer_types=_PERIOD * 4,  # attention at layers 5, 15, 25, 35
    ssm=SSMSpec(kind="mamba2", state_dim=128, head_dim=64, d_conv=4, expand=2, chunk=256),
    norm_eps=1e-5,
    attention_multiplier=0.015625,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
)
