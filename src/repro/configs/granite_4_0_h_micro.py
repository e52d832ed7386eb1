"""Granite-4.0-H-Micro [hf:ibm-granite/granite-4.0-h-micro]: hybrid of
Mamba-2 and GQA attention mixers, each layer [mixer -> SwiGLU MLP]; 40
layers, attention at 5, 15, 25, 35 (a period of 9 Mamba-2 + 1 attention);
no positions (NoPE); Granite's embedding, attention, residual and logit
multipliers; tied vocabulary.
Source: https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json"""

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    arch_type="hybrid_mlp",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab_size=100352,
    use_rope=False,
    nope=True,
    layer_pattern=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
    ssm_state=128,
    ssm_heads=64,
    ssm_expand=2,
    conv_dim=4,
    ssm_chunk=256,
    embedding_multiplier=12.0,
    attention_multiplier=0.015625,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    mlp_type="swiglu",
    norm_type="rmsnorm",
    norm_eps=1e-5,
    tie_embeddings=True,
    max_seq_len=131072,
    param_dtype="bfloat16",
    compute_dtype="bfloat16",
    source="https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json",
)
