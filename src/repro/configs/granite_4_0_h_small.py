"""granite-4.0-h-small — hybrid Mamba2 + NoPE GQA decoder with a 72-expert
MoE (and a shared expert) in every layer.
[hf:ibm-granite/granite-4.0-h-small; model_type granitemoehybrid, 32B-A9B]
40L d_model=4096 32H (GQA kv=8, head_dim 128) vocab=100352; attention at
i % 10 == 5, Mamba2 elsewhere (128 heads x 64, d_state 128); MoE 72e top-10,
expert width 768, shared SwiGLU expert of width 1536
"""
from repro.configs.base import ModelConfig, MoEConfig, SSMConfig, register_arch

CONFIG = register_arch(ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=1536,               # the shared expert
    vocab_size=100352,
    tie_embeddings=True,
    rope=False,              # position_embedding_type "nope"
    norm_eps=1e-5,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    attn_scale=0.0078125,
    attn_every=10,
    attn_offset=5,
    moe=MoEConfig(num_experts=72, top_k=10, d_ff=768, dense_residual=True),
    ssm=SSMConfig(d_state=128, expand=2, head_dim=64, conv_kernel=4,
                  chunk=256, n_groups=1),
    optimizer="adafactor",
    notes="attn at i%10==5, mamba otherwise; MoE + shared expert in every "
          "layer; Granite multipliers; no rotary embedding.",
))
