"""zamba2-7b — hybrid: 81 Mamba2 layers (2 groups) and two SHARED
transformer blocks used in turn before 13 of them, over concat(hidden,
embeddings), with a LoRA adapter and a linear of each invocation's own.
[hf:Zyphra/Zamba2-7B-Instruct; hf]
81L d_model=3584 32H (kv=32) head_dim=224 d_ff=14336 vocab=32000,
ssm_state=64, 112 mamba heads of 64."""

import math

from .base import ModelConfig

CONFIG = ModelConfig(
    name="zamba2-7b",
    family="hybrid",
    num_layers=81,
    d_model=3584,
    num_heads=32,
    num_kv_heads=32,
    head_dim=224,              # attention_head_dim: 2 * d_model / heads
    attn_scale_mult=math.sqrt(2.0),   # softmax scale (head_dim / 2) ** -0.5
    d_ff=14336,
    mlp_act="gelu",
    vocab_size=32000,
    rope_theta=10_000.0,
    norm_eps=1e-5,
    tie_embeddings=True,
    ssm_state=64,
    ssm_head_dim=64,
    ssm_expand=2,
    ssm_conv_width=4,
    ssm_groups=2,
    chunk_size=256,
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    shared_blocks=2,
    shared_concat=True,
    adapter_rank=128,
    hybrid_linear=True,
)
