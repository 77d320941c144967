"""Granite-4.0-H-Micro [hf: ibm-granite/granite-4.0-h-micro] — dense hybrid:
36 Mamba-2 mixers and 4 NoPE GQA layers, a gated SiLU MLP after every
mixer, muP scalars and a tied head."""

from repro.configs.base import ArchConfig, SSMConfig, register

_ATTENTION = (5, 15, 25, 35)

GRANITE_4_H_MICRO = register(ArchConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    source="https://huggingface.co/ibm-granite/granite-4.0-h-micro",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab_size=100_352,
    attn_kind="gqa",
    rope_fraction=0.0,                  # position_embedding_type "nope"
    attention_multiplier=0.015625,
    ssm=SSMConfig(kind="mamba2", state_dim=128, head_dim=64, conv_width=4,
                  expand=2, chunk_size=256),
    layer_types=tuple("attention" if i in _ATTENTION else "mamba"
                      for i in range(40)),
    mlp_act="silu",
    mlp_gated=True,
    norm_eps=1e-5,
    tie_embeddings=True,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    subquadratic=True,        # mamba state + 4 global attention layers
))
