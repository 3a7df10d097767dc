"""zamba2-2.7b — Zamba2-2.7B: the Zamba2 block (see ``zamba2_7b``) at
d_model 2560, 54 layers [arXiv:2411.15242].

Stated by the paper: 54 layers, d_model 2560, two shared blocks, vocab 32000.
Assumed, as no config.json of this model is held here: the hybrid layer ids
(the layer pattern transformers' ``Zamba2Config`` builds by default for 54
layers), 32 heads of 2 * 2560 / 32 = 160, d_ff 4 * 2560, one B/C group,
state 64, head_dim 64, chunk 256, adapter rank 128 on the MLP, gelu, RoPE
theta 1e4 and the (head_dim / 2) ** -0.5 score scale of Zamba2-7B.
"""
from repro.configs.base import ModelConfig, HYBRID

CONFIG = ModelConfig(
    name="zamba2-2.7b",
    family=HYBRID,
    num_layers=54,
    d_model=2560,
    num_heads=32,
    num_kv_heads=32,
    head_dim=160,
    d_ff=10_240,
    vocab_size=32_000,
    rope_theta=10_000.0,
    ssm_state=64,
    ssm_expand=2,
    ssm_head_dim=64,
    ssm_chunk=256,
    ssm_ngroups=1,
    ssm_dt_min=0.001,
    hybrid_layer_ids=(6, 12, 18, 24, 30, 36, 42, 47, 51),
    num_mem_blocks=2,
    adapter_rank=128,
    tie_embeddings=True,
    source="Zamba2 [arXiv:2411.15242]; widths not stated there assumed",
)
