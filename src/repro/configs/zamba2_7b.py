"""zamba2-7b — Zamba2-7B-Instruct: Mamba2 layers with two shared transformer
blocks applied in turn at 13 hybrid layers [arXiv:2411.15242;
https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/config.json].

81L d_model=3584, mamba2 112 heads of 64, state 64, 2 groups, conv 4, chunk
256; shared blocks: attention over concat(hidden, embedding) (7168 wide), 32
heads of 224 (scores x (224/2)^-0.5, RoPE theta 1e4), gelu MLP 14336 with a
rank-128 adapter per application on gate_up, a 3584^2 linear per
application; vocab 32000.
"""
from repro.configs.base import ModelConfig, HYBRID

CONFIG = ModelConfig(
    name="zamba2-7b",
    family=HYBRID,
    num_layers=81,
    d_model=3584,
    num_heads=32,
    num_kv_heads=32,
    head_dim=224,
    d_ff=14_336,
    vocab_size=32_000,
    rope_theta=10_000.0,
    ssm_state=64,
    ssm_expand=2,
    ssm_head_dim=64,
    ssm_chunk=256,
    ssm_conv_width=4,
    ssm_ngroups=2,
    ssm_dt_min=0.001,
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    num_mem_blocks=2,
    adapter_rank=128,
    norm_eps=1e-5,
    tie_embeddings=True,
    source="Zyphra/Zamba2-7B-Instruct config.json [arXiv:2411.15242]",
)
