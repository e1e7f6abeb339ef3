"""zamba2-7b — hybrid: Mamba2 layers with two shared attention blocks.

[arXiv:2411.15242; hf:Zyphra/Zamba2-7B-Instruct config.json]
81L d_model=3584 vocab=32000. Mamba2 mixers: 112 heads of 64, d_state 64,
2 groups of B/C, conv bias, an RMSNorm in front of each mixer. Before the
Mamba layers 6, 11, 17, ..., 77 a shared block runs on [x, embedding]
(7168 wide): 32-head MHA of head_dim 224 with rotary, then a gated GELU
MLP of 14336 with a rank-128 adapter per site; blocks A and B alternate
over the 13 sites, and a per-site linear feeds the result into the next
Mamba layer's input (not its residual). Tied embedding.
"""

from repro.configs.base import ModelConfig, SSMConfig, register

CONFIG = ModelConfig(
    arch_id="zamba2-7b",
    family="hybrid",
    n_layers=81,
    d_model=3584,
    n_heads=32,
    n_kv_heads=32,
    head_dim=224,
    d_ff=14336,
    vocab_size=32000,
    rope_theta=10000.0,
    tie_embeddings=True,
    mlp_kind="geglu",
    ssm=SSMConfig(d_state=64, expand=2, headdim=64, chunk=256, d_conv=4,
                  ngroups=2, conv_bias=True, pre_norm=True, dt_min=0.001),
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    num_mem_blocks=2,
    adapter_rank=128,
)

# Zamba2-shaped at CPU size: 2 groups, 2 memory blocks, an irregular
# pattern of 4 sites in 11 layers, adapters, tied embedding
SMOKE = CONFIG.replace(
    n_layers=11, d_model=64, n_heads=4, n_kv_heads=4, head_dim=32,
    d_ff=128, vocab_size=512, remat=False,
    ssm=SSMConfig(d_state=16, expand=2, headdim=16, chunk=16, d_conv=4,
                  ngroups=2, conv_bias=True, pre_norm=True, dt_min=0.001),
    hybrid_layer_ids=(3, 5, 8, 10),
    adapter_rank=8,
)

register(CONFIG, SMOKE)
