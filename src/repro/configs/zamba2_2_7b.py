"""zamba2-2.7b — hybrid: the Zamba2 block of ``zamba2-7b`` at 2.7B-like
widths, not the published Zamba2-2.7B.

[arXiv:2411.15242; widths after hf:Zyphra/Zamba2-2.7B]
54L d_model=2560 vocab=32000, Mamba2 with d_state 64 and one B/C group.
One shared attention+MLP block (32 heads of 80 over [x, embedding]) runs
before every sixth Mamba layer, from layer 5 on (9 sites), with no
adapters. The published model has two memory blocks, adapters and another
pattern; this config keeps one block and the even pattern.
"""

from repro.configs.base import ModelConfig, SSMConfig, register

CONFIG = ModelConfig(
    arch_id="zamba2-2.7b",
    family="hybrid",
    n_layers=54,
    d_model=2560,
    n_heads=32,
    n_kv_heads=32,
    d_ff=10240,
    vocab_size=32000,
    head_dim=80,
    microbatches=2,
    mlp_kind="geglu",
    ssm=SSMConfig(d_state=64, expand=2, headdim=64, chunk=256,
                  conv_bias=True, pre_norm=True),
    hybrid_layer_ids=tuple(range(5, 54, 6)),
)

SMOKE = CONFIG.replace(
    n_layers=4, d_model=128, n_heads=4, n_kv_heads=4, head_dim=32,
    d_ff=256, vocab_size=512, remat=False, microbatches=1,
    ssm=SSMConfig(d_state=16, expand=2, headdim=32, chunk=32,
                  conv_bias=True, pre_norm=True),
    hybrid_layer_ids=(1, 3),
)

register(CONFIG, SMOKE)
