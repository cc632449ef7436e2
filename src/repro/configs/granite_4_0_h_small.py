"""granite-4.0-h-small [hybrid] — Mamba-2 and NoPE GQA layers 9:1, each followed
by a 72-expert top-10 MoE with a shared expert; Granite's four scalar
multipliers [hf:ibm-granite/granite-4.0-h-small config.json].

40 layers with attention at 5, 15, 25 and 35: one period of 10 is five Mamba-2
mixers, one attention layer, four Mamba-2 mixers. Mamba-2: 128 heads of 64,
d_state 128, one group, conv 4 with bias. Routing: top-10 of 72 with the
gates normalised over the 10 chosen, dropless.
"""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=768,               # the width of one routed expert
    vocab_size=100_352,
    layer_pattern=("mamba",) * 5 + ("attn",) + ("mamba",) * 4,
    mlp_kind="swiglu",
    norm_eps=1e-5,
    use_rope=False,
    tie_embeddings=True,
    embedding_multiplier=12.0,
    attention_multiplier=1.0 / 128,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    num_experts=72,
    experts_per_tok=10,
    moe_every=1,
    moe_dropless=True,
    shared_d_ff=1536,
    ssm_state=128,
    ssm_expand=2,
    ssm_headdim=64,         # d_inner = 8192 -> 128 SSD heads
    ssm_conv=4,
    sharding_preset="tp",
)
