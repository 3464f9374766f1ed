"""Mellum2-12B-A2.5B (hf:JetBrains/Mellum2-12B-A2.5B-Instruct, config.json),
and one chip's share of it under expert parallelism 8.

Every layer is sparse: 64 SwiGLU experts of width 896, 8 per token, gates
renormalised over the 8 chosen.  Layers repeat sliding, sliding, sliding,
full: the sliding layers see 1,024 positions with default rotary positions
at theta 500,000; the full layers use YaRN (factor 16 over 8,192 original
positions, beta_fast 32, beta_slow 1, attention factor 1.2773).  The dense
``intermediate_size`` (7,168) belongs to no layer.  The MTP head is left
out: the config has no key for it.
"""
from repro.configs.base import (ArchConfig, LayerKind, MoEConfig, Rope,
                                expert_share, register)

SLIDING = LayerKind(window=1024, rope=Rope(theta=500_000.0))
FULL = LayerKind(window=0, rope=Rope(
    theta=500_000.0, factor=16.0, original_max_positions=8192,
    beta_fast=32.0, beta_slow=1.0, attention_factor=1.2772588722239782))

CONFIG = register(ArchConfig(
    name="mellum2-12b-a2.5b", family="moe",
    n_layers=28, d_model=2304, n_heads=32, n_kv_heads=4, head_dim=128,
    d_ff=7168, vocab_size=98304, rope_theta=500_000.0, rms_eps=1e-6,
    moe=MoEConfig(n_experts=64, top_k=8, d_ff_expert=896),
    layer_types=(SLIDING, SLIDING, SLIDING, FULL) * 7,
    source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct; 64 experts top-8, "
           "3 sliding (1,024) : 1 full (YaRN) attention",
))

# one of 8 chips sharing each layer: 8 of the 64 experts, 12,288 of the
# 98,304 vocabulary rows; run with --layers 4 (one period)
SHARE = register(expert_share(CONFIG, chips=8))
