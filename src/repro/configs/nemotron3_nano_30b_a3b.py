"""nemotron3-nano-30b-a3b — 52L d2688, pattern MEMEM*E...: 23 Mamba-2
(64 heads of 64, state 128, 8 groups, conv 4 with bias, chunk 128), 23
MoE (128 experts of 1856, relu², top-6, sigmoid router with a selection
bias, renormalised, x2.5, the bias stepped against each expert's load
after every training step; one shared expert of 3712), 6 GQA attention
(32/2 heads of 128, no RoPE); v131072 untied; RMSNorm eps 1e-5.
31.58 B parameters.
[hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 config.json]

SMOKE keeps the first seven layers' pattern (MEMEM*E), several B/C groups,
and fewer experts held than exist."""

import dataclasses

from ..models.config import ModelConfig

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

CONFIG = ModelConfig(
    name="nemotron3-nano-30b-a3b", family="nemotron_h", n_layers=52,
    d_model=2688, n_heads=32, kv_heads=2, head_dim_override=128,
    d_ff=1856, vocab=131072, n_experts=128, top_k=6, router="sigmoid",
    routed_scale=2.5, shared_ff=3712, ffn_act="relu2",
    ssm_state=128, ssm_headdim=64, ssm_n_heads=64, ssm_groups=8,
    ssm_chunk=128, conv_bias=True, layer_pattern=PATTERN, rope="none",
    norm_eps=1e-5)

SMOKE = dataclasses.replace(
    CONFIG, n_layers=7, d_model=64, n_heads=4, kv_heads=2,
    head_dim_override=16, d_ff=32, vocab=256, n_experts=16, top_k=3,
    experts_held=4, shared_ff=48, ssm_state=8, ssm_headdim=8,
    ssm_n_heads=8, ssm_groups=4, ssm_chunk=16, remat="none")
