"""nemotron3-nano-30b-a3b — NVIDIA Nemotron 3 Nano 30B-A3B, a hybrid
Mamba2 / MoE / attention stack [hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16].

52 pre-norm blocks, one mixer each, in ``hybrid_override_pattern`` order:
23 Mamba2 (M; 64 heads × 64 = d_inner 4096, B/C in 8 groups of state 128,
conv 4 with bias, gated RMSNorm per group), 23 MoE (E; 128 routed relu²
experts of width 1856, top-6 sigmoid router normalised over the chosen and
scaled by 2.5, one shared relu² expert of width 3712) and 6 GQA attention
(*; 32 query heads, 2 KV heads of 128, no rotary embedding). Untied head.
31.58 B parameters. Sub-quadratic (6 attention blocks of 52) → runs
long_500k.
"""

from repro.configs.base import ModelConfig

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

FULL = ModelConfig(
    name="nemotron3-nano-30b-a3b", family="hybrid_moe",
    num_layers=len(PATTERN), layer_pattern=PATTERN,
    d_model=2688, num_heads=32, num_kv_heads=2, head_dim=128,
    d_ff=1856, shared_d_ff=3712, vocab_size=131072, rope=False,
    num_experts=128, num_experts_per_tok=6, routed_scale=2.5,
    ssm_state=128, ssm_headdim=64, ssm_nheads=64, ssm_groups=8,
    ssm_chunk=128, ssm_conv=4, ssm_conv_bias=True,
    source="hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 config.json [hf]",
)

SMOKE = ModelConfig(
    name="nemotron3-nano-30b-a3b-smoke", family="hybrid_moe",
    num_layers=5, layer_pattern="MEM*E",
    d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    d_ff=32, shared_d_ff=64, vocab_size=512, rope=False,
    num_experts=16, num_experts_per_tok=2, experts_held=4, experts_first=4,
    routed_scale=2.5,
    ssm_state=16, ssm_headdim=16, ssm_nheads=4, ssm_groups=2, ssm_chunk=8,
    ssm_conv_bias=True, param_dtype="float32",
)
