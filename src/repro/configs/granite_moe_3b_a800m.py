"""Granite-3.0-3B-A800M MoE [hf:ibm-granite] — 40 experts top-8.

40 experts cannot split a 16-way model axis evenly → the "moe_cap" profile
shards the expert *capacity* dim over model and runs attention sequence-
parallel (24 heads % 16 != 0 as well).

The published model routes without drops: ``capacity_factor = n_experts /
top_k`` states that, so ``moe_apply`` takes its dropless grouped-matmul
path (the smoke config likewise).
"""

from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="granite-moe-3b-a800m", family="moe",
    n_layers=32, d_model=1536, n_heads=24, n_kv_heads=8, d_ff=512,
    vocab_size=49155, head_dim=64, mlp="swiglu", norm="rms",
    moe=MoEConfig(n_experts=40, top_k=8, expert_d_ff=512,
                  capacity_factor=40 / 8),
    sharding_profile="moe_cap", subquadratic=False,
)


def smoke() -> ModelConfig:
    return ModelConfig(
        name="granite-moe-smoke", family="moe",
        n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
        vocab_size=256, moe=MoEConfig(n_experts=4, top_k=2, expert_d_ff=64,
                                      capacity_factor=4 / 2),
        remat="none")
