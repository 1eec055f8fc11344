"""OLMoE-1B-7B [arXiv:2409.02060]: 64-expert top-8 MoE (1B active / 7B total)
with QK-norm, a float32 softmax router whose top-8 weights are not
renormalised, and a 0.01 load-balancing loss (allenai/OLMoE-1B-7B-0924).
The expert layer runs dropless (`models/moe.py`); a config that holds a
share of the experts sets ``router_experts`` and ``expert_offset``."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="olmoe-1b-7b",
    family="moe",
    source="https://huggingface.co/allenai/OLMoE-1B-7B-0924 [arXiv:2409.02060]",
    num_layers=16,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,
    d_ff=1024,
    vocab_size=50304,
    num_experts=64,
    experts_per_token=8,
    moe_norm_topk=False,
    moe_aux_coef=0.01,
    moe_mode="dropless",
    qk_norm=True,
    mlp_type="swiglu",
    norm_type="rmsnorm",
    norm_eps=1e-5,
    pos_type="rope",
    rope_theta=1e4,
    fed_mode="parallel",
)


def smoke_config() -> ModelConfig:
    """A share as the chip cut keeps it: 4 held of an 8-wide router, top 2."""
    import dataclasses
    return dataclasses.replace(
        CONFIG, num_layers=2, d_model=256, num_heads=4, num_kv_heads=4,
        head_dim=64, d_ff=128, vocab_size=512, num_experts=4,
        router_experts=8, experts_per_token=2, dtype="float32")
