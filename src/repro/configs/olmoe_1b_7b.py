"""OLMoE-1B-7B — MoE decoder, 64 experts top-8, full-width qk-norm.

[arXiv:2409.02060; hf:allenai/OLMoE-1B-7B-0924] 16L d_model=2048 16H
(kv=16) head_dim=128, vocab=50304, rope theta 1e4. Every layer is sparse:
64 SwiGLU experts of width 1024, 8 a token, no shared expert. The router
is a softmax over all 64 experts read at the top 8, NOT renormalised
(``norm_topk_prob: false``). The qk-norm is one RMSNorm over the whole
projected q (16 x 128 = 2048 wide) and one over the whole projected k,
before RoPE — not per head.
"""
from repro.configs.base import ModelConfig, MoEConfig, reduce_for_smoke

CONFIG = ModelConfig(
    name="olmoe-1b-7b",
    family="moe",
    num_layers=16,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_head=128,
    d_ff=1024,
    vocab_size=50_304,
    norm="rmsnorm",
    act="swiglu",
    rope="rope",
    rope_theta=1e4,
    qk_norm=True,
    qk_norm_span="full",
    moe=MoEConfig(num_experts=64, top_k=8, capacity_factor=1.25,
                  norm_topk_prob=False),
    source="arXiv:2409.02060",
)


def smoke_config() -> ModelConfig:
    return reduce_for_smoke(CONFIG)
