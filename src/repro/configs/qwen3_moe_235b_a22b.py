"""qwen3-moe-235b-a22b [moe]: 128 experts, top-8, 94 layers.

[hf:Qwen/Qwen3-235B-A22B; arXiv:2505.09388] 94L d_model=4096 64H (GQA
kv=4) d_head=128 (q is 8192 wide), per-head RMSNorm with gains on q and k
before RoPE (QK-norm), no attention biases, rope_theta 1e6, RMSNorm eps
1e-6.  Every layer is MoE: 128 routed experts, softmax router with the
top-8 weights renormalised (norm_topk_prob), SwiGLU experts of width
1536, no shared expert.  Untied LM head, vocab 151936.  All 128 experts
are held by default; a chip's share sets ``expert_first`` /
``experts_held``.
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-moe-235b-a22b",
    family="moe",
    n_layers=94,
    d_model=4096,
    n_heads=64,
    n_kv_heads=4,
    d_head=128,
    d_ff=1536,
    vocab=151936,
    norm="rms",
    act="silu",
    rope_theta=1e6,
    qk_norm=True,
    norm_eps=1e-6,
    tie_embeddings=False,
    n_experts=128,
    topk_experts=8,
    param_dtype="bfloat16",  # the published dtype
    source="hf:Qwen/Qwen3-235B-A22B; arXiv:2505.09388",
)
