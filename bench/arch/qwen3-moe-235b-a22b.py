"""qwen3-moe-235b-a22b's architecture module: how
``bench/configs/qwen3-moe-235b-a22b.json`` maps onto the program, its
parameters and the work of its weights.

Qwen3-MoE's block (arXiv:2505.09388; ``Qwen/Qwen3-235B-A22B``): RMSNorm
with gains, GQA with a per-head RMSNorm on q and k before RoPE, no
attention biases, and an MoE layer in every layer: a softmax router over
all 128 experts, the top 8 weights renormalised, SwiGLU experts, no
shared expert; an untied LM head.  The file holds one chip's share: the
router keeps its 128 outputs, and the chip holds ``num_experts`` of them
from ``expert_first`` on.
"""
from __future__ import annotations

import dataclasses

import jax

from bench import work

# the file's statement of the block this module maps, and the program's
# ModelConfig fields that carry the same block
STATED = {"norm": "rmsnorm", "qk_norm": True, "mlp": "moe_swiglu", "router": "softmax_topk",
          "norm_topk_prob": True, "shared_expert": False, "attention_bias": False,
          "tie_word_embeddings": False}
PROGRAM = {"family": "moe", "norm": "rms", "act": "silu", "qkv_bias": False,
           "qk_norm": True, "tie_embeddings": False}


def model_config(config: dict):
    """The program's ModelConfig for the file: its architecture entry with
    the file's sizes and share, checked against the file's statement of
    the block."""
    from repro.configs import get_config

    stated = {k: config[k] for k in STATED}
    if stated != STATED:
        raise ValueError(f"{config['name']}: the file states the block {stated}, "
                         f"this module maps {STATED}")
    cfg = dataclasses.replace(
        get_config(config["program_arch"]), n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], d_head=config["head_dim"],
        d_ff=config["moe_intermediate_size"], vocab=config["vocab_size"],
        rope_theta=float(config["rope_theta"]), norm_eps=float(config["rms_norm_eps"]),
        n_experts=config["router_experts"], topk_experts=config["num_experts_per_tok"],
        expert_first=config["expert_first"], experts_held=config["num_experts"],
        param_dtype=config["param_dtype"], compute_dtype=config["compute_dtype"],
    )
    have = {k: getattr(cfg, k) for k in PROGRAM}
    if have != PROGRAM:
        raise ValueError(f"{config['name']}: the program's block {have} is not "
                         f"the configuration's {PROGRAM}")
    return cfg


def layer_view(params, layer: int) -> dict:
    """Layer ``layer`` under the reference's names; the experts are the
    ones held here, [E_held, ...]."""
    lp = jax.tree.map(lambda a: a[layer], params["layers"])
    attn, moe = lp["attn"], lp["moe"]
    return {"attn_norm": lp["norm1"]["w"], "mlp_norm": lp["norm2"]["w"],
            "wq": attn["wq"], "wk": attn["wk"], "wv": attn["wv"], "wo": attn["wo"],
            "q_norm": attn["q_norm"], "k_norm": attn["k_norm"], "router": moe["router"],
            "w_gate": moe["w1"], "w_up": moe["w3"], "w_down": moe["w2"]}


def head_view(params, vocab: int) -> dict:
    """The [vocab, d] embedding, the final norm's gain, and the untied
    [vocab, d] LM head (the program keeps it as [d, padded vocab])."""
    return {"embed": params["embed"][:vocab], "norm": params["final_norm"]["w"],
            "lm_head": params["lm_head"][:, :vocab].T}


def expert_work(config: dict, batch: int) -> tuple[float, float]:
    """(FLOPs, bytes) of the held experts in one decode step of ``batch``
    tokens, over all layers: FLOPs over the B·k·E_held/E routes expected
    to land here, bytes over the E_held·(1 − (1 − k/E)^B) held experts a
    step is expected to touch, each read once in bf16."""
    E, k = config["router_experts"], config["num_experts_per_tok"]
    held, L = config["num_experts"], config["num_hidden_layers"]
    per_expert = 3 * config["hidden_size"] * config["moe_intermediate_size"]
    routes = batch * k * held / E
    touched = held * (1.0 - (1.0 - k / E) ** batch)
    return 2.0 * L * routes * per_expert, float(L * touched * per_expert * work.BF16)


def weight_work(config: dict, batch: int) -> tuple[float, float]:
    """(FLOPs, bytes) of the weights in one decode step of ``batch``
    tokens: every token through the attention weights, the router and the
    untied head, each read once in bf16, and the held experts'
    ``expert_work``."""
    d, D = config["hidden_size"], config["head_dim"]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    attn = d * (H + 2 * Hkv) * D + H * D * d
    router = d * config["router_experts"]
    p = config["num_hidden_layers"] * (attn + router) + config["vocab_size"] * d
    ef, eb = expert_work(config, batch)
    return 2.0 * batch * p + ef, float(p * work.BF16) + eb
