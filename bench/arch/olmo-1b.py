"""olmo-1b's architecture module: how ``bench/configs/olmo-1b.json`` maps
onto the program, its parameters and the work of its weights.

OLMo's block (arXiv:2402.00838): non-parametric LayerNorm, SwiGLU MLP, no
biases, an LM head tied to the embedding.  The program runs it as the
architecture entry the file's ``program_arch`` names.
"""
from __future__ import annotations

import dataclasses

import jax

from bench import work

# the file's statement of the block this module maps, and the program's
# ModelConfig fields that carry the same block
STATED = {"norm": "layernorm_nonparametric", "mlp": "swiglu", "attention_bias": False,
          "mlp_bias": False, "tie_word_embeddings": True}
PROGRAM = {"family": "dense", "norm": "nonparametric", "act": "silu", "qkv_bias": False,
           "tie_embeddings": True}


def model_config(config: dict):
    """The program's ModelConfig for the file: its architecture entry with
    the file's sizes, checked against the file's statement of the block."""
    from repro.configs import get_config

    stated = {k: config[k] for k in STATED}
    if stated != STATED:
        raise ValueError(f"{config['name']}: the file states the block {stated}, "
                         f"this module maps {STATED}")
    cfg = dataclasses.replace(
        get_config(config["program_arch"]), n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], d_head=config["head_dim"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        rope_theta=float(config["rope_theta"]),
        param_dtype=config["param_dtype"], compute_dtype=config["compute_dtype"],
    )
    have = {k: getattr(cfg, k) for k in PROGRAM}
    if have != PROGRAM:
        raise ValueError(f"{config['name']}: the program's block {have} is not "
                         f"the configuration's {PROGRAM}")
    return cfg


def layer_view(params, layer: int) -> dict:
    """Layer ``layer`` under the reference's names."""
    lp = jax.tree.map(lambda a: a[layer], params["layers"])
    attn, mlp = lp["attn"], lp["mlp"]
    return {"wq": attn["wq"], "wk": attn["wk"], "wv": attn["wv"], "wo": attn["wo"],
            "w_gate": mlp["w1"], "w_up": mlp["w3"], "w_down": mlp["w2"]}


def head_view(params, vocab: int) -> jax.Array:
    """The [vocab, d] embedding (the program pads its rows), which is also
    the tied LM head."""
    if "lm_head" in params:
        raise ValueError("the program's head is not tied to its embedding")
    return params["embed"][:vocab]


def matmul_params(config: dict) -> int:
    """Parameters of the matmuls one token passes through (head included)."""
    d, D = config["hidden_size"], config["head_dim"]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    attn = d * (H + 2 * Hkv) * D + H * D * d
    mlp = 3 * d * config["intermediate_size"]
    return config["num_hidden_layers"] * (attn + mlp) + config["vocab_size"] * d


def weight_work(config: dict, batch: int) -> tuple[float, float]:
    """(FLOPs, bytes) of the weights in one decode step of ``batch``
    tokens: every token passes through every matmul weight, and every
    weight is read once, in bf16."""
    p = matmul_params(config)
    return 2.0 * batch * p, float(p * work.BF16)
