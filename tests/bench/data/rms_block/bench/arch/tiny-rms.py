"""tiny-rms's architecture module: RMSNorm with gains, SwiGLU, GQA and an
untied LM head, which the program runs as a dense block with ``rms``
norms and ``tie_embeddings`` off.  The program has no QK-norm, so a file
that states one is refused."""
from __future__ import annotations

import jax

from bench import work

STATED = {"norm": "rmsnorm", "mlp": "swiglu", "qk_norm": False, "attention_bias": False,
          "tie_word_embeddings": False}


def model_config(config: dict):
    from repro.configs.base import ModelConfig

    stated = {k: config[k] for k in STATED}
    if stated != STATED:
        raise ValueError(f"{config['name']}: the file states the block {stated}, "
                         f"the program runs {STATED}")
    return ModelConfig(
        name=config["name"], family="dense", n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], d_head=config["head_dim"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"], norm="rms",
        act="silu", rope_theta=float(config["rope_theta"]), qkv_bias=False,
        tie_embeddings=False, param_dtype=config["param_dtype"],
        compute_dtype=config["compute_dtype"])


def layer_view(params, layer: int) -> dict:
    lp = jax.tree.map(lambda a: a[layer], params["layers"])
    attn, mlp = lp["attn"], lp["mlp"]
    return {"attn_norm": lp["norm1"]["w"], "mlp_norm": lp["norm2"]["w"],
            "wq": attn["wq"], "wk": attn["wk"], "wv": attn["wv"], "wo": attn["wo"],
            "w_gate": mlp["w1"], "w_up": mlp["w3"], "w_down": mlp["w2"]}


def head_view(params, vocab: int) -> dict:
    """The [vocab, d] embedding, the final norm's gain, and the untied
    [vocab, d] LM head (the program keeps it as [d, padded vocab])."""
    return {"embed": params["embed"][:vocab], "norm": params["final_norm"]["w"],
            "lm_head": params["lm_head"][:, :vocab].T}


def matmul_params(config: dict) -> int:
    d, D = config["hidden_size"], config["head_dim"]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    attn = d * (H + 2 * Hkv) * D + H * D * d
    mlp = 3 * d * config["intermediate_size"]
    return config["num_hidden_layers"] * (attn + mlp) + config["vocab_size"] * d


def weight_work(config: dict, batch: int) -> tuple[float, float]:
    p = matmul_params(config)
    return 2.0 * batch * p, float(p * work.BF16)
