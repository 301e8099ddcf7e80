"""Plain reference of a pre-norm decoder with RMSNorm gains, SwiGLU, GQA
and an untied LM head, served with FIER (``bench/reference/fier.py``), in
float32.  It imports nothing of the program."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import fier as F

BLOCK = {"norm": "rmsnorm", "mlp": "swiglu", "qk_norm": False, "attention_bias": False,
         "tie_word_embeddings": False}


@dataclasses.dataclass(frozen=True)
class Arch:
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    norm_eps: float


def _norm(x, gain, arch):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + arch.norm_eps) * gain


@functools.partial(jax.jit, static_argnames=("arch", "fier", "lowp"))
def layer(h, w, dec_rows, *, arch: Arch, fier: F.Fier | None, lowp: bool):
    T = h.shape[0]
    H, Hkv, D = arch.heads, arch.kv_heads, arch.head_dim
    pos = jnp.arange(T)
    x = _norm(h, w["attn_norm"], arch)
    q = F.ein("td,de->te", x, w["wq"], lowp).reshape(T, H, D)
    k = F.ein("td,de->te", x, w["wk"], lowp).reshape(T, Hkv, D)
    v = F.ein("td,de->te", x, w["wv"], lowp).reshape(T, Hkv, D)
    q = F.rope(q, pos, arch.rope_theta).reshape(T, Hkv, H // Hkv, D)
    k = F.rope(k, pos, arch.rope_theta)
    o = F.attention(q, k, v, dec_rows, fier, lowp)
    h = h + F.ein("te,ed->td", o.reshape(T, H * D), w["wo"], lowp)
    x = _norm(h, w["mlp_norm"], arch)
    a = jax.nn.silu(F.ein("td,df->tf", x, w["w_gate"], lowp))
    a = a * F.ein("td,df->tf", x, w["w_up"], lowp)
    return h + F.ein("tf,fd->td", a, w["w_down"], lowp)


@functools.partial(jax.jit, static_argnames=("arch", "lowp"))
def head(h, rows, w, *, arch: Arch, lowp: bool):
    return F.ein("nd,vd->nv", _norm(h[rows], w["norm"], arch), w["lm_head"], lowp)


def logits(config: dict, dep: dict, layer_weights, head_weights, tokens,
           first_row: int, *, lowp: bool = False) -> np.ndarray:
    arch = Arch(config["num_attention_heads"], config["num_key_value_heads"],
                config["head_dim"], float(config["rope_theta"]), float(config["norm_eps"]))
    return F.logits(
        dep, tokens, first_row, config["num_hidden_layers"],
        embed=lambda toks: jnp.take(head_weights["embed"], toks, axis=0),
        layer=lambda l, h, dec, fier: layer(h, layer_weights(l), dec, arch=arch,
                                            fier=fier, lowp=lowp),
        head=lambda h, rows: head(h, rows, head_weights, arch=arch, lowp=lowp))
