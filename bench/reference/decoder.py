"""Plain reference of OLMo's block served with FIER, in float32:
non-parametric LayerNorm, SwiGLU MLP, no biases, rotate-half RoPE, tied
embeddings (arXiv:2402.00838).  FIER's decode, the padding and the
float8 control are ``bench/reference/fier.py``'s.

It imports nothing of the program and computes everything in float32 at
``highest`` matmul precision, layer by layer over one session's whole
sequence.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import fier as F

# the configuration file's statement of the block this module computes
BLOCK = {"norm": "layernorm_nonparametric", "mlp": "swiglu", "attention_bias": False,
         "mlp_bias": False, "tie_word_embeddings": True}


@dataclasses.dataclass(frozen=True)
class Arch:
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    norm_eps: float

    @classmethod
    def of(cls, config: dict) -> "Arch":
        return cls(config["num_attention_heads"], config["num_key_value_heads"],
                   config["head_dim"], float(config["rope_theta"]),
                   float(config["norm_eps"]))


def _norm(x, arch):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + arch.norm_eps)


@functools.partial(jax.jit, static_argnames=("arch", "fier", "lowp"))
def layer(h, w, dec_rows, *, arch: Arch, fier: F.Fier | None, lowp: bool):
    """One decoder layer over the whole (padded) sequence h [T, d]; the
    rows ``dec_rows`` attend through FIER when ``fier`` is given."""
    T = h.shape[0]
    H, Hkv, D = arch.heads, arch.kv_heads, arch.head_dim
    rep = H // Hkv
    pos = jnp.arange(T)
    x = _norm(h, arch)
    q = F.ein("td,de->te", x, w["wq"], lowp).reshape(T, H, D)
    k = F.ein("td,de->te", x, w["wk"], lowp).reshape(T, Hkv, D)
    v = F.ein("td,de->te", x, w["wv"], lowp).reshape(T, Hkv, D)
    q = F.rope(q, pos, arch.rope_theta).reshape(T, Hkv, rep, D)
    k = F.rope(k, pos, arch.rope_theta)
    o = F.attention(q, k, v, dec_rows, fier, lowp)
    h = h + F.ein("te,ed->td", o.reshape(T, H * D), w["wo"], lowp)
    x = _norm(h, arch)
    a = jax.nn.silu(F.ein("td,df->tf", x, w["w_gate"], lowp))
    a = a * F.ein("td,df->tf", x, w["w_up"], lowp)
    return h + F.ein("tf,fd->td", a, w["w_down"], lowp)


@functools.partial(jax.jit, static_argnames=("arch", "lowp"))
def head(h, rows, embed, *, arch: Arch, lowp: bool):
    """Logits of ``rows`` through the LM head, tied to the embedding."""
    return F.ein("nd,vd->nv", _norm(h[rows], arch), embed, lowp)


def logits(config: dict, dep: dict, layer_weights, embed, tokens,
           first_row: int, *, lowp: bool = False) -> np.ndarray:
    """Reference logits [n, vocab] of rows ``first_row .. len(tokens)-1``
    (``fier.logits``).  ``layer_weights(l)`` gives layer l's weights;
    ``embed`` is the [vocab, d] embedding, which is also the LM head."""
    arch = Arch.of(config)
    return F.logits(
        dep, tokens, first_row, config["num_hidden_layers"],
        embed=lambda toks: jnp.take(embed, toks, axis=0),
        layer=lambda l, h, dec, fier: layer(h, layer_weights(l), dec, arch=arch,
                                            fier=fier, lowp=lowp),
        head=lambda h, rows: head(h, rows, embed, arch=arch, lowp=lowp))
