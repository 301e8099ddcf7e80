"""Plain reference of Qwen3-MoE's block served with FIER, in float32, at
one chip's share of its experts (arXiv:2505.09388; ``Qwen/Qwen3-235B-A22B``):

* RMSNorm with gains before attention, before the MoE layer and at the end;
* GQA attention with a per-head RMSNorm with gains on q and on k, applied
  before rotate-half RoPE; no biases;
* an MoE layer in every layer: the router scores all ``router_experts``
  experts, softmax, the top ``num_experts_per_tok`` kept and their weights
  renormalised (``norm_topk_prob``); the output is the sum, over the top
  experts that are held here (``num_experts`` of them from
  ``expert_first``), of weight · SwiGLU_e(x);
* an untied LM head.

Departures from the published model, each the chip's share or the
benchmark's: only the held experts' part of each MoE layer is computed
(the routes to experts held elsewhere add nothing, as in the program:
the ``model-configs`` guide's share); the model is cut to the file's
``num_hidden_layers``.  Attention is computed one KV head at a time,
which changes nothing in float32 (FIER selects per KV head) and bounds
the memory at long contexts; the float8 control then takes one scale per
KV head's operands rather than per tensor.  FIER's decode, the padding
and the float8 control are ``bench/reference/fier.py``'s.

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
BLOCK = {"norm": "rmsnorm", "qk_norm": True, "mlp": "moe_swiglu", "router": "softmax_topk",
         "norm_topk_prob": True, "shared_expert": False, "attention_bias": False,
         "tie_word_embeddings": False}


@dataclasses.dataclass(frozen=True)
class Arch:
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    norm_eps: float
    router_experts: int
    topk: int
    expert_first: int

    @classmethod
    def of(cls, config: dict) -> "Arch":
        return cls(config["num_attention_heads"], config["num_key_value_heads"],
                   config["head_dim"], float(config["rope_theta"]),
                   float(config["rms_norm_eps"]), config["router_experts"],
                   config["num_experts_per_tok"], config["expert_first"])


def _norm(x, gain, arch):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + arch.norm_eps) * gain


def _moe(x, w, arch, lowp):
    """The held experts' part of the MoE layer for x [T, d]."""
    logits = F.ein("td,de->te", x, w["router"], lowp)
    top, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), arch.topk)
    top = top / top.sum(-1, keepdims=True)
    held = w["w_gate"].shape[0]
    rel = idx - arch.expert_first
    rel = jnp.where((rel >= 0) & (rel < held), rel, held)      # held elsewhere
    gates = (jax.nn.one_hot(rel, held) * top[..., None]).sum(1)  # [T, held]

    def expert(y, e):
        a = jax.nn.silu(F.ein("td,df->tf", x, w["w_gate"][e], lowp))
        a = a * F.ein("td,df->tf", x, w["w_up"][e], lowp) * gates[:, e, None]
        return y + F.ein("tf,fd->td", a, w["w_down"][e], lowp), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(held))
    return y


@functools.partial(jax.jit, static_argnames=("arch", "fier", "lowp"))
def layer(h, w, dec_rows, *, arch: Arch, fier: F.Fier | None, lowp: bool):
    """One decoder layer over the whole (padded) sequence h [T, d]; the
    rows ``dec_rows`` attend through FIER when ``fier`` is given."""
    T = h.shape[0]
    H, Hkv, D = arch.heads, arch.kv_heads, arch.head_dim
    rep = H // Hkv
    pos = jnp.arange(T)
    x = _norm(h, w["attn_norm"], arch)
    q = F.ein("td,de->te", x, w["wq"], lowp).reshape(T, H, D)
    k = F.ein("td,de->te", x, w["wk"], lowp).reshape(T, Hkv, D)
    v = F.ein("td,de->te", x, w["wv"], lowp).reshape(T, Hkv, D)
    q = F.rope(_norm(q, w["q_norm"], arch), pos, arch.rope_theta).reshape(T, Hkv, rep, D)
    k = F.rope(_norm(k, w["k_norm"], arch), pos, arch.rope_theta)

    def one_kv_head(qkv):
        qh, kh, vh = qkv
        return F.attention(qh[:, None], kh[:, None], vh[:, None], dec_rows, fier,
                           lowp)[:, 0]

    o = jax.lax.map(one_kv_head, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2),
                                  v.transpose(1, 0, 2)))           # [Hkv, T, rep, D]
    h = h + F.ein("te,ed->td", o.transpose(1, 0, 2, 3).reshape(T, H * D), w["wo"], lowp)
    return h + _moe(_norm(h, w["mlp_norm"], arch), w, arch, lowp)


@functools.partial(jax.jit, static_argnames=("arch", "lowp"))
def head(h, rows, w, *, arch: Arch, lowp: bool):
    """Logits of ``rows`` through the final norm and the untied head."""
    return F.ein("nd,vd->nv", _norm(h[rows], w["norm"], arch), w["lm_head"], lowp)


def logits(config: dict, dep: dict, layer_weights, head_weights, tokens,
           first_row: int, *, lowp: bool = False) -> np.ndarray:
    """Reference logits [n, vocab] of rows ``first_row .. len(tokens)-1``
    (``fier.logits``).  ``layer_weights(l)`` gives layer l's weights;
    ``head_weights`` the embedding, the final norm's gain and the head."""
    arch = Arch.of(config)
    return F.logits(
        dep, tokens, first_row, config["num_hidden_layers"],
        embed=lambda toks: jnp.take(head_weights["embed"], toks, axis=0).astype(jnp.float32),
        layer=lambda l, h, dec, fier: layer(h, layer_weights(l), dec, arch=arch,
                                            fier=fier, lowp=lowp),
        head=lambda h, rows: head(h, rows, head_weights, arch=arch, lowp=lowp))
