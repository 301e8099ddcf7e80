"""Plain reference of a pre-norm decoder LM served with FIER, in float32.

It follows OLMo's published architecture (non-parametric LayerNorm,
SwiGLU MLP, no biases, rotate-half RoPE, tied embeddings) and the FIER
paper's decode
(arXiv:2508.08256, Alg. 1): keys quantized to 1 bit per channel with a
min/max scale and zero per ``group`` consecutive tokens, approximate
scores q·k̃ reduced over each KV head's query group by max, the first
``sink`` and last ``recent`` tokens forced in, the top ``budget`` kept,
and exact softmax attention over them; the first ``skip_layers`` layers
attend densely.  Prompt rows attend densely, as a prefill does.

It imports nothing of the program and computes everything in float32 at
``highest`` matmul precision, layer by layer over one session's whole
sequence.  ``lowp=True`` is the control: every matmul operand rounded to
float8 (e4m3, one scale per tensor), the precision below the bfloat16 the
configuration computes in.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
DEC_PAD = 256           # decode rows are padded to a multiple of this
Q_BLOCK = 512           # dense attention query block


@dataclasses.dataclass(frozen=True)
class Arch:
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    norm_eps: float

    @classmethod
    def of(cls, config: dict) -> "Arch":
        block = (config["norm"], config["mlp"], config["attention_bias"],
                 config["mlp_bias"], config["tie_word_embeddings"])
        if block != ("layernorm_nonparametric", "swiglu", False, False, True):
            raise ValueError(f"{config['name']}: the reference computes OLMo's "
                             f"block, not {block}")
        return cls(config["num_attention_heads"], config["num_key_value_heads"],
                   config["head_dim"], float(config["rope_theta"]),
                   float(config["norm_eps"]))


@dataclasses.dataclass(frozen=True)
class Fier:
    budget: int
    group: int
    sink: int
    recent: int


def _q8(x):
    """Round to float8 e4m3 with one scale for the tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ein(spec, a, b, lowp):
    if lowp:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _norm(x, arch):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + arch.norm_eps)


def _rope(x, pos, theta):
    D = x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float32) / D)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _dense_attention(q, k, v, lowp):
    """Causal softmax attention of every row.  q [T, Hkv, rep, D];
    k, v [T, Hkv, D]."""
    T, Hkv, rep, D = q.shape
    keys = jnp.arange(T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = _ein("thrd,khd->thrk", qb, k, lowp) / math.sqrt(D)
        s = jnp.where((keys[None, :] <= rows[:, None])[:, None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _ein("thrk,khd->thrd", p, v, lowp)

    out = jax.lax.map(block, jnp.arange(T // Q_BLOCK))
    return out.reshape(T, Hkv, rep, D)


def _fier_attention(q, k, v, rows, fier, lowp):
    """FIER decode attention of ``rows`` [N]: the query of row t attends
    over the selected keys among 0..t.  q [N, Hkv, rep, D]."""
    T, Hkv, D = k.shape
    g = fier.group
    kg = k.reshape(T // g, g, Hkv, D)
    hi_, lo_ = kg.max(1), kg.min(1)
    zero, scale = (hi_ + lo_) / 2, (hi_ - lo_) / 2
    bits = kg >= zero[:, None]
    kt = jnp.where(bits, (zero + scale)[:, None], (zero - scale)[:, None]).reshape(T, Hkv, D)
    approx = _ein("nhrd,khd->nhrk", q, kt, lowp).max(2)          # [N, Hkv, T]
    keys = jnp.arange(T)[None, None, :]
    t = rows[:, None, None]
    valid = keys <= t
    forced = (keys < fier.sink) | (keys >= t + 1 - fier.recent)
    score = jnp.where(valid, jnp.where(forced, jnp.inf, approx), -jnp.inf)
    _, idx = jax.lax.top_k(score, fier.budget)                   # [N, Hkv, k]
    n_i = jnp.arange(q.shape[0])[:, None, None]
    h_i = jnp.arange(Hkv)[None, :, None]
    sel = jnp.zeros(score.shape, bool).at[n_i, h_i, idx].set(True) & valid
    s = _ein("nhrd,khd->nhrk", q, k, lowp) / math.sqrt(D)
    s = jnp.where(sel[:, :, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return _ein("nhrk,khd->nhrd", p, v, lowp)


@functools.partial(jax.jit, static_argnames=("arch", "fier", "lowp"))
def layer(h, w, dec_rows, *, arch: Arch, fier: Fier | None, lowp: bool):
    """One decoder layer over the whole (padded) sequence h [T, d]; the
    rows ``dec_rows`` attend through FIER when ``fier`` is given."""
    T = h.shape[0]
    H, Hkv, D = arch.heads, arch.kv_heads, arch.head_dim
    rep = H // Hkv
    pos = jnp.arange(T)
    x = _norm(h, arch)
    q = _ein("td,de->te", x, w["wq"], lowp).reshape(T, H, D)
    k = _ein("td,de->te", x, w["wk"], lowp).reshape(T, Hkv, D)
    v = _ein("td,de->te", x, w["wv"], lowp).reshape(T, Hkv, D)
    q = _rope(q, pos, arch.rope_theta).reshape(T, Hkv, rep, D)
    k = _rope(k, pos, arch.rope_theta)
    o = _dense_attention(q, k, v, lowp)
    if fier is not None:
        o = o.at[dec_rows].set(_fier_attention(q[dec_rows], k, v, dec_rows, fier, lowp))
    h = h + _ein("te,ed->td", o.reshape(T, H * D), w["wo"], lowp)
    x = _norm(h, arch)
    a = jax.nn.silu(_ein("td,df->tf", x, w["w_gate"], lowp))
    a = a * _ein("td,df->tf", x, w["w_up"], lowp)
    return h + _ein("tf,fd->td", a, w["w_down"], lowp)


@functools.partial(jax.jit, static_argnames=("arch", "lowp"))
def head(h, rows, embed, *, arch: Arch, lowp: bool):
    """Logits of ``rows`` through the LM head, tied to the embedding."""
    return _ein("nd,vd->nv", _norm(h[rows], arch), embed, lowp)


def logits(config: dict, dep: dict, layer_weights, embed, tokens,
           first_row: int, *, lowp: bool = False) -> np.ndarray:
    """Reference logits [n, vocab] of rows ``first_row .. len(tokens)-1``;
    rows after ``first_row`` are decode rows (FIER past the skip layers).
    ``layer_weights(l)`` gives layer l's weights; ``embed`` is the
    [vocab, d] embedding, which is also the LM head."""
    arch = Arch.of(config)
    fier = Fier(dep["budget"], dep["group"], dep["sink"], dep["recent"])
    T = len(tokens)
    # every session is padded to the slot's capacity: one compiled shape
    Tp = dep["capacity"]
    if not (T <= Tp and Tp % Q_BLOCK == 0 and Tp % dep["group"] == 0):
        raise ValueError(f"{T} tokens in a capacity of {Tp}")
    n_dec = T - first_row - 1
    Np = max(DEC_PAD, -(-n_dec // DEC_PAD) * DEC_PAD)
    toks = np.zeros((Tp,), np.int32)
    toks[:T] = tokens
    dec = np.full((Np,), T - 1, np.int32)
    dec[:n_dec] = np.arange(first_row + 1, T)
    rows = np.full((Np + DEC_PAD,), T - 1, np.int32)
    rows[: n_dec + 1] = np.arange(first_row, T)
    with jax.default_matmul_precision("highest"):
        h = jnp.take(embed, jnp.asarray(toks), axis=0)
        dec = jnp.asarray(dec)
        for l in range(config["num_hidden_layers"]):
            h = layer(h, layer_weights(l), dec, arch=arch,
                      fier=None if l < dep["skip_layers"] else fier, lowp=lowp)
        out = head(h, jnp.asarray(rows), embed, arch=arch, lowp=lowp)
    return np.asarray(out)[: n_dec + 1]
