"""What every plain reference of a decoder served with FIER shares: the
float32 matmul at ``highest`` precision and its float8 control, RoPE,
dense causal attention, FIER's decode attention (arXiv:2508.08256,
Alg. 1), and the walk over one session's padded sequence.  A block's own
reference (``bench/reference/<name>.py``) brings its norms, MLP, head and
how its weights are laid out, and calls ``logits`` here.

FIER's decode: keys quantized to 1 bit per channel with a min/max scale
and zero per ``group`` consecutive tokens, approximate scores q·k̃ reduced
over each KV head's query group by max, the first ``sink`` and last
``recent`` tokens forced in, the top ``budget`` kept, and exact softmax
attention over them; the first ``skip_layers`` layers attend densely.
Prompt rows attend densely, as a prefill does.

``lowp=True`` is the control: every matmul operand rounded to float8
(e4m3, one scale per tensor), the precision below the bfloat16 the
configurations compute in.  Nothing here imports the program.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
DEC_PAD = 256           # decode rows are padded to a multiple of this
Q_BLOCK = 512           # dense attention query block


@dataclasses.dataclass(frozen=True)
class Fier:
    budget: int
    group: int
    sink: int
    recent: int


def q8(x):
    """Round to float8 e4m3 with one scale for the tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def ein(spec, a, b, lowp):
    if lowp:
        a, b = q8(a), q8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rope(x, pos, theta):
    """Rotate-half RoPE of x [T, H, D] at positions ``pos`` [T]."""
    D = x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float32) / D)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def dense_attention(q, k, v, lowp):
    """Causal softmax attention of every row.  q [T, Hkv, rep, D];
    k, v [T, Hkv, D]."""
    T, Hkv, rep, D = q.shape
    keys = jnp.arange(T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = ein("thrd,khd->thrk", qb, k, lowp) / math.sqrt(D)
        s = jnp.where((keys[None, :] <= rows[:, None])[:, None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return ein("thrk,khd->thrd", p, v, lowp)

    out = jax.lax.map(block, jnp.arange(T // Q_BLOCK))
    return out.reshape(T, Hkv, rep, D)


def fier_attention(q, k, v, rows, fier, lowp):
    """FIER decode attention of ``rows`` [N]: the query of row t attends
    over the selected keys among 0..t.  q [N, Hkv, rep, D]."""
    T, Hkv, D = k.shape
    g = fier.group
    kg = k.reshape(T // g, g, Hkv, D)
    hi_, lo_ = kg.max(1), kg.min(1)
    zero, scale = (hi_ + lo_) / 2, (hi_ - lo_) / 2
    bits = kg >= zero[:, None]
    kt = jnp.where(bits, (zero + scale)[:, None], (zero - scale)[:, None]).reshape(T, Hkv, D)
    approx = ein("nhrd,khd->nhrk", q, kt, lowp).max(2)           # [N, Hkv, T]
    keys = jnp.arange(T)[None, None, :]
    t = rows[:, None, None]
    valid = keys <= t
    forced = (keys < fier.sink) | (keys >= t + 1 - fier.recent)
    score = jnp.where(valid, jnp.where(forced, jnp.inf, approx), -jnp.inf)
    _, idx = jax.lax.top_k(score, fier.budget)                   # [N, Hkv, k]
    n_i = jnp.arange(q.shape[0])[:, None, None]
    h_i = jnp.arange(Hkv)[None, :, None]
    sel = jnp.zeros(score.shape, bool).at[n_i, h_i, idx].set(True) & valid
    s = ein("nhrd,khd->nhrk", q, k, lowp) / math.sqrt(D)
    s = jnp.where(sel[:, :, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return ein("nhrk,khd->nhrd", p, v, lowp)


def attention(q, k, v, dec_rows, fier, lowp):
    """Every row attends densely; the rows ``dec_rows`` attend through
    FIER instead when ``fier`` is given.  q [T, Hkv, rep, D]."""
    o = dense_attention(q, k, v, lowp)
    if fier is not None:
        o = o.at[dec_rows].set(fier_attention(q[dec_rows], k, v, dec_rows, fier, lowp))
    return o


def logits(dep: dict, tokens, first_row: int, n_layers: int, *, embed, layer,
           head) -> np.ndarray:
    """Reference logits [n, vocab] of rows ``first_row .. len(tokens)-1``
    of one session; rows after ``first_row`` are decode rows (FIER past
    the skip layers).  The block's functions: ``embed(tokens)`` gives the
    input rows [T, d]; ``layer(l, h, dec_rows, fier)`` runs layer l over
    the whole sequence (``fier`` None: densely); ``head(h, rows)`` gives
    the logits of ``rows``."""
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
        h = embed(jnp.asarray(toks))
        dec = jnp.asarray(dec)
        for l in range(n_layers):
            h = layer(l, h, dec, None if l < dep["skip_layers"] else fier)
        out = head(h, jnp.asarray(rows))
    return np.asarray(out)[: n_dec + 1]
