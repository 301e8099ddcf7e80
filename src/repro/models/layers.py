"""Shared model-zoo layers: norms, RoPE, MLPs, flash attention, embeddings.

Everything is a pure function over explicit param pytrees (plain dicts of
arrays) — no framework dependency.  Layer params are *stacked* along a
leading L axis by the builders so depth is traversed with ``lax.scan``
(keeps HLO size O(1) in depth; mandatory for the 94-layer dry-runs on one
CPU core).

Compute dtype is bf16 (TPU-native), params fp32 by default, reductions and
softmax in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

def wuse(w: jax.Array, tp_dim: int = -1) -> jax.Array:
    """ZeRO-3 gather-before-use: constrain a weight to its TP-only sharding
    at the use site.

    FSDP stores matmul weights sharded on the *contraction* dim; left
    alone, GSPMD keeps that dim sharded through the matmul and all-reduces
    partial ACTIVATIONS (measured 1.15 GB f32 per layer on mamba2-370m
    prefill vs the 18 MB weight gather it should do — §Perf iteration 10).
    Constraining the weight to P(model-on-tp_dim) here forces the cheap
    weight all-gather instead.  No-op without an active mesh (unit tests,
    single device) or for shard_map-managed weights (MoE EP).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "model" not in mesh.axis_names or w.ndim < 2:
        return w
    from jax.sharding import PartitionSpec as P

    spec = [None] * w.ndim
    d = tp_dim if tp_dim >= 0 else w.ndim + tp_dim
    spec[d] = "model"
    return jax.lax.with_sharding_constraint(w, P(*spec))


# --------------------------------------------------------------------- norms

def rms_norm(x: jax.Array, w: jax.Array | None, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    if w is not None:
        y = y * w.astype(jnp.float32)
    return y.astype(x.dtype)


def layer_norm(
    x: jax.Array, w: jax.Array | None, b: jax.Array | None, eps: float = 1e-5
) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if w is not None:
        y = y * w.astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(x.dtype)


def apply_norm(x: jax.Array, p: dict, kind: str, eps: float = 1e-5) -> jax.Array:
    """kind: rms | layernorm | nonparametric (OLMo: LN with no learnables)."""
    if kind == "rms":
        return rms_norm(x, p["w"], eps)
    if kind == "layernorm":
        return layer_norm(x, p.get("w"), p.get("b"), eps)
    if kind == "nonparametric":
        return layer_norm(x, None, None, eps)
    raise ValueError(f"unknown norm {kind!r}")


def init_norm(kind: str, d: int) -> dict:
    if kind == "rms":
        return {"w": jnp.ones((d,), jnp.float32)}
    if kind == "layernorm":
        return {"w": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}
    if kind == "nonparametric":
        return {}
    raise ValueError(kind)


# ---------------------------------------------------------------------- RoPE

def rope_freqs(d_head: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (np.arange(0, d_head, 2, dtype=np.float32) / d_head))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, D]; positions: broadcastable to [..., S] (int32)."""
    D = x.shape[-1]
    freqs = rope_freqs(D, theta)  # [D/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, D/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2 :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------- MLPs

def mlp_apply(x: jax.Array, p: dict, act: str) -> jax.Array:
    """SwiGLU ('silu': w1/w3 gate) or GeLU ('gelu': single up-proj)."""
    if act == "silu":
        h = jax.nn.silu(x @ wuse(p["w1"], -1).astype(x.dtype)) * (
            x @ wuse(p["w3"], -1).astype(x.dtype))
    elif act == "gelu":
        h = jax.nn.gelu(x @ wuse(p["w1"], -1).astype(x.dtype))
    else:
        raise ValueError(act)
    return h @ wuse(p["w2"], 0).astype(x.dtype)


def init_mlp(rng: jax.Array, d: int, ff: int, act: str) -> dict:
    k1, k2, k3 = jax.random.split(rng, 3)
    s_in, s_out = d**-0.5, ff**-0.5
    p = {
        "w1": jax.random.normal(k1, (d, ff), jnp.float32) * s_in,
        "w2": jax.random.normal(k2, (ff, d), jnp.float32) * s_out,
    }
    if act == "silu":
        p["w3"] = jax.random.normal(k3, (d, ff), jnp.float32) * s_in
    return p


# ----------------------------------------------------------- flash attention

def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_k: int = 512,
    q_offset: int | jax.Array = 0,
    bias_mask: jax.Array | None = None,
) -> jax.Array:
    """Flash attention with a custom VJP (memory O(S·block) in fwd AND bwd).

    q: [B, Sq, Hq, D]; k, v: [B, Sk, Hkv, D] (GQA: Hq = rep·Hkv).
    Forward scans key blocks with an online softmax; backward recomputes
    scores blockwise from saved (q, k, v, out, lse) — autodiff through the
    forward scan would instead save per-block probability tensors
    (observed: 10s of GB/device on the 4k-train cells; EXPERIMENTS.md
    §Perf iteration 3).  ``q_offset`` is the global position of q[0];
    ``bias_mask`` [B, Sk] marks valid key slots (padding).
    """
    return _flash_custom(q, k, v, causal, block_k, q_offset, bias_mask)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_custom(q, k, v, causal, block_k, q_offset, bias_mask):
    out, _ = _flash_fwd_impl(q, k, v, causal, block_k, q_offset, bias_mask)
    return out


def _flash_fwd_rule(q, k, v, causal, block_k, q_offset, bias_mask):
    out, lse = _flash_fwd_impl(q, k, v, causal, block_k, q_offset, bias_mask)
    return out, (q, k, v, out, lse, q_offset, bias_mask)


def _flash_bwd_rule(causal, block_k, res, dout):
    q, k, v, out, lse, q_offset, bias_mask = res
    dq, dk, dv = _flash_bwd_impl(
        q, k, v, out, lse, dout, causal, block_k, q_offset, bias_mask
    )
    return dq, dk, dv, None, None


_BLOCK_Q = 512


def _qblocks(x, block_q):
    """[B, Sq, ...] → [nq, B, block_q, ...] (zero-padded)."""
    B, Sq = x.shape[:2]
    nq = -(-Sq // block_q)
    if nq * block_q != Sq:
        pad = [(0, 0), (0, nq * block_q - Sq)] + [(0, 0)] * (x.ndim - 2)
        x = jnp.pad(x, pad)
    return jnp.moveaxis(x.reshape(B, nq, block_q, *x.shape[2:]), 1, 0)


def _flash_fwd_impl(q, k, v, causal, block_k, q_offset, bias_mask):
    """Tile over q blocks (scan) × k blocks (inner scan): peak score tile
    is [B, block_q, Hq, block_k] — both dims bounded."""
    B, Sq, Hq, D = q.shape
    if Sq <= _BLOCK_Q:
        return _flash_fwd_one(q, k, v, causal, block_k, q_offset, bias_mask)
    qb = _qblocks(q, _BLOCK_Q)
    nq = qb.shape[0]

    def body(_, xs):
        qi, i = xs
        out_i, lse_i = _flash_fwd_one(
            qi, k, v, causal, block_k,
            jnp.asarray(q_offset, jnp.int32) + i * _BLOCK_Q, bias_mask,
        )
        return None, (out_i, lse_i)

    _, (outb, lseb) = jax.lax.scan(body, None, (qb, jnp.arange(nq, dtype=jnp.int32)))
    out = jnp.moveaxis(outb, 0, 1).reshape(B, nq * _BLOCK_Q, Hq, D)[:, :Sq]
    Hkv, rep = lseb.shape[3], lseb.shape[4]
    lse = jnp.moveaxis(lseb, 0, 1).reshape(B, nq * _BLOCK_Q, Hkv, rep)[:, :Sq]
    return out, lse


def _flash_bwd_impl(q, k, v, out, lse, dout, causal, block_k, q_offset, bias_mask):
    B, Sq, Hq, D = q.shape
    if Sq <= _BLOCK_Q:
        return _flash_bwd_one(q, k, v, out, lse, dout, causal, block_k, q_offset, bias_mask)
    qb, ob, dob, lb = (_qblocks(x, _BLOCK_Q) for x in (q, out, dout, lse))
    nq = qb.shape[0]
    Sk, Hkv = k.shape[1], k.shape[2]

    def body(carry, xs):
        dk_acc, dv_acc = carry
        qi, oi, doi, li, i = xs
        dq_i, dk_i, dv_i = _flash_bwd_one(
            qi, k, v, oi, li, doi, causal, block_k,
            jnp.asarray(q_offset, jnp.int32) + i * _BLOCK_Q, bias_mask,
        )
        return (dk_acc + dk_i.astype(jnp.float32),
                dv_acc + dv_i.astype(jnp.float32)), dq_i

    zero = jnp.zeros((B, Sk, Hkv, D), jnp.float32)
    (dk, dv), dqb = jax.lax.scan(
        body, (zero, zero), (qb, ob, dob, lb, jnp.arange(nq, dtype=jnp.int32))
    )
    dq = jnp.moveaxis(dqb, 0, 1).reshape(B, nq * _BLOCK_Q, Hq, D)[:, :Sq]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _flash_fwd_one(q, k, v, causal, block_k, q_offset, bias_mask):
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    nb = -(-Sk // block_k)
    Skp = nb * block_k
    if Skp != Sk:  # pad keys to a whole number of blocks
        pad = [(0, 0), (0, Skp - Sk), (0, 0), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    scale = 1.0 / np.sqrt(D)
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, Hkv, rep, D)
    q_pos = jnp.asarray(q_offset, jnp.int32) + jnp.arange(Sq, dtype=jnp.int32)

    kb = k.reshape(B, nb, block_k, Hkv, D)
    vb = v.reshape(B, nb, block_k, Hkv, D)

    def body(carry, xs):
        m, num, den = carry
        kblk, vblk, bidx = xs
        s = jnp.einsum(
            "bqhrd,bkhd->bqhrk", qf, kblk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        k_pos = bidx * block_k + jnp.arange(block_k, dtype=jnp.int32)
        mask = k_pos[None, :] < Sk  # [1, blk] padding
        if bias_mask is not None:
            blk_valid = jax.lax.dynamic_slice_in_dim(
                jnp.pad(bias_mask, ((0, 0), (0, Skp - Sk))), bidx * block_k,
                block_k, axis=1,
            )
            mask = mask & blk_valid
        if causal:
            cm = q_pos[:, None] >= k_pos[None, :]  # [Sq, blk]
            s = jnp.where(cm[None, :, None, None, :], s, -jnp.inf)
        s = jnp.where(mask[:, None, None, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # guard fully-masked rows (m_new = -inf): no contribution
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - safe_m[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        num = num * alpha[..., None] + jnp.einsum(
            "bqhrk,bkhd->bqhrd", p, vblk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        den = den * alpha + p.sum(axis=-1)
        return (m_new, num, den), None

    init = (
        jnp.full((B, Sq, Hkv, rep), -jnp.inf, jnp.float32),
        jnp.zeros((B, Sq, Hkv, rep, D), jnp.float32),
        jnp.zeros((B, Sq, Hkv, rep), jnp.float32),
    )
    (m, num, den), _ = jax.lax.scan(
        body, init, (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0),
                     jnp.arange(nb, dtype=jnp.int32))
    )
    out = num / jnp.maximum(den, 1e-30)[..., None]
    lse = m + jnp.log(jnp.maximum(den, 1e-30))  # [B,Sq,Hkv,rep]
    return out.reshape(B, Sq, Hq, D).astype(q.dtype), lse


def _flash_bwd_one(q, k, v, out, lse, dout, causal, block_k, q_offset, bias_mask):
    """Blockwise flash backward: recompute p from (q,k,lse), accumulate
    dq/dk/dv over key blocks.  All f32 accumulation; O(S·block) memory."""
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    nb = -(-Sk // block_k)
    Skp = nb * block_k
    if Skp != Sk:
        pad = [(0, 0), (0, Skp - Sk), (0, 0), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    scale = 1.0 / np.sqrt(D)
    qf = q.astype(jnp.float32).reshape(B, Sq, Hkv, rep, D)
    dof = dout.astype(jnp.float32).reshape(B, Sq, Hkv, rep, D)
    of = out.astype(jnp.float32).reshape(B, Sq, Hkv, rep, D)
    # D_i = Σ_d dout·out  (softmax backward diagonal term)
    Dterm = jnp.sum(dof * of, axis=-1)  # [B,Sq,Hkv,rep]
    q_pos = jnp.asarray(q_offset, jnp.int32) + jnp.arange(Sq, dtype=jnp.int32)
    kb = jnp.moveaxis(k.reshape(B, nb, block_k, Hkv, D), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nb, block_k, Hkv, D), 1, 0)
    if bias_mask is not None:
        bm = jnp.pad(bias_mask, ((0, 0), (0, Skp - Sk)))

    def body(dq_acc, xs):
        kblk, vblk, bidx = xs
        kf = kblk.astype(jnp.float32)
        s = jnp.einsum("bqhrd,bkhd->bqhrk", qf, kf) * scale
        k_pos = bidx * block_k + jnp.arange(block_k, dtype=jnp.int32)
        mask = k_pos[None, :] < Sk
        if bias_mask is not None:
            blk_valid = jax.lax.dynamic_slice_in_dim(bm, bidx * block_k, block_k, 1)
            mask = mask & blk_valid
        neg = jnp.float32(-1e30)
        if causal:
            s = jnp.where((q_pos[:, None] >= k_pos[None, :])[None, :, None, None, :], s, neg)
        s = jnp.where(mask[:, None, None, None, :], s, neg)
        p = jnp.exp(s - lse[..., None])            # [B,Sq,Hkv,rep,blk]
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        dv_blk = jnp.einsum("bqhrk,bqhrd->bkhd", p, dof)
        dp = jnp.einsum("bqhrd,bkhd->bqhrk", dof, vblk.astype(jnp.float32))
        ds = p * (dp - Dterm[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bqhrk,bkhd->bqhrd", ds, kf)
        dk_blk = jnp.einsum("bqhrk,bqhrd->bkhd", ds, qf)
        return dq_acc, (dk_blk, dv_blk)

    dq0 = jnp.zeros((B, Sq, Hkv, rep, D), jnp.float32)
    dq, (dk_b, dv_b) = jax.lax.scan(
        body, dq0, (kb, vb, jnp.arange(nb, dtype=jnp.int32))
    )
    dk = jnp.moveaxis(dk_b, 0, 1).reshape(B, Skp, Hkv, D)[:, :Sk]
    dv = jnp.moveaxis(dv_b, 0, 1).reshape(B, Skp, Hkv, D)[:, :Sk]
    return (
        dq.reshape(B, Sq, Hq, D).astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
    )


_flash_custom.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def attention_ref(q, k, v, *, causal=True, q_offset=0, bias_mask=None):
    """Dense oracle for flash_attention (test-only; materialises S×S)."""
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    scale = 1.0 / np.sqrt(D)
    qf = q.astype(jnp.float32).reshape(B, Sq, Hkv, rep, D) * scale
    s = jnp.einsum("bqhrd,bkhd->bqhrk", qf, k.astype(jnp.float32))
    q_pos = jnp.asarray(q_offset, jnp.int32) + jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    if causal:
        s = jnp.where(
            (q_pos[:, None] >= k_pos[None, :])[None, :, None, None, :], s, -jnp.inf
        )
    if bias_mask is not None:
        s = jnp.where(bias_mask[:, None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    o = jnp.einsum("bqhrk,bkhd->bqhrd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, Hq, D).astype(q.dtype)


# ----------------------------------------------------------------- embedding

def init_embedding(rng: jax.Array, vocab: int, d: int) -> jax.Array:
    return jax.random.normal(rng, (vocab, d), jnp.float32) * (d**-0.5)


def init_linear(rng: jax.Array, d_in: int, d_out: int) -> jax.Array:
    return jax.random.normal(rng, (d_in, d_out), jnp.float32) * (d_in**-0.5)
