"""Pallas TPU kernel: FIER 1-bit approximate score scan (the decode hot spot).

The paper's Triton kernel reads 1-bit quantized keys and computes
approximate attention scores.  TPU adaptation (DESIGN.md §2): the win is
HBM *bytes*, not popcount arithmetic — packed codes stream HBM→VMEM at
1/16 the bf16 key bytes, unpack inside VREGs, each bit selects one of
its group's two key levels z ± s, and the MXU computes

    s̃[t, r] = Σ_d k̃[t,d] · q[r,d],   k̃[t,d] = (z + s if bit else z − s)[G(t), d]

with the group-broadcast of the levels done in-register (scale/zero add
2·16/g bits per weight bit — Eq. 8's load ratio, measured exactly in
bench_load_ratio).

Layout: the kernel works on head-major views [B, Hkv, ...] so the seq
scan is the innermost contiguous stream; ``ops.fier_score`` adapts from
the seq-major cache layout.

Grid: (B·Hkv, S/blk_s).  VMEM per step ≈ blk_s·D/8 (codes) +
2·(blk_s/g)·D·2 (s,z) + rep·D·4 (q) + blk_s·rep·4 (out) bytes —
blk_s=512, D=128, g=32: 8 KiB + 16 KiB + ~4 KiB + 16·rep KiB ≪ VMEM;
block shapes are (8,128)-aligned for the VPU/MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quantize import dequant_levels


def score_block(
    qbf: jax.Array,
    codes: jax.Array,
    scale: jax.Array,
    zero: jax.Array,
    *,
    group: int,
) -> jax.Array:
    """Score one seq block of packed codes against a kv head's query group.

    qbf [rep, D] bf16; codes [blk_s/8, D] uint8; scale/zero [blk_s/g, D]
    → f32 [rep, blk_s].

    The score contract (``quantize.dequant_levels``; DESIGN.md §One-pass):
    each token's key is the bf16 level ``z ± s`` its code bit selects, and
    its score is one bf16 MXU dot with the query, accumulated in f32.  The
    levels are formed once per (group, channel) — ``g``× fewer VPU ops than
    dequantizing per token — and the jnp oracle
    (``core.retrieval.approx_scores``) evaluates the same expression.
    Shared by the score-scan kernel and the one-pass fused-retrieval
    kernel, so their per-token scores are bit-identical.
    """
    n8, D = codes.shape
    blk_s = n8 * 8
    ng = scale.shape[0]
    # unpack in int32 (native (8, 128) tiles): bit t of byte i is token 8i+t
    shifts = jax.lax.broadcasted_iota(jnp.int32, (n8, 8, D), 1)
    bits = (codes.astype(jnp.int32)[:, None, :] >> shifts) & 1
    bits = bits.reshape(blk_s, D)

    # select in f32 (bf16 levels are exact there), one exact cast back
    hi, lo = (x.astype(jnp.float32) for x in dequant_levels(scale, zero))
    hi_b = jnp.broadcast_to(hi[:, None, :], (ng, group, D)).reshape(blk_s, D)
    lo_b = jnp.broadcast_to(lo[:, None, :], (ng, group, D)).reshape(blk_s, D)
    keys = jnp.where(bits == 1, hi_b, lo_b).astype(jnp.bfloat16)
    return jax.lax.dot_general(
        qbf, keys, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _kernel(q_ref, codes_ref, scale_ref, zero_ref, out_ref, *, group: int):
    """One (batch·kv-head, seq-block) step.

    q_ref:     [rep, D]       f32/bf16 — queries of this kv head's group
    codes_ref: [blk_s/8, D]   uint8 packed sign bits (seq-major bit order)
    scale_ref: [blk_s/g, D]   bf16 group scales
    zero_ref:  [blk_s/g, D]   bf16 group zeros
    out_ref:   [rep, blk_s]   f32 scores
    """
    out_ref[...] = score_block(
        q_ref[...].astype(jnp.bfloat16), codes_ref[...], scale_ref[...],
        zero_ref[...], group=group,
    )


@functools.partial(jax.jit, static_argnames=("group", "blk_s", "interpret"))
def fier_score_hm(
    q: jax.Array,
    codes: jax.Array,
    scale: jax.Array,
    zero: jax.Array,
    *,
    group: int,
    blk_s: int = 512,
    interpret: bool = True,
) -> jax.Array:
    """Head-major score scan.

    q [BH, rep, D], codes [BH, S/8, D] uint8, scale/zero [BH, S/g, D]
    → scores f32 [BH, rep, S].
    """
    BH, rep, D = q.shape
    S = codes.shape[1] * 8
    blk_s = min(blk_s, S)
    assert S % blk_s == 0 and blk_s % group == 0 and blk_s % 8 == 0
    grid = (BH, S // blk_s)
    return pl.pallas_call(
        functools.partial(_kernel, group=group),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, rep, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, blk_s // 8, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, blk_s // group, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, blk_s // group, D), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, rep, blk_s), lambda b, i: (b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((BH, rep, S), jnp.float32),
        interpret=interpret,
    )(q, codes, scale, zero)
