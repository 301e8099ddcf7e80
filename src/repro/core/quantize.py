"""1-bit group-wise RTN quantization of the key cache (FIER, §3.2/Alg. 1).

Layout conventions
------------------
Keys are stored seq-major: ``K[b, s, h_kv, d]``.  Quantization groups are
``g`` *consecutive tokens along the sequence* within each channel (paper
Alg. 1 line 4: "partition K into groups of size g along each channel").
Each (group, channel) cell stores a bf16 ``(scale, zero)`` pair; each token
stores one sign bit per channel.

Packing: 8 consecutive tokens of one channel share a byte (seq-major bit
order, bit ``t`` = token ``8*i + t``).  This keeps the decode-time score scan
sequential in HBM and lets a Pallas block unpack with broadcast shifts.

The load ratio of the packed representation is ``(1 + 32/g) / 16`` of the
bf16 key bytes (paper Eq. 8) — verified exactly in
``benchmarks/bench_load_ratio.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
class QuantizedKeys:
    """Packed 1-bit key-cache side-car (pytree; ``group`` is static aux data
    so instances survive vmap/scan/jit and can be stacked across layers).

    codes:  uint8[B, S//8, H, D]   sign bits, 8 seq positions per byte
    scale:  bf16 [B, S//g, H, D]   per (seq-group, channel) scale  (s)
    zero:   bf16 [B, S//g, H, D]   per (seq-group, channel) zero   (z)
    group:  python int, tokens per group (g)
    """

    def __init__(self, codes, scale, zero, group: int):
        self.codes = codes
        self.scale = scale
        self.zero = zero
        self.group = group

    def tree_flatten(self):
        return (self.codes, self.scale, self.zero), self.group

    @classmethod
    def tree_unflatten(cls, group, children):
        return cls(*children, group)

    def __repr__(self):
        return (f"QuantizedKeys(codes={getattr(self.codes, 'shape', None)}, "
                f"group={self.group})")

    @property
    def seq_len(self) -> int:
        return self.codes.shape[-3] * 8


def _check_seq(S: int, group: int) -> None:
    if S % group != 0:
        raise ValueError(f"seq len {S} not divisible by group size {group}")
    if S % 8 != 0:
        raise ValueError(f"seq len {S} not divisible by 8 (bit packing)")
    if group % 8 != 0:
        raise ValueError(f"group size {group} must be a multiple of 8")


def group_stats(K: jax.Array, group: int) -> tuple[jax.Array, jax.Array]:
    """Per (seq-group, channel) midpoint/half-range: 1-bit RTN scale & zero.

    K: [B, S, H, D] → scale, zero: [B, S//g, H, D]

    With levels {-1, +1}, RTN maps a group to {z - s, z + s}; choosing
    z = (max+min)/2 and s = (max-min)/2 makes the two levels the group
    min / max, the optimum for the min-max (round-to-nearest) quantizer.
    """
    B, S, H, D = K.shape
    Kg = K.reshape(B, S // group, group, H, D)
    kmax = Kg.max(axis=2)
    kmin = Kg.min(axis=2)
    zero = (kmax + kmin) * 0.5
    scale = (kmax - kmin) * 0.5
    return scale.astype(jnp.bfloat16), zero.astype(jnp.bfloat16)


def sign_bits(K: jax.Array, zero: jax.Array, group: int) -> jax.Array:
    """±1 codes as {0,1} bits: bit = (K >= z).  [B, S, H, D] uint8 (unpacked)."""
    B, S, H, D = K.shape
    z = jnp.repeat(zero.astype(K.dtype), group, axis=1)
    return (K >= z).astype(jnp.uint8)


def pack_bits(bits: jax.Array) -> jax.Array:
    """Pack seq-major bits [B, S, H, D] → uint8[B, S//8, H, D]."""
    B, S, H, D = bits.shape
    b8 = bits.reshape(B, S // 8, 8, H, D)
    shifts = jnp.arange(8, dtype=jnp.uint8).reshape(1, 1, 8, 1, 1)
    return jnp.sum(b8 << shifts, axis=2).astype(jnp.uint8)


def unpack_bits(codes: jax.Array) -> jax.Array:
    """uint8[B, S//8, H, D] → {0,1} uint8[B, S, H, D]."""
    B, S8, H, D = codes.shape
    shifts = jnp.arange(8, dtype=jnp.uint8).reshape(1, 1, 8, 1, 1)
    bits = (codes[:, :, None] >> shifts) & jnp.uint8(1)
    return bits.reshape(B, S8 * 8, H, D)


def quantize(K: jax.Array, group: int = 32) -> QuantizedKeys:
    """Full 1-bit group RTN quantization of a key cache slab."""
    _check_seq(K.shape[1], group)
    scale, zero = group_stats(K, group)
    bits = sign_bits(K, zero, group)
    return QuantizedKeys(pack_bits(bits), scale, zero, group)


def dequant_levels(scale: jax.Array, zero: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The two per-(group, channel) key levels ``(z + s, z − s)`` in bf16.

    Each level is one f32 add of two bf16 values, rounded once to bf16 —
    an expression every backend evaluates identically.  This is the
    score contract shared by the Pallas scorer
    (``kernels.fier_score.score_block``) and the jnp oracle
    (``core.retrieval.approx_scores``): a token's dequantized key is the
    level its code bit selects, and its score is the bf16 dot of the
    bf16 query with it, accumulated in f32."""
    s = scale.astype(jnp.float32)
    z = zero.astype(jnp.float32)
    return (z + s).astype(jnp.bfloat16), (z - s).astype(jnp.bfloat16)


def dequantize(q: QuantizedKeys) -> jax.Array:
    """K̃ ∈ {z−s, z+s}, the level each code bit selects.  bf16 [B, S, H, D]."""
    hi, lo = dequant_levels(q.scale, q.zero)
    bits = unpack_bits(q.codes)
    return jnp.where(
        bits == 1,
        jnp.repeat(hi, q.group, axis=1),
        jnp.repeat(lo, q.group, axis=1),
    )


def packed_nbytes(S: int, H: int, D: int, group: int) -> int:
    """Bytes touched by the score scan per batch element (codes + s/z)."""
    return S // 8 * H * D + 2 * (S // group) * H * D * 2


def load_ratio(group: int) -> float:
    """Paper Eq. 8: key-cache load ratio of the selection pass."""
    return (1.0 + 32.0 / group) / 16.0
