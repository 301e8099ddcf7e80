"""The work one decode step needs, computed from the cell's shapes.

Counts are of what the algorithm needs, never of what the program happens
to do: weights are read once per step at the compute dtype (bf16, 2 bytes
a parameter) whatever dtype they are stored in, every needed byte counts
once, and the FIER side-car is swept once per KV head.  So a later change
that stores bf16 weights, or reads each code once, can approach 100% of
these rooflines and never pass it.

Per decode step, with ``L`` the keys a slot attends over (its resident
tokens, the new one included):

* weights: the FLOPs and bytes the configuration's architecture module
  counts for a batch of B tokens (``weight_work`` in
  ``bench/arch/<config>.py``), since those follow the block: a dense MLP,
  or the routed experts;
* the token's embedding row, in bf16;
* dense attention on the first ``skip_layers`` layers: all ``L`` K and V
  rows of every KV head;
* FIER retrieval on the other layers: 1 bit a key channel plus the bf16
  scale and zero of every ``group`` tokens, per KV head (the paper's
  (1 + 32/g)/16 of the key bytes), and the q·k̃ score FLOPs of every
  query head over all ``L`` tokens;
* FIER attention: ``min(budget, L)`` K and V rows per (slot, KV head),
  the bf16 query in and the f32 output out;
* the new token's K and V written to every layer.

All but the weights depend only on the attention's shapes, so every
configuration's FIER kernels are counted alike.
"""
from __future__ import annotations

import dataclasses
import math

BF16 = 2
F32 = 4


@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    budget: int
    group: int
    skip_layers: int

    @classmethod
    def of(cls, config: dict, deployment: dict) -> "Shapes":
        return cls(
            layers=config["num_hidden_layers"], d_model=config["hidden_size"],
            heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
            budget=deployment["budget"], group=deployment["group"],
            skip_layers=deployment["skip_layers"],
        )


def retrieve(s: Shapes, L: int) -> tuple[float, float]:
    """(FLOPs, bytes) of FIER retrieval for one slot over all FIER layers."""
    n = s.layers - s.skip_layers
    codes = math.ceil(L * s.kv_heads * s.head_dim / 8)
    stats = math.ceil(L / s.group) * s.kv_heads * s.head_dim * 2 * BF16
    return n * 2.0 * s.heads * L * s.head_dim, n * float(codes + stats)


def attend(s: Shapes, L: int) -> tuple[float, float]:
    """(FLOPs, bytes) of FIER select-and-attend for one slot over all FIER
    layers."""
    n = s.layers - s.skip_layers
    k = min(s.budget, L)
    rows = k * s.kv_heads * s.head_dim * 2 * BF16
    qo = s.heads * s.head_dim * (BF16 + F32)
    return n * 4.0 * s.heads * k * s.head_dim, n * float(rows + qo)


def dense(s: Shapes, L: int) -> tuple[float, float]:
    """(FLOPs, bytes) of dense attention for one slot on the skip layers."""
    n = s.skip_layers
    rows = L * s.kv_heads * s.head_dim * 2 * BF16
    qo = s.heads * s.head_dim * 2 * BF16
    return n * 4.0 * s.heads * L * s.head_dim, n * float(rows + qo)


def step(s: Shapes, lengths: list[int], weights: tuple[float, float]) -> dict[str, float]:
    """Work of one batched decode step; ``lengths`` holds, per running
    slot, the keys it attends over, and ``weights`` the (FLOPs, bytes) of
    the block's weights for that batch.  FLOPs and bytes of the whole step
    and of its two FIER kernels."""
    B = len(lengths)
    flops, nbytes = weights
    nbytes += B * s.d_model * BF16                       # embedding rows
    nbytes += B * s.layers * s.kv_heads * s.head_dim * 2 * BF16   # K/V append
    out = {"retrieve_flops": 0.0, "retrieve_bytes": 0.0,
           "attend_flops": 0.0, "attend_bytes": 0.0}
    for L in lengths:
        for part, fn in (("retrieve", retrieve), ("attend", attend)):
            f, b = fn(s, L)
            out[f"{part}_flops"] += f
            out[f"{part}_bytes"] += b
        f, b = dense(s, L)
        flops += f
        nbytes += b
    out["flops"] = flops + out["retrieve_flops"] + out["attend_flops"]
    out["bytes"] = nbytes + out["retrieve_bytes"] + out["attend_bytes"]
    return out


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of FLOPs over peak
    FLOP/s and bytes over peak bytes/s."""
    return max(flops / peak["bf16_flop_per_s"], nbytes / peak["hbm_bytes_per_s"])
