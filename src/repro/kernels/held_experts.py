"""Grouped SwiGLU over the experts this chip holds (the expert layer at a
chip's share, DESIGN.md §Experts at a chip's share).

    y[t] = Σ_e gates[t, e] · (silu(x[t] W1_e) ⊙ (x[t] W3_e)) W2_e

over the ``E_held`` experts stored here; ``gates[t, e]`` is the token's
renormalised router weight for held expert ``e``, 0 where the token does
not route to it.  The grid is (token tile, expert, ff tile); the output
tile stays in VMEM across the experts and ff tiles and accumulates in f32.

A (token tile, expert) pair that no token routes to is skipped: its step
computes nothing, and its weight blocks' index maps point at the blocks
the pipeline already holds (the last ones fetched, or the next expert's
first), so the pipeline issues no DMA and the expert's weights stay
unread.  The per-tile route counts and those fetch targets come in by
scalar prefetch.  A skipped pair would have added exact zeros, so which
tokens share a tile never changes a token's result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# token rows per tile, and expert-hidden columns per ff tile: at d = 4096
# the double-buffered x, output and w1/w3/w2 tiles take ~36 MiB of VMEM
TM = 512
TF = 256
VMEM_LIMIT = 64 * 2**20


def _kernel(counts_ref, src_ref, x_ref, g_ref, w1_ref, w3_ref, w2_ref, o_ref, *,
            n_experts: int):
    t, e, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((e == 0) & (j == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(counts_ref[t * n_experts + e] > 0)
    def _():
        x = x_ref[...]
        a = jnp.dot(x, w1_ref[...], preferred_element_type=jnp.float32)
        b = jnp.dot(x, w3_ref[...], preferred_element_type=jnp.float32)
        h = jax.nn.silu(a) * b * g_ref[...]
        o_ref[...] += jnp.dot(h.astype(w2_ref.dtype), w2_ref[...],
                              preferred_element_type=jnp.float32)


def _fetch_targets(active: jax.Array) -> jax.Array:
    """Per (tile, expert) step in grid order, the step whose weight blocks
    the pipeline holds while it runs: itself when active, else the last
    active step before it, else the first after it (0 if none is)."""
    n = active.shape[0]
    i = jnp.arange(n, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(active, i, -1))
    nxt = jax.lax.cummin(jnp.where(active, i, n), reverse=True)
    return jnp.where(last >= 0, last, jnp.where(nxt < n, nxt, 0)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def held_experts_swiglu(x: jax.Array, gates: jax.Array, w1: jax.Array, w3: jax.Array,
                        w2: jax.Array, *, interpret: bool = True) -> jax.Array:
    """x [T, d]; gates f32 [T, E_held]; w1, w3 [E_held, d, ff]; w2
    [E_held, ff, d] → f32 [T, d]."""
    T, d = x.shape
    E, _, ff = w1.shape
    tf = min(TF, ff)
    assert ff % tf == 0, (ff, tf)
    nf = ff // tf
    tm = min(TM, -(-T // 8) * 8)
    Tp = -(-T // tm) * tm
    nt = Tp // tm
    if Tp != T:
        x = jnp.pad(x, ((0, Tp - T), (0, 0)))
        gates = jnp.pad(gates, ((0, Tp - T), (0, 0)))
    gates = gates.astype(jnp.float32)
    counts = (gates != 0).reshape(nt, tm, E).sum(1, dtype=jnp.int32).reshape(-1)
    src = _fetch_targets(counts > 0)
    g3 = gates.T[:, :, None]                                   # [E, Tp, 1]

    def wmap(t, e, j, counts, src, *, rows: bool):
        s = t * E + e
        at = src[s]
        # a skipped step holds its target's last ff tile when the target
        # ran before it, and the target's first when it runs after
        jj = jnp.where(at == s, j, jnp.where(at < s, nf - 1, 0))
        return (at % E, jj, 0) if rows else (at % E, 0, jj)

    y = pl.pallas_call(
        functools.partial(_kernel, n_experts=E),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nt, E, nf),
            in_specs=[
                pl.BlockSpec((tm, d), lambda t, e, j, c, s: (t, 0)),
                pl.BlockSpec((None, tm, 1), lambda t, e, j, c, s: (e, t, 0)),
                pl.BlockSpec((None, d, tf), functools.partial(wmap, rows=False)),
                pl.BlockSpec((None, d, tf), functools.partial(wmap, rows=False)),
                pl.BlockSpec((None, tf, d), functools.partial(wmap, rows=True)),
            ],
            out_specs=pl.BlockSpec((tm, d), lambda t, e, j, c, s: (t, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((Tp, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name="held_experts_swiglu",
    )(counts, src, x, g3, w1, w3, w2)
    return y[:T]
