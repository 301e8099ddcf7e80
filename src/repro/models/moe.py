"""Top-k routed MoE FFN (Granite 32e/top-8, Qwen3-MoE 128e/top-8).

Serving (prefill, chunked prefill, decode) runs ``moe_held``: the drop-free
expert layer at this chip's share.  The router scores all ``n_experts``;
the layer computes the part of the result that the experts it holds give
(``ModelConfig.expert_first`` / ``experts_held``), through the
``held_experts_swiglu`` kernel, and no token's output depends on the
other tokens of its batch.

Training uses capacity-based scatter dispatch (Megablocks-style, GShard capacity):
tokens are scattered into per-expert buckets ``[E, C, d]``, experts run as
one batched matmul, outputs gather back weighted by the renormalised top-k
router probs.  Overflow tokens drop (capacity_factor bounds memory — the
dump row trick keeps everything shape-static and jit/GSPMD friendly).

Sharding: expert-major tensors (``w1/w2/w3`` and the ``[E·C, d]`` buckets)
shard over the 'model' axis (EP); the roofline hillclimb may swap this for
an explicit shard_map EP path (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops

from .layers import init_linear


def init_moe(rng: jax.Array, cfg: ModelConfig) -> dict:
    """The router over all ``n_experts``, and the held experts' weights."""
    d, ff, E, H = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.n_held
    kr, k1, k2, k3 = jax.random.split(rng, 4)
    s_in, s_out = d**-0.5, ff**-0.5
    return {
        "router": init_linear(kr, d, E),
        "w1": jax.random.normal(k1, (H, d, ff), jnp.float32) * s_in,
        "w3": jax.random.normal(k3, (H, d, ff), jnp.float32) * s_in,
        "w2": jax.random.normal(k2, (H, ff, d), jnp.float32) * s_out,
    }


def route(x: jax.Array, router: jax.Array, cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """Router in f32 over all ``n_experts``: softmax, top-k, the top-k
    weights renormalised (``norm_topk_prob``).  x [T, d] → (gates f32
    [T, E_held] of the held experts, 0 where a token does not route to
    one; routes int32 [T, E_held], 1 where it does)."""
    first, H = cfg.expert_first, cfg.n_held
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    top, eidx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.topk_experts)
    top = top / top.sum(axis=-1, keepdims=True)
    rel = eidx - first
    # a route to an expert held elsewhere one-hots to nothing
    hit = jax.nn.one_hot(jnp.where((rel >= 0) & (rel < H), rel, H), H,
                         dtype=jnp.float32)                      # [T, k, H]
    return (hit * top[..., None]).sum(axis=1), hit.sum(axis=1).astype(jnp.int32)


def moe_held(x: jax.Array, p: dict, cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """The drop-free expert layer at this chip's share: x [T, d] → (y [T, d],
    Σ over the token's top-k experts held here of gate · SwiGLU_e(x);
    counts int32 [2]: routes that landed on held experts, held experts
    touched).  Experts held elsewhere add nothing."""
    gates, routes = route(x, p["router"], cfg)
    w = [p[n].astype(x.dtype) for n in ("w1", "w3", "w2")]
    y = ops.held_experts_swiglu(x, gates, *w)
    per_expert = routes.sum(axis=0)
    counts = jnp.stack([per_expert.sum(), (per_expert > 0).sum()]).astype(jnp.int32)
    return y.astype(x.dtype), counts


def moe_apply(
    x: jax.Array, p: dict, cfg: ModelConfig
) -> tuple[jax.Array, jax.Array]:
    """x: [T, d] (caller flattens batch×seq) → (y [T, d], aux_loss scalar)."""
    T, d = x.shape
    E, k = cfg.n_experts, cfg.topk_experts
    C = max(int(T * k / E * cfg.capacity_factor + 0.999), 1)

    logits = (x.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # [T,E]
    gvals, eidx = jax.lax.top_k(logits, k)  # [T,k]
    gates = jax.nn.softmax(gvals, axis=-1)  # renormalise among top-k

    # position of each (token, k) slot within its expert's bucket
    e_flat = eidx.reshape(-1)  # [T*k]
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)
    pos = jnp.take_along_axis(
        jnp.cumsum(onehot, axis=0) - 1, e_flat[:, None], axis=1
    )[:, 0]
    keep = pos < C
    slot = jnp.where(keep, e_flat * C + pos, E * C)  # E*C = dump row (dropped)

    xrep = jnp.repeat(x, k, axis=0)  # [T*k, d]
    buf = jnp.zeros((E * C + 1, d), x.dtype).at[slot].set(xrep)
    hb = buf[: E * C].reshape(E, C, d)
    h1 = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", hb, p["w1"].astype(x.dtype))
    ) * jnp.einsum("ecd,edf->ecf", hb, p["w3"].astype(x.dtype))
    ob = jnp.einsum("ecf,efd->ecd", h1, p["w2"].astype(x.dtype)).reshape(E * C, d)
    ob = jnp.concatenate([ob, jnp.zeros((1, d), ob.dtype)], axis=0)
    y_slots = ob[slot] * keep[:, None].astype(ob.dtype)  # dropped → 0
    y = (y_slots.reshape(T, k, d) * gates[..., None].astype(ob.dtype)).sum(axis=1)

    # load-balancing aux (Switch-style): E · Σ_e f_e · P_e
    probs = jax.nn.softmax(logits, axis=-1)
    f = jnp.mean(onehot.reshape(T, k, E).sum(axis=1).astype(jnp.float32), axis=0)
    P = probs.mean(axis=0)
    aux = E * jnp.sum(f / k * P)
    return y.astype(x.dtype), aux


# --------------------------------------------------------------------- EP

def moe_apply_ep(
    x: jax.Array,
    p: dict,
    cfg: ModelConfig,
    *,
    mesh,
    token_axes: tuple[str, ...],
    model_axis: str = "model",
    fsdp_axes: tuple[str, ...] = (),
) -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE via shard_map (train/prefill path at pod scale).

    Communication-free dispatch: expert weights shard E over ``model_axis``
    and are *replicated over the data axes* (mod FSDP storage), so every
    (data, model) device runs its own data shard's tokens through its own
    expert shard — no all-to-all.  Combine = one psum over ``model_axis``
    (merges with the TP all-reduce pattern).  FSDP-stored expert weights
    all-gather over ``fsdp_axes`` inside the body (ZeRO-3 semantics).

    Memory per device is bounded by construction:
    T_loc·k·capacity_factor·d dispatch buffer — the GSPMD scatter
    pathology of ``moe_apply`` at 1M tokens cannot occur (DESIGN.md §4).
    """
    from jax.sharding import PartitionSpec as P

    E, k = cfg.n_experts, cfg.topk_experts
    n_model = mesh.shape[model_axis]
    E_loc = E // n_model
    tok = tuple(token_axes) if token_axes else None
    f_ax = tuple(fsdp_axes) if fsdp_axes else ()
    all_axes = tuple(a for a in mesh.axis_names)

    def body(xl, router, w1, w3, w2):
        if f_ax:
            w1 = jax.lax.all_gather(w1, f_ax, axis=1, tiled=True)
            w3 = jax.lax.all_gather(w3, f_ax, axis=1, tiled=True)
            w2 = jax.lax.all_gather(w2, f_ax, axis=2, tiled=True)
        T_loc, d = xl.shape
        C = max(int(T_loc * k / E * cfg.capacity_factor + 0.999), 1)
        logits = xl.astype(jnp.float32) @ router
        gvals, eidx = jax.lax.top_k(logits, k)
        gates = jax.nn.softmax(gvals, axis=-1)
        midx = jax.lax.axis_index(model_axis)
        e_flat = eidx.reshape(-1)
        e_rel = e_flat - midx * E_loc
        local = (e_rel >= 0) & (e_rel < E_loc)
        e_loc = jnp.where(local, e_rel, E_loc)
        onehot = jax.nn.one_hot(e_loc, E_loc + 1, dtype=jnp.int32)
        pos = jnp.take_along_axis(
            jnp.cumsum(onehot, axis=0) - 1, e_loc[:, None], axis=1
        )[:, 0]
        keep = local & (pos < C)
        slot = jnp.where(keep, e_loc * C + pos, E_loc * C)
        # dispatch as scatter-of-INDICES + gather (never materialises the
        # [T·k, d] repeat — 4.3 GB/layer on qwen3; §Perf iteration 4):
        # empty slots point at a zero row of the padded tokens
        slot_tok = (
            jnp.full((E_loc * C + 1,), T_loc, jnp.int32)
            .at[slot]
            .set(jnp.arange(e_flat.shape[0], dtype=jnp.int32) // k)
        )
        xp = jnp.concatenate([xl, jnp.zeros((1, d), xl.dtype)], axis=0)
        hb = xp[slot_tok[: E_loc * C]].reshape(E_loc, C, d)
        h1 = jax.nn.silu(
            jnp.einsum("ecd,edf->ecf", hb, w1.astype(xl.dtype))
        ) * jnp.einsum("ecd,edf->ecf", hb, w3.astype(xl.dtype))
        ob = jnp.einsum("ecf,efd->ecd", h1, w2.astype(xl.dtype)).reshape(-1, d)
        ob = jnp.concatenate([ob, jnp.zeros((1, d), ob.dtype)], axis=0)
        # combine unrolled over k: k gathers of [T_loc, d] instead of one
        # [T_loc·k, d] materialisation
        slot_t = slot.reshape(T_loc, k)
        gk = gates.astype(ob.dtype)
        y_part = sum(ob[slot_t[:, j]] * gk[:, j, None] for j in range(k))
        y = jax.lax.psum(y_part, model_axis)
        # aux loss: local estimate, averaged over every mesh shard
        probs = jax.nn.softmax(logits, axis=-1)
        ffrac = jnp.mean(
            jax.nn.one_hot(e_flat, E).reshape(T_loc, k, E).sum(1), axis=0
        )
        aux_local = E * jnp.sum(ffrac / k * probs.mean(0))
        aux = jax.lax.pmean(aux_local, all_axes)
        return y, aux

    f = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(tok, None),
            P(None, None),
            P(model_axis, f_ax if f_ax else None, None),
            P(model_axis, f_ax if f_ax else None, None),
            P(model_axis, None, f_ax if f_ax else None),
        ),
        out_specs=(P(tok, None), P()),
        check_vma=False,
    )
    return f(x, p["router"], p["w1"], p["w3"], p["w2"])
