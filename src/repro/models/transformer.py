"""Decoder-only LM (dense / MoE / VLM-backbone) with FIER-integrated decode.

Design points:
  * stacked layer params + ``lax.scan`` — HLO depth-independent;
  * train/prefill use blocked flash attention (no S×S materialisation);
  * decode splits the stack into front (full attention, the paper's
    skip-layers) and rest (policy: fier/quest/full) — two scans, so the
    compiled decode HLO contains each attention flavour once;
  * cross-entropy is sequence-chunked (never materialises [B,S,V] logits);
  * vocab padded to a sharding-friendly multiple; padded columns masked.

Batch formats (produced by repro.data / launch.input_specs):
  train:   {tokens [B,St], targets [B,S], loss_mask [B,S], vision_embeds?}
  prefill: {tokens [B,St], lengths [B], vision_embeds?}
  decode:  token [B] + cache pytree
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, padded_vocab
from repro.core.policy import DecodePlan, PolicyConfig
from repro.kvcache import cache as kvcache
from repro.kvcache import paged as kvcache_paged

from . import attention as attn
from . import moe as moe_mod
from .tuning import maybe_scan
from .layers import apply_norm, init_embedding, init_mlp, init_norm, mlp_apply

MOE_AUX_COEF = 0.01


@dataclasses.dataclass
class ModelBundle:
    cfg: ModelConfig
    init: Callable
    train_loss: Callable          # (params, batch) -> (loss, metrics)
    prefill: Callable             # (params, batch) -> (logits [B,Vp], cache)
    decode_step: Callable         # (params, token [B], cache) -> (logits, cache)
    init_cache: Callable          # (B, capacity, length) -> cache
    param_count: Callable
    policy: "PolicyConfig | None" = None  # the cache policy the bundle was
                                          # built with (engine introspects
                                          # layout/block_size from here)
    plan: "DecodePlan | None" = None      # the resolved DecodePlan the
                                          # decode path dispatches through
    prefill_chunk: "Callable | None" = None  # (params, batch, cache, *,
                                             # final) -> (logits|None, cache)
                                             # chunked-prefill step; None on
                                             # families without it


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


def build(
    cfg: ModelConfig,
    pol: PolicyConfig | None = None,
    dcfg: attn.DistConfig | None = None,
    *,
    remat: bool = True,
    loss_chunk: int = 1024,
) -> ModelBundle:
    pol = pol or PolicyConfig(kind="full")
    # resolve + validate the decode plan once (capability matrix, paged
    # block-size rules); capacity-dependent checks re-run in init_cache.
    # A mesh sharding spec (dcfg.shard) rides on the plan — the front-scan
    # full layers share the same sharded pool, so plan_full carries it too
    shard = dcfg.shard if dcfg is not None and pol.layout == "paged" else None
    plan = DecodePlan.build(pol, shard=shard)
    pol_full = PolicyConfig(
        kind="full", skip_layers=0,
        layout=pol.layout, block_size=pol.block_size,
        pool_blocks=pol.pool_blocks,
    )
    plan_full = DecodePlan.build(pol_full, shard=shard)
    Vp = padded_vocab(cfg)
    cdt = _dtype(cfg.compute_dtype)
    pdt = _dtype(cfg.param_dtype)
    skip = min(pol.skip_layers if pol.kind != "full" else 0, cfg.n_layers)
    is_moe = cfg.family == "moe"

    # ----------------------------------------------------------------- init
    def init_layer(rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        p = {
            "norm1": init_norm(cfg.norm, cfg.d_model),
            "attn": attn.init_attention(k1, cfg),
            "norm2": init_norm(cfg.norm, cfg.d_model),
        }
        if is_moe:
            p["moe"] = moe_mod.init_moe(k2, cfg)
        else:
            p["mlp"] = init_mlp(k3, cfg.d_model, cfg.d_ff, cfg.act)
        return p

    def init(rng):
        ke, kl, kh = jax.random.split(rng, 3)
        layers = jax.vmap(init_layer)(jax.random.split(kl, cfg.n_layers))
        params = {
            "embed": init_embedding(ke, Vp, cfg.d_model),
            "layers": layers,
            "final_norm": init_norm(cfg.norm, cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = init_embedding(kh, Vp, cfg.d_model).T
        return jax.tree.map(lambda a: a.astype(pdt), params)

    # ------------------------------------------------------------- helpers
    def _embed_inputs(params, batch):
        toks = batch["tokens"]
        h = jnp.take(params["embed"], toks, axis=0).astype(cdt)  # [B,St,d]
        if "vision_embeds" in batch and batch["vision_embeds"] is not None:
            h = jnp.concatenate([batch["vision_embeds"].astype(cdt), h], axis=1)
        # pin the layout after the vocab-sharded gather (§Perf iteration 11)
        return attn.seq_shard_constraint(h, dcfg)

    def _head(params):
        if cfg.tie_embeddings:
            return params["embed"].T
        return params["lm_head"]

    def _ffn(lp, x2, B, S, mode="train"):
        if not is_moe:
            return mlp_apply(x2, lp["mlp"], cfg.act), jnp.float32(0.0)
        x2d = x2.reshape(B * S, cfg.d_model)
        if mode == "decode":
            # T ≈ batch: dense-masked einsum path (GSPMD-friendly, no scatter)
            y, aux = moe_mod.moe_apply_masked(x2d, lp["moe"], cfg)
        elif dcfg is not None and dcfg.ep_axis is not None:
            # pod scale: shard_map expert parallelism
            tok = tuple(dcfg.batch_axes)
            y, aux = moe_mod.moe_apply_ep(
                x2d, lp["moe"], cfg, mesh=dcfg.mesh, token_axes=tok,
                model_axis=dcfg.ep_axis, fsdp_axes=tuple(dcfg.fsdp_axes),
            )
        else:
            y, aux = moe_mod.moe_apply(x2d, lp["moe"], cfg)
        return y.reshape(B, S, cfg.d_model), aux

    # --------------------------------------------------------------- train
    def _layer_train(h, lp):
        B, S, _ = h.shape
        a = attn.attention_train(lp["attn"], apply_norm(h, lp["norm1"], cfg.norm), cfg)
        h = h + a
        y, aux = _ffn(lp, apply_norm(h, lp["norm2"], cfg.norm), B, S)
        # sequence-parallel residual stream: bounds the remat-saved
        # activation at L·B·S·d/(data·model) per device
        return attn.seq_shard_constraint(h + y, dcfg), aux

    layer_train = (
        jax.checkpoint(_layer_train, policy=jax.checkpoint_policies.nothing_saveable)
        if remat
        else _layer_train
    )

    def train_loss(params, batch):
        h = _embed_inputs(params, batch)
        h, auxs = maybe_scan(layer_train, h, params["layers"])
        h = apply_norm(h, params["final_norm"], cfg.norm)
        loss, n_tok = _chunked_ce(
            h, _head(params), batch["targets"], batch["loss_mask"], cfg.vocab, Vp,
            loss_chunk,
        )
        aux = auxs.mean() if is_moe else jnp.float32(0.0)
        total = loss + MOE_AUX_COEF * aux
        return total, {"loss": loss, "moe_aux": aux, "tokens": n_tok}

    # ------------------------------------------------------------- prefill
    def prefill(params, batch, capacity: int | None = None):
        """Returns (last-token logits [B, Vp], filled cache).  ``capacity``
        is static (jit with functools.partial)."""
        lengths = batch["lengths"]
        h = _embed_inputs(params, batch)
        B, S, _ = h.shape
        cap = capacity if capacity is not None else S
        valid = kvcache.valid_mask(S, lengths)

        def layer_fn(hc, lp):
            xn = apply_norm(hc, lp["norm1"], cfg.norm)
            q, k, v = attn.qkv_proj(lp["attn"], xn, cfg, positions=None)
            o = attn.flash_attention(q, k, v, causal=True, bias_mask=valid)
            o = o.reshape(B, S, cfg.n_heads * cfg.d_head) @ lp["attn"]["wo"].astype(hc.dtype)
            hc = hc + o
            y, _ = _ffn(lp, apply_norm(hc, lp["norm2"], cfg.norm), B, S)
            pad = ((0, 0), (0, cap - S), (0, 0), (0, 0))
            return attn.seq_shard_constraint(hc + y, dcfg), (
                jnp.pad(k.astype(jnp.bfloat16), pad),
                jnp.pad(v.astype(jnp.bfloat16), pad),
            )

        h, (K, V) = maybe_scan(layer_fn, h, params["layers"])  # K: [L,B,cap,H,D]
        h = apply_norm(h, params["final_norm"], cfg.norm)
        cache = _assemble_cache(K, V, lengths)
        last = jnp.take_along_axis(h, (lengths - 1)[:, None, None], axis=1)[:, 0]
        logits = _masked_logits(last, _head(params), cfg.vocab, Vp)
        return logits, cache

    def _assemble_cache(K, V, lengths):
        front = {"k": K[:skip], "v": V[:skip]}
        rest = {"k": K[skip:], "v": V[skip:]}
        if pol.kind in ("fier", "quest"):
            from repro.core.policy import build_metadata

            rest["meta"] = jax.vmap(lambda Kl: build_metadata(Kl, pol))(rest["k"])
        return {"front": front, "rest": rest, "length": lengths}

    def init_cache(B, capacity, length):
        # capacity-dependent plan validation happens here, where capacity
        # is first known (budget/sink/recent bounds, block divisibility)
        plan.validate_capacity(capacity)
        if pol.layout == "paged":
            # one block pool shared by every request: a physical block id
            # indexes the same row of every layer's pool slab, and the
            # per-request [B, capacity/bs] block table (all-zeros = the
            # reserved null block) is the only per-slot state
            bs = pol.block_size
            if capacity % bs:
                raise ValueError(
                    f"capacity {capacity} not divisible by block_size {bs}"
                )
            n_btab = capacity // bs
            n_blocks = pol.pool_blocks or (B * n_btab + 1)
            return {
                "front": kvcache_paged.init_paged_pool(
                    skip, n_blocks, bs, cfg.n_kv_heads, cfg.d_head, None
                ),
                "rest": kvcache_paged.init_paged_pool(
                    cfg.n_layers - skip, n_blocks, bs, cfg.n_kv_heads,
                    cfg.d_head, pol if pol.kind != "full" else None,
                ),
                "length": jnp.full((B,), length, jnp.int32),
                "block_table": jnp.zeros((B, n_btab), jnp.int32),
            }
        c = {
            "front": kvcache.init_layer_cache(
                skip, B, capacity, cfg.n_kv_heads, cfg.d_head, None
            ),
            "rest": kvcache.init_layer_cache(
                cfg.n_layers - skip, B, capacity, cfg.n_kv_heads, cfg.d_head,
                pol if pol.kind != "full" else None,
            ),
            "length": jnp.full((B,), length, jnp.int32),
        }
        return c

    # ------------------------------------------------------ chunked prefill
    def prefill_chunk(params, batch, cache, *, final: bool):
        """One prompt chunk for a single slot of the *batched* cache.

        batch = {tokens [1,n], start, slot, total, table_row? [n_btab]}:
        the chunk covers logical positions [start, start+n) of a prompt of
        ``total`` tokens.  The chunk's K/V are appended through the cache
        layout's addressing (slab row write / block-table scatter), then
        each layer attends over the logical prefix with ``q_offset=start``
        — flash attention's masked keys contribute exact zeros, so row i
        sees precisely keys 0..i and the hidden states are bit-identical
        to a monolithic prefill of the same prompt (bf16 compute: the
        cache round-trip is lossless).

        Only the final chunk produces logits: it zeroes the slab/tail-
        block rows beyond ``total`` (matching monolithic prefill's zero
        padding, so selection-group statistics straddling the prompt end
        agree), rebuilds the selection metadata over the full logical key
        row, publishes ``length[slot] = total`` and (paged) the device
        block-table row.  Non-final chunks return (None, cache) and leave
        length untouched, so interleaved decode steps keep routing this
        slot's scratch writes into masked rows.
        """
        toks = batch["tokens"]                  # [1, n]
        start = batch["start"]                  # scalar int32
        slot = batch["slot"]                    # scalar int32
        total = batch["total"]                  # scalar int32
        table_row = batch.get("table_row")      # [n_btab] int32 (paged)
        paged = pol.layout == "paged"
        h = jnp.take(params["embed"], toks, axis=0).astype(cdt)  # [1,n,d]
        n = h.shape[1]
        positions = (start + jnp.arange(n, dtype=jnp.int32))[None]
        if paged:
            bs = pol.block_size
            phys = table_row[positions[0] // bs]                 # [n]
            offs = positions[0] % bs
        if shard is not None:           # paged layout on a mesh
            replicated = jax.sharding.NamedSharding(
                shard.mesh, jax.sharding.PartitionSpec()
            )

        def chunk_body(hc, xs):
            lp, lc = xs
            xn = apply_norm(hc, lp["norm1"], cfg.norm)
            q, k, v = attn.qkv_proj(lp["attn"], xn, cfg, positions=positions)
            kc, vc = k.astype(lc["k"].dtype), v.astype(lc["v"].dtype)
            if paged:
                lck = lc["k"].at[phys, offs].set(kc[0])
                lcv = lc["v"].at[phys, offs].set(vc[0])
                Kl = kvcache_paged.gather_block_rows(lck, table_row[None])
                Vl = kvcache_paged.gather_block_rows(lcv, table_row[None])
                if shard is not None:
                    # gathered from a mesh-sharded pool: replicate before
                    # attention so the wo contraction reduces in the same
                    # order as the single-device prefill (bit-identity)
                    Kl = jax.lax.with_sharding_constraint(Kl, replicated)
                    Vl = jax.lax.with_sharding_constraint(Vl, replicated)
            else:
                lck = jax.lax.dynamic_update_slice(lc["k"], kc, (slot, start, 0, 0))
                lcv = jax.lax.dynamic_update_slice(lc["v"], vc, (slot, start, 0, 0))
                Kl = jax.lax.dynamic_index_in_dim(lck, slot, axis=0, keepdims=True)
                Vl = jax.lax.dynamic_index_in_dim(lcv, slot, axis=0, keepdims=True)
            cap = Kl.shape[1]
            valid = (jnp.arange(cap, dtype=jnp.int32) < start + n)[None]
            o = attn.flash_attention(
                q, Kl, Vl, causal=True, q_offset=start, bias_mask=valid
            )
            if shard is not None:
                # q inherits the pool's head sharding from the fused qkv
                # projection; left sharded, wo would contract over a split
                # head axis and all-reduce partial sums in bf16
                o = jax.lax.with_sharding_constraint(o, replicated)
            o = o.reshape(1, n, cfg.n_heads * cfg.d_head) @ lp["attn"]["wo"].astype(hc.dtype)
            hc = hc + o
            y, _ = _ffn(lp, apply_norm(hc, lp["norm2"], cfg.norm), 1, n)
            new_lc = dict(lc, k=lck, v=lcv)
            if final:
                row_valid = (jnp.arange(cap, dtype=jnp.int32) < total)
                rmask = row_valid[None, :, None, None]
                Kz = jnp.where(rmask, Kl, 0).astype(Kl.dtype)
                Vz = jnp.where(rmask, Vl, 0).astype(Vl.dtype)
                if paged:
                    nb = table_row.shape[0]

                    def put_blocks(pool, val):
                        pb = pool.shape[1]
                        return pool.at[table_row].set(
                            val[0].reshape(nb, pb, *val.shape[2:]).astype(pool.dtype)
                        )

                    new_lc["k"] = put_blocks(new_lc["k"], Kz)
                    new_lc["v"] = put_blocks(new_lc["v"], Vz)
                else:
                    new_lc["k"] = jax.lax.dynamic_update_index_in_dim(
                        new_lc["k"], Kz[0], slot, 0
                    )
                    new_lc["v"] = jax.lax.dynamic_update_index_in_dim(
                        new_lc["v"], Vz[0], slot, 0
                    )
                if "meta" in lc:
                    from repro.core.policy import build_metadata

                    mv = build_metadata(Kz, pol)
                    if paged:
                        new_lc["meta"] = jax.tree.map(
                            put_blocks, new_lc["meta"], mv
                        )
                    else:
                        new_lc["meta"] = jax.tree.map(
                            lambda pool, val: jax.lax.dynamic_update_index_in_dim(
                                pool, val[0].astype(pool.dtype), slot, 0
                            ),
                            new_lc["meta"], mv,
                        )
            return hc + y, new_lc

        h, front_cache = _layer_loop(
            chunk_body, h, params["layers"], cache["front"], 0
        )
        h, rest_cache = _layer_loop(
            chunk_body, h, params["layers"], cache["rest"], skip
        )
        new_cache = dict(cache, front=front_cache, rest=rest_cache)
        if not final:
            return None, new_cache
        new_cache["length"] = cache["length"].at[slot].set(total)
        if "block_table" in cache:
            new_cache["block_table"] = cache["block_table"].at[slot].set(table_row)
        h = apply_norm(h, params["final_norm"], cfg.norm)[:, n - 1]
        return _masked_logits(h, _head(params), cfg.vocab, Vp), new_cache

    # -------------------------------------------------------------- decode
    def decode_step(params, token, cache):
        length = cache["length"]
        # paged layout: the per-request block table rides in the cache
        # pytree (host-updated between steps by the engine's allocator)
        # and is closed over by both layer scans — it has no layer axis
        block_table = (
            cache.get("block_table") if plan.layout == "paged" else None
        )
        x = jnp.take(params["embed"], token, axis=0)[:, None, :].astype(cdt)
        B = x.shape[0]

        def mk_body(layer_plan, use_dist):
            def body(h, xs):
                lp, lc = xs
                o, lc = attn.decode_self_attention(
                    lp["attn"], apply_norm(h, lp["norm1"], cfg.norm), lc, length,
                    cfg, layer_plan, dcfg if use_dist else None,
                    block_table=block_table,
                )
                h = h + o
                y, _ = _ffn(lp, apply_norm(h, lp["norm2"], cfg.norm), B, 1, "decode")
                return h + y, lc

            return body

        h, front_cache = _layer_loop(
            mk_body(plan_full, use_dist=False), x, params["layers"],
            cache["front"], 0,
        )
        h, rest_cache = _layer_loop(
            mk_body(plan, use_dist=True), h, params["layers"], cache["rest"],
            skip,
        )
        h = apply_norm(h, params["final_norm"], cfg.norm)[:, 0]
        logits = _masked_logits(h, _head(params), cfg.vocab, Vp)
        new_cache = {
            "front": front_cache,
            "rest": rest_cache,
            "length": length + 1,
        }
        if block_table is not None:
            new_cache["block_table"] = block_table
        return logits, new_cache

    return ModelBundle(
        cfg=cfg,
        init=init,
        train_loss=train_loss,
        prefill=prefill,
        decode_step=decode_step,
        init_cache=init_cache,
        param_count=cfg.param_count,
        policy=pol,
        plan=plan,
        prefill_chunk=prefill_chunk,
    )


# ------------------------------------------------------------- layer loop

def _layer_loop(body, h, layer_params, cache_stack, first: int):
    """Run ``body(h, (params_l, cache_l)) -> (h, cache_l)`` over the layers
    of a stacked cache, with the stack carried through the loop and each
    layer written back in place.

    Layer ``l`` of the stack uses ``layer_params`` layer ``first + l``,
    read by dynamic index from the full stack.  Scanning the cache as
    ``xs``/``ys`` (and slicing ``params[first:]``) instead makes XLA
    materialise a second copy of the whole KV pool and of the weights: at
    olmo-1b widths with a 4 GiB pool that no longer fits a 16 GB chip.
    """
    leaves = jax.tree.leaves(cache_stack)
    n = leaves[0].shape[0] if leaves else 0
    if n == 0:
        return h, cache_stack

    def take(tree, i):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), tree
        )

    def step(carry, i):
        hc, stack = carry
        hc, lc = body(hc, (take(layer_params, first + i), take(stack, i)))
        stack = jax.tree.map(
            lambda a, x: jax.lax.dynamic_update_index_in_dim(
                a, x.astype(a.dtype), i, 0
            ),
            stack, lc,
        )
        return (hc, stack), None

    (h, cache_stack), _ = maybe_scan(
        step, (h, cache_stack), jnp.arange(n, dtype=jnp.int32)
    )
    return h, cache_stack


# ---------------------------------------------------------------- CE / head

def _vocab_col_mask(vocab: int, Vp: int) -> jax.Array:
    return jnp.where(jnp.arange(Vp) < vocab, 0.0, -1e30).astype(jnp.float32)


def _masked_logits(h: jax.Array, W: jax.Array, vocab: int, Vp: int) -> jax.Array:
    logits = h.astype(jnp.float32) @ W.astype(jnp.float32)
    return logits + _vocab_col_mask(vocab, Vp)


def _chunked_ce(
    h: jax.Array,
    W: jax.Array,
    targets: jax.Array,
    mask: jax.Array,
    vocab: int,
    Vp: int,
    chunk: int,
) -> tuple[jax.Array, jax.Array]:
    """Sequence-chunked CE: logits live one [B, chunk, Vp] slice at a time."""
    B, S, d = h.shape
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    nc = S // chunk
    hc = jnp.moveaxis(h.reshape(B, nc, chunk, d), 1, 0)
    tc = jnp.moveaxis(targets.reshape(B, nc, chunk), 1, 0)
    mc = jnp.moveaxis(mask.reshape(B, nc, chunk).astype(jnp.float32), 1, 0)
    col_mask = _vocab_col_mask(vocab, Vp)
    Wf = W.astype(jnp.float32)

    # remat per chunk: the backward recomputes this chunk's logits instead
    # of keeping [B, chunk, Vp] per chunk alive across the whole scan
    @jax.checkpoint
    def body(carry, xs):
        hs, ts, ms = xs
        logits = hs.astype(jnp.float32) @ Wf + col_mask
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ts[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * ms
        return (carry[0] + nll.sum(), carry[1] + ms.sum()), None

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), (hc, tc, mc)
    )
    return tot / jnp.maximum(cnt, 1.0), cnt
