"""Serving engine: jitted prefill/decode around a ModelBundle, with
slot-based continuous batching support.

The decode step is the FIER fast path: policy-dispatched attention over
the cache slabs (optionally sequence-sharded across the mesh).  The
*default* serving policy (``serving_policy`` / ``Engine.build``) is the
one-pass fused pipeline: a single Pallas retrieval kernel (1-bit score
scan + GQA group-reduce + masking + exact radix threshold top-k — the
per-token score tensors never touch HBM) chained into in-kernel row
gather + attention (no materialised K'/V' copies) — see DESIGN.md
§One-pass retrieval and §Fused decode.

Slot insertion runs a B=1 prefill and scatters the resulting cache into
the batched cache; the batch axis of every cache leaf is discovered
automatically by diffing ``init_cache`` shapes at two batch sizes (no
per-model bookkeeping).

Paged mode (``Engine.build(..., layout='paged')``; DESIGN.md §Paged KV
cache): the cache is a shared block pool + per-request block tables, the
engine owns the host-side ``BlockAllocator`` (prefix sharing via chained
block hashes, full-prompt hits skip prefill entirely, copy-on-write on
shared tails), and insertion scatters the prefilled slab block-wise into
the pool — HBM is bounded by tokens resident, not slots × capacity.
"""
from __future__ import annotations

import dataclasses
import time
import weakref
from collections import Counter, OrderedDict
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import DecodePlan, PolicyConfig
from repro.kvcache.offload import HostOffloadTier, double_buffered_puts, to_host
from repro.kvcache.paged import (
    NULL_BLOCK,
    AllocatorAuditError,
    BlockAllocator,
    SeqBlocks,
    block_hash_chain,
)
from repro.models.model_zoo import ModelBundle
from repro.obs import Observability

MAX_CACHED_PROMPT_LOGITS = 1024  # LRU bound on the full-prompt logits cache

# Every live engine, for the test-suite allocator-audit fixture: conftest
# sweeps this after each test and asserts a drained engine leaked nothing.
_LIVE_ENGINES: "weakref.WeakSet[Engine]" = weakref.WeakSet()


class PoolExhausted(RuntimeError):
    """The block pool ran dry mid-operation (insert raced a concurrent
    consumer, or a fault-injected allocation failure).  The operation has
    been rolled back — the caller can re-queue and retry."""


def serving_policy(
    budget: int = 1024,
    group: int = 32,
    *,
    skip_layers: int = 2,
    sink: int = 4,
    recent: int = 64,
    pipeline: str = "one_pass",
    layout: str = "slab",
    fused: bool | None = None,
    one_pass: bool | None = None,
) -> PolicyConfig:
    """The serving-default FIER policy: the ``one_pass`` pipeline (score
    scan + group-reduce + mask + exact threshold top-k in a single
    kernel — per-token scores never touch HBM) chained into the fused
    select-and-attend kernel, with the standard sink/recent guard-rails
    for generation quality.  ``pipeline='two_pass'`` keeps the two-pass
    kernel retrieval (score tensor materialised between kernels);
    ``pipeline='reference'`` is the unfused top-k + gather oracle.
    ``layout='paged'`` serves from the block-pool cache.

    The pre-registry ``fused`` / ``one_pass`` booleans are accepted as
    deprecated aliases and translated onto ``pipeline``."""
    if fused is not None or one_pass is not None:
        from repro.core.policy import _warn_deprecated

        _warn_deprecated(
            "serving_policy's `fused` / `one_pass` booleans",
            "pipeline='reference'|'two_pass'|'one_pass'",
        )
        if fused is False:
            pipeline = "reference"
        elif one_pass is False:
            pipeline = "two_pass"
        else:
            pipeline = "one_pass"
    return PolicyConfig(
        kind="fier", budget=budget, group=group, skip_layers=skip_layers,
        sink=sink, recent=recent, pipeline=pipeline, layout=layout,
    )


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    temperature: float = 0.0   # 0 → greedy
    top_k: int = 0             # 0 → no truncation


def sample_token(rng, logits: jax.Array, cfg: SamplingConfig) -> jax.Array:
    if cfg.temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    l = logits / cfg.temperature
    if cfg.top_k > 0:
        kth = jax.lax.top_k(l, cfg.top_k)[0][..., -1:]
        l = jnp.where(l < kth, -1e30, l)
    return jax.random.categorical(rng, l, axis=-1).astype(jnp.int32)


def _cache_batch_axes(bundle: ModelBundle, capacity: int) -> Any:
    """Pytree of batch-axis indices, discovered by shape-diffing."""
    c2 = jax.eval_shape(lambda: bundle.init_cache(2, capacity, 0))
    c3 = jax.eval_shape(lambda: bundle.init_cache(3, capacity, 0))

    def axis(a, b):
        diffs = [i for i, (x, y) in enumerate(zip(a.shape, b.shape)) if x != y]
        if len(diffs) != 1:
            raise ValueError(f"ambiguous batch axis: {a.shape} vs {b.shape}")
        return diffs[0]

    return jax.tree.map(axis, c2, c3)


class Engine:
    """Batched generation engine with continuous-batching slot management."""

    def __init__(
        self,
        bundle: ModelBundle,
        *,
        n_slots: int,
        capacity: int,
        sampling: SamplingConfig = SamplingConfig(),
        donate_cache: bool = True,
        seed: int = 0,
        degrade_floor: int = 64,
        restore_free_frac: float = 0.5,
        obs: Observability | None = None,
        offload_blocks: int = 0,
        prefix_ttl: float | None = None,
        recall_cost: float = 1.0,
        shard: Any = None,
        dcfg: Any = None,
    ):
        self.bundle = bundle
        # observability bundle (DESIGN.md §Observability): shared metrics
        # registry + tracer.  The default is the disabled bundle — no-op
        # instruments, null tracer — so an un-instrumented engine runs
        # the identical host path and jitted functions as before.
        self.obs = obs if obs is not None else Observability.disabled()
        self.n_slots = n_slots
        self.capacity = capacity
        self.sampling = sampling
        # fallback sampling rng: split per decode call so stochastic
        # sampling never reuses a key (callers may still pass rng=...)
        self._rng = jax.random.PRNGKey(seed)
        pol = bundle.policy
        self.paged = bool(pol is not None and pol.layout == "paged")
        # mesh sharding (DESIGN.md §Sharded serving): `shard` is the
        # kvcache.sharded.ShardSpec the bundle's plan carries; `dcfg` is
        # kept so budget-ladder bundle rebuilds preserve the sharding
        self.shard = shard
        self._dcfg = dcfg
        self._n_dp = shard.n_dp if shard is not None else 1
        if shard is not None and not self.paged:
            raise ValueError("mesh-sharded serving requires layout='paged'")
        if self._n_dp > 1 and n_slots % self._n_dp:
            raise ValueError(
                f"n_slots {n_slots} not divisible by {self._n_dp} DP shards"
            )
        self._slots_per_shard = n_slots // max(1, self._n_dp)
        if bundle.plan is not None:
            # fail fast at engine construction instead of deep inside the
            # first decode kernel (budget/sink/recent vs capacity)
            bundle.plan.validate_capacity(capacity)
        self._prefill = jax.jit(partial(bundle.prefill, capacity=capacity))
        self._donate = (2,) if donate_cache else ()
        self._decode, self._decode_active = self._make_decode_fns(bundle)

        # graceful-degradation budget ladder (DESIGN.md §Serving fault
        # tolerance): under pool pressure the scheduler halves the
        # retrieval budget down to ``degrade_floor`` (rebuilding the
        # decode fns from a plan-validated policy), restoring the full
        # budget once the free pool recovers past ``restore_free_frac``
        self.base_budget = pol.budget if pol is not None else 0
        self.current_budget = self.base_budget
        self.degrade_floor = max(1, degrade_floor)
        self.restore_free_frac = restore_free_frac
        self.downshifts = 0
        self.restores = 0
        self.blocks_shed = 0
        # prefill/prefix accounting lives on both layouts (engine_stats()
        # reports it for slab engines too; prefix_hits stays 0 there — the
        # prefix cache is a paged-pool feature)
        self.prefill_count = 0
        self.prefix_hits = 0
        self._budget_fns = {self.base_budget: (self._decode, self._decode_active)}

        # chunked prefill (ContinuousScheduler's token quantum): one jitted
        # step per (final?) flavour — jax retraces per chunk length
        self._chunk_jits: dict[bool, Any] = {}
        self._chunk_keys: dict[int, list[int]] = {}
        self._set_length = jax.jit(self._set_length_impl, donate_argnums=(0,))

        if self.paged:
            # paged mode: slot insertion scatters prefix blocks into the
            # shared pool through the allocator instead of writing one
            # batch row, so the batch-axis discovery is neither possible
            # (pool leaves have no batch axis) nor needed
            self.block_size = pol.block_size
            if capacity % self.block_size:
                raise ValueError(
                    f"capacity {capacity} not divisible by "
                    f"block_size {self.block_size}"
                )
            self.n_btab = capacity // self.block_size
            # sharded pools reserve one null block per DP shard
            self.pool_blocks = pol.pool_blocks or (
                n_slots * self.n_btab + max(1, self._n_dp)
            )
            if self._n_dp > 1 and self.pool_blocks % self._n_dp:
                raise ValueError(
                    f"pool_blocks {self.pool_blocks} not divisible by "
                    f"{self._n_dp} DP shards"
                )
            if self.pool_blocks // max(1, self._n_dp) - 1 < self.n_btab:
                # undersized pool: a request can outgrow the pool before
                # reaching capacity.  Previously a hard error ("a lone
                # request could deadlock the scheduler") — the scheduler
                # now retires such requests with a structured `rejected`
                # outcome (livelock detection + admission-time pool-bound
                # check), so the configuration is merely degraded
                import warnings

                warnings.warn(
                    f"pool_blocks={self.pool_blocks} cannot hold one "
                    f"worst-case context ({self.n_btab} blocks + null): "
                    f"requests outgrowing the pool will be retired as "
                    f"rejected instead of running to capacity"
                )
            # two-tier KV reuse (DESIGN.md §KV reuse tiers): the trie-
            # backed allocator is tier 1 (free-but-cached device blocks,
            # TTL-aged on the scheduler's virtual clock); an optional
            # host-DRAM tier receives LRU/TTL-evicted blocks and recalls
            # them bit-identically at admission time
            self.prefix_ttl = prefix_ttl
            self.recall_cost = float(recall_cost)
            self.allocator = self._make_allocator()
            self.offload: HostOffloadTier | None = (
                HostOffloadTier(offload_blocks) if offload_blocks > 0 else None
            )
            self.allocator.record_evictions = self.offload is not None
            self._pool_clock = None
            self.prefix_partial_hits = 0
            self.blocks_recalled = 0
            self.tokens_recalled = 0
            self.tokens_recomputed = 0
            self._recall_units = 0.0
            self._seq: dict[int, SeqBlocks] = {}
            self._prompt_logits: OrderedDict[int, np.ndarray] = OrderedDict()
            self._paged_scatter = jax.jit(
                self._paged_scatter_impl, donate_argnums=(0,)
            )
            self._read_block = jax.jit(self._read_block_impl)
            self._write_block = jax.jit(
                self._write_block_impl, donate_argnums=(0,)
            )
            self._set_slot_state = jax.jit(
                self._set_slot_state_impl, donate_argnums=(0,)
            )
            self._set_table_entry = jax.jit(
                self._set_table_entry_impl, donate_argnums=(0,)
            )
            self._copy_block = jax.jit(self._copy_block_impl, donate_argnums=(0,))
            self._zero_block = jax.jit(self._zero_block_impl, donate_argnums=(0,))
        else:
            self.offload = None
            self._batch_axes = _cache_batch_axes(bundle, capacity)
            self._insert = jax.jit(self._insert_impl, donate_argnums=(0,))
        self._corrupt_meta = jax.jit(self._corrupt_meta_impl, donate_argnums=(0,))
        _LIVE_ENGINES.add(self)

    def _make_decode_fns(self, bundle: ModelBundle):
        """Jitted (decode, decode_active) pair for one bundle — rebuilt
        per budget rung by the degradation ladder (the cache pytree is
        budget-independent, so swapping fns never invalidates a cache)."""
        dec = jax.jit(bundle.decode_step, donate_argnums=self._donate)

        def _decode_active_impl(params, tokens, cache, active):
            old_len = cache["length"]
            logits, new_cache = bundle.decode_step(params, tokens, cache)
            new_cache = dict(
                new_cache, length=jnp.where(active, new_cache["length"], old_len)
            )
            return logits, new_cache

        return dec, jax.jit(_decode_active_impl, donate_argnums=self._donate)

    # ------------------------------------------------------- shard routing
    def _make_allocator(self):
        """The host-side allocator for the current layout: one pool, or
        one pool per DP shard behind the global-id wrapper."""
        if self._n_dp > 1:
            from repro.kvcache.sharded import ShardedBlockAllocator

            return ShardedBlockAllocator(
                self.pool_blocks, self.block_size, self._n_dp,
                park_ttl=self.prefix_ttl,
            )
        return BlockAllocator(
            self.pool_blocks, self.block_size, park_ttl=self.prefix_ttl
        )

    def slot_shard(self, slot: int) -> int:
        """Home DP shard of ``slot`` (0 on unsharded engines).  Slots
        split into contiguous per-shard ranges matching the DP partition
        of the cache's slot axis, so a slot's blocks always come from —
        and its decode reads always stay on — one device group."""
        return slot // self._slots_per_shard if self._n_dp > 1 else 0

    def _alloc_block(self, slot: int) -> int | None:
        if self._n_dp > 1:
            return self.allocator.alloc(self.slot_shard(slot))
        return self.allocator.alloc()

    def _lookup_block(self, key: int, slot: int) -> int | None:
        if self._n_dp > 1:
            return self.allocator.lookup(key, self.slot_shard(slot))
        return self.allocator.lookup(key)

    def _peek_blocks(self, keys, slot: int) -> tuple[int, int]:
        if self._n_dp > 1:
            return self.allocator.peek(keys, self.slot_shard(slot))
        return self.allocator.peek(keys)

    @classmethod
    def build(
        cls,
        cfg,
        *,
        n_slots: int,
        capacity: int,
        policy: PolicyConfig | None = None,
        sampling: SamplingConfig = SamplingConfig(),
        layout: str | None = None,
        block_size: int = 32,
        pool_blocks: int = 0,
        degrade_floor: int = 64,
        restore_free_frac: float = 0.5,
        obs: Observability | None = None,
        offload_blocks: int = 0,
        prefix_ttl: float | None = None,
        recall_cost: float = 1.0,
        mesh=None,
        shard_mode: str = "exact",
        **build_kwargs,
    ) -> "Engine":
        """Build bundle + engine with the serving defaults: when ``policy``
        is None the one-pass FIER fast path (``serving_policy()``) is
        used, with the budget clamped to ``capacity`` (a budget larger
        than the cache would otherwise fail plan validation).

        ``mesh=`` (DESIGN.md §Sharded serving) shards the paged pool over
        the mesh: axes named ``'model'`` run KV-head tensor parallelism,
        axes named ``'data'`` run slot/batch data parallelism.  The spec
        rides on the ``DecodePlan`` (validated against each backend's
        ``supports_sharding``) and the engine's allocator becomes
        per-shard (``kvcache.sharded.ShardedBlockAllocator``).

        ``layout='paged'`` switches the cache to the block-pool layout
        (``pool_blocks`` physical blocks of ``block_size`` tokens, prefix
        sharing + copy-on-write; see DESIGN.md §Paged KV cache), so HBM
        is bounded by *tokens resident* instead of n_slots × worst-case
        capacity.  ``pool_blocks=0`` keeps the worst-case pool size (no
        memory saving, useful for A/B testing the layouts)."""
        from repro.models import build_model

        if "paged" in build_kwargs:
            # pre-registry kwarg: forward onto layout= with a deprecation
            # warning instead of dying in build_model's signature
            from repro.core.policy import _warn_deprecated

            _warn_deprecated(
                "Engine.build's `paged` boolean", "layout='paged'"
            )
            if build_kwargs.pop("paged") and layout is None:
                layout = "paged"
                # legacy semantics: the pre-registry paged dispatch
                # ignored the one_pass flag, so a two_pass policy paged
                # through this deprecated kwarg keeps serving via the
                # one-pass kernels instead of tripping the
                # (paged, two_pass) capability-matrix hole.  The new
                # layout= parameter does NOT remap — an explicit
                # two_pass+paged plan raises UnsupportedPlanError.
                if policy is not None and policy.pipeline == "two_pass":
                    policy = dataclasses.replace(policy, pipeline="one_pass")
        if policy is not None:
            pol = policy
        else:
            base = serving_policy()
            pol = dataclasses.replace(base, budget=min(base.budget, capacity))
        if layout is not None and layout != pol.layout:
            pol = dataclasses.replace(
                pol, layout=layout, block_size=block_size,
                pool_blocks=pool_blocks,
            )
        spec = None
        if mesh is not None:
            from repro.kvcache.sharded import ShardSpec
            from repro.models.attention import DistConfig

            if pol.layout != "paged":
                raise ValueError(
                    "Engine.build(mesh=...) shards the paged pool; pass "
                    "layout='paged'"
                )
            names = tuple(mesh.axis_names)
            unknown = [a for a in names if a not in ("model", "data")]
            if unknown:
                raise ValueError(
                    f"mesh axes must be named 'model' (TP over KV heads) "
                    f"or 'data' (DP over slots); got {unknown}"
                )
            spec = ShardSpec(
                mesh=mesh,
                tp_axes=tuple(a for a in names if a == "model"),
                dp_axes=tuple(a for a in names if a == "data"),
                mode=shard_mode,
            )
            if cfg.n_kv_heads % spec.n_tp:
                raise ValueError(
                    f"n_kv_heads {cfg.n_kv_heads} not divisible by TP "
                    f"degree {spec.n_tp} (mesh axes "
                    f"{spec.tp_axes!r})"
                )
            if not pol.pool_blocks:
                # worst-case pool with one null block per DP shard (the
                # bundle's own default reserves a single null block, which
                # does not split over the DP shards)
                pol = dataclasses.replace(
                    pol,
                    pool_blocks=n_slots * (capacity // pol.block_size)
                    + spec.n_dp,
                )
            # mesh=None on the DistConfig: the paged shard path carries
            # its mesh on the spec; DistConfig.mesh would additionally
            # arm the slab sequence-sharding machinery (activation
            # constraints over the 'model' axis), whose partitioned
            # prefill reductions are not bit-identical to the oracle
            build_kwargs["dcfg"] = DistConfig(shard=spec)
        bundle = build_model(cfg, pol, **build_kwargs)
        return cls(
            bundle, n_slots=n_slots, capacity=capacity, sampling=sampling,
            degrade_floor=degrade_floor, restore_free_frac=restore_free_frac,
            obs=obs, offload_blocks=offload_blocks, prefix_ttl=prefix_ttl,
            recall_cost=recall_cost, shard=spec,
            dcfg=build_kwargs.get("dcfg"),
        )

    # ------------------------------------------------------------ lifecycle
    def new_cache(self, length: int = 0):
        if self.current_budget != self.base_budget:
            # a degraded budget never outlives its serving session
            self.restore_budget()
        if self.paged:
            # the pool restarts empty: reset the allocator and drop the
            # prompt caches (their contents describe the old pool / the
            # params used with it)
            self.allocator = self._make_allocator()
            if self.offload is not None:
                # the host tier restarts empty too: sessions must not see
                # KV produced under another session's params/budget
                self.offload = HostOffloadTier(self.offload.capacity_blocks)
            self.allocator.record_evictions = self.offload is not None
            if self._pool_clock is not None:
                self.set_pool_clock(self._pool_clock)
            self._recall_units = 0.0
            self._seq = {}
            self._prompt_logits = OrderedDict()
        cache = self.bundle.init_cache(self.n_slots, self.capacity, length)
        if self.shard is not None:
            from repro.kvcache.sharded import shard_cache

            cache = shard_cache(cache, self.shard)
        return cache

    def place_params(self, params):
        """Put ``params`` where the decode step reads them: replicated on
        every device of a mesh-sharded engine's mesh (once, instead of a
        transfer on every jitted call); unchanged on a single device."""
        if self.shard is None:
            return params
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(
            params, NamedSharding(self.shard.mesh, PartitionSpec())
        )

    def prefill_batch(self, params, batch):
        """Whole-batch prefill (offline / static batching path)."""
        if self.paged:
            raise NotImplementedError(
                "paged engines insert requests one by one (Engine.insert / "
                "ContinuousScheduler); whole-batch prefill returns a slab "
                "cache the paged decode step cannot consume"
            )
        return self._prefill(params, batch)

    def _insert_impl(self, batched_cache, single_cache, slot):
        def put(dest, src, ax):
            return jax.lax.dynamic_update_index_in_dim(dest, src[0], slot, ax)

        return jax.tree.map(put, batched_cache, single_cache, self._batch_axes)

    def insert(self, params, batched_cache, tokens_1xS, length: int, slot: int, extras=None):
        """Prefill one request and place it into ``slot``.  Returns
        (first sampled token logits, updated batched cache).

        Paged mode: allocates/shares blocks through the allocator; a
        full-prompt prefix hit skips the prefill computation entirely
        (the first-token logits are replayed from the prompt cache)."""
        if self.paged:
            return self._insert_paged(
                params, batched_cache, tokens_1xS, length, slot, extras
            )
        batch = {"tokens": tokens_1xS, "lengths": jnp.array([length], jnp.int32)}
        if extras:
            batch.update(extras)
        logits, single = self._prefill(params, batch)
        self.prefill_count += 1
        return logits, self._insert(batched_cache, single, jnp.int32(slot))

    # ------------------------------------------------------- paged lifecycle
    def _paged_scatter_impl(self, cache, single, row, wmask, slot, length):
        """Scatter a prefilled single-request slab cache into the pool.

        ``row`` [n_btab] int32: this request's physical block ids (null-
        padded); ``wmask`` [n_btab] bool: which of them to actually write
        (False = prefix-shared block, its identical contents are already
        resident — the write is redirected to the null block).
        """
        ids = jnp.where(wmask, row, NULL_BLOCK)

        def put(pool, slab):
            # pool [L, N, pb, ...]; slab [L, 1, n_btab·pb, ...]
            L, _, pb = pool.shape[:3]
            if L == 0:
                # zero-layer stack (e.g. the "front" pool under
                # kind="full", where every layer is a rest layer) — the
                # -1 reshape below would divide by zero
                return pool
            blocks = slab.reshape(L, -1, pb, *pool.shape[3:])
            return pool.at[:, ids].set(blocks.astype(pool.dtype))

        pools = {"front": cache["front"], "rest": cache["rest"]}
        slabs = {"front": single["front"], "rest": single["rest"]}
        out = jax.tree.map(put, pools, slabs)
        return dict(
            cache,
            front=out["front"],
            rest=out["rest"],
            block_table=cache["block_table"].at[slot].set(row),
            length=cache["length"].at[slot].set(length),
        )

    def _set_length_impl(self, cache, slot, val):
        return dict(cache, length=cache["length"].at[slot].set(val))

    def _set_slot_state_impl(self, cache, slot, row, length):
        return dict(
            cache,
            block_table=cache["block_table"].at[slot].set(row),
            length=cache["length"].at[slot].set(length),
        )

    def _set_table_entry_impl(self, cache, slot, j, bid):
        return dict(
            cache, block_table=cache["block_table"].at[slot, j].set(bid)
        )

    def _copy_block_impl(self, cache, src, dst):
        """Copy-on-write: duplicate pool block ``src`` into ``dst`` across
        every layer of every pool leaf (K/V and the code side-car)."""

        def cp(pool):
            return pool.at[:, dst].set(pool[:, src])

        return dict(
            cache,
            front=jax.tree.map(cp, cache["front"]),
            rest=jax.tree.map(cp, cache["rest"]),
        )

    def _zero_block_impl(self, cache, bid):
        """Scrub a recycled block before a decode-time append lands in it.
        The token-append metadata update *merges* with the group stats
        already in the block, so a recycled block's stale stats would leak
        into the new tokens' quantization scales — outputs would depend on
        pool recycling history.  Zeroing restores the never-used-block
        contents, making decode bit-identical regardless of pool pressure."""

        def z(pool):
            return pool.at[:, bid].set(jnp.zeros_like(pool[:, bid]))

        return dict(
            cache,
            front=jax.tree.map(z, cache["front"]),
            rest=jax.tree.map(z, cache["rest"]),
        )

    def _read_block_impl(self, cache, bid):
        """Slice one block's rows out of every pool leaf (K/V and the FIER
        side-car) — the D2H half of an offload save."""

        def rd(pool):
            return pool[:, bid]

        return {
            "front": jax.tree.map(rd, cache["front"]),
            "rest": jax.tree.map(rd, cache["rest"]),
        }

    def _write_block_impl(self, cache, payload, bid):
        """Commit a recalled block payload into pool row ``bid`` — the H2D
        half of a recall.  Payload layout is exactly ``_read_block``'s
        output, so an offload round trip is bit-identical."""

        def wr(pool, blk):
            return pool.at[:, bid].set(blk.astype(pool.dtype))

        out = jax.tree.map(
            wr,
            {"front": cache["front"], "rest": cache["rest"]},
            {"front": payload["front"], "rest": payload["rest"]},
        )
        return dict(cache, front=out["front"], rest=out["rest"])

    # ----------------------------------------------------- host offload tier
    def _drain_evictions(self, cache):
        """Snapshot just-evicted prefix blocks into the host tier.  Must
        run after the allocator operation that evicted and *before* any
        device write to the reclaimed rows — at this point the pool rows
        still hold the evicted contents."""
        if self.offload is None:
            return cache
        for ev in self.allocator.take_evicted():
            if self.allocator.key_resident(ev.key):
                # sharded pools can register the same content key on
                # several DP shards; a key still resident on *any* shard
                # must not move to the host tier (cross-tier
                # single-ownership — audit checks host ∩ device = ∅).
                # Conservative: the other shard's copy serves future hits
                continue
            payload = to_host(self._read_block(cache, jnp.int32(ev.bid)))
            self.offload.save(ev.key, ev.parent_key, payload, reason=ev.reason)
            if self.obs.enabled:
                self.obs.metrics.counter(
                    "offload_saves_total",
                    "blocks demoted to the host tier").inc()
        return cache

    def sweep_parked(self, cache):
        """TTL sweep of tier-1 parked blocks — the scheduler calls this
        once per step on its virtual clock.  Expired blocks demote to the
        host tier (when attached) before their rows become reusable.
        Returns (n_expired, cache)."""
        if not self.paged or self.allocator.park_ttl is None:
            return 0, cache
        n = self.allocator.expire_parked()
        if n:
            cache = self._drain_evictions(cache)
        return n, cache

    def _recall_extension(self, cache, keys, blocks, L, slot):
        """Extend a device prefix match through the host tier: allocate a
        fresh device block per resident host key (capped so the final
        chunk still computes ≥ 1 token), stream the payloads back with
        double-buffered ``device_put``s, and re-register each block under
        its original parent linkage — bit-identical to never having been
        evicted.  Partial recall is fine: an alloc failure mid-walk keeps
        what was recalled and recomputes the rest.  Mutates ``blocks`` in
        place; returns the updated cache."""
        if self.offload is None:
            return cache
        max_blocks = (L - 1) // self.block_size
        ext = self.offload.match_extension(keys, len(blocks))
        ext = ext[: max_blocks - len(blocks)]
        if not ext:
            return cache
        fresh: list[int] = []
        for _ in ext:
            bid = self._alloc_block(slot)
            if bid is None:
                break
            fresh.append(bid)
        # evictions caused by the recall allocations themselves demote
        # before we overwrite the reclaimed rows with recalled payloads
        cache = self._drain_evictions(cache)
        if not fresh:
            return cache
        hbs = [self.offload.pop(k) for k in ext[: len(fresh)]]
        t0 = time.monotonic()
        n_done = 0
        for i, (bid, payload) in enumerate(
            double_buffered_puts((b, hb.payload) for b, hb in zip(fresh, hbs))
        ):
            cache = self._write_block(cache, payload, jnp.int32(bid))
            self.allocator.register(
                bid, hbs[i].key, parent_key=hbs[i].parent_key
            )
            blocks.append(bid)
            n_done += 1
        wall = time.monotonic() - t0
        self.offload.recall_wall_s += wall
        self.blocks_recalled += n_done
        self.tokens_recalled += n_done * self.block_size
        self._recall_units += self.recall_cost * n_done
        if self.obs.enabled:
            self.obs.tracer.instant(
                "blocks_recalled", cat="offload", blocks=n_done)
            self.obs.metrics.histogram(
                "offload_recall_seconds",
                "wall time of host-tier block recalls").observe(wall)
        return cache

    def set_pool_clock(self, clock) -> None:
        """Point the allocator trie and host tier at an external monotone
        clock (the scheduler's virtual token clock).  Remembered across
        ``new_cache`` resets, which rebuild both tiers."""
        self._pool_clock = clock
        self.allocator.set_clock(clock)
        if self.offload is not None:
            self.offload.set_clock(clock)

    def take_recall_units(self) -> float:
        """Drain the virtual-clock cost of recalls since the last call.
        The scheduler charges it to vtime: recalling a block costs
        ``recall_cost`` units against the ``block_size`` prefill-token
        units it saved."""
        u, self._recall_units = self._recall_units, 0.0
        return u

    def try_prefix_replay(self, cache, tokens, slot: int):
        """Full-prompt prefix hit: every block resident AND the first-token
        logits cached under the full-prompt key — place the slot with zero
        prefill FLOPs (references taken on every block, logits replayed
        from the prompt cache).  Returns (logits | None, cache); None
        means no full hit and nothing was changed."""
        if not self.paged:
            return None, cache
        toks = [int(t) for t in tokens]
        keys = block_hash_chain(toks, self.block_size)
        nb = len(keys)
        # empty prompt: no blocks, no hash chain — nothing to replay
        if not keys or keys[-1] not in self._prompt_logits:
            return None, cache
        n_hit, _ = self._peek_blocks(keys, slot)
        if n_hit < nb:
            return None, cache
        blocks = [self._lookup_block(key, slot) for key in keys]
        self.prefix_hits += 1
        self._prompt_logits.move_to_end(keys[-1])
        row = np.zeros((self.n_btab,), np.int32)
        row[:nb] = blocks
        cache = self._set_slot_state(
            cache, jnp.int32(slot), jnp.asarray(row), jnp.int32(len(toks))
        )
        self._seq[slot] = SeqBlocks(blocks=blocks, length=len(toks))
        return jnp.asarray(self._prompt_logits[keys[-1]]), cache

    def _insert_paged(self, params, cache, tokens_1xS, length, slot, extras):
        toks = [int(t) for t in np.asarray(tokens_1xS)[0, :length]]
        keys = block_hash_chain(toks, self.block_size)
        nb = len(keys)
        if nb > self.n_btab:
            raise ValueError(
                f"prompt of {length} tokens exceeds capacity {self.capacity}"
            )
        if slot in self._seq:
            raise ValueError(f"slot {slot} still holds blocks; release first")
        logits, cache = self.try_prefix_replay(cache, toks, slot)
        if logits is not None:
            return logits, cache
        # longest shared prefix: take a reference on every hit block
        blocks: list[int] = []
        for key in keys:
            bid = self._lookup_block(key, slot)
            if bid is None:
                break
            blocks.append(bid)
        n_hit = len(blocks)
        full_key = keys[-1] if keys else None
        row = np.zeros((self.n_btab,), np.int32)

        for _ in range(n_hit, nb):
            bid = self._alloc_block(slot)
            if bid is None:
                for b in blocks:
                    self.allocator.free(b)
                raise PoolExhausted(
                    "block pool exhausted during insert — admit on "
                    "Engine.blocks_needed() <= Engine.free_blocks first"
                )
            blocks.append(bid)
        # demote evicted prefix blocks before the scatter overwrites them
        cache = self._drain_evictions(cache)
        batch = {"tokens": tokens_1xS, "lengths": jnp.array([length], jnp.int32)}
        if extras:
            batch.update(extras)
        logits, single = self._prefill(params, batch)
        self.prefill_count += 1
        # monolithic prefill recomputes the whole prompt (the scatter only
        # skips *writes* for hit blocks) — chunked admission is the path
        # that converts prefix/host hits into skipped FLOPs
        self.tokens_recomputed += length
        row[:nb] = blocks
        wmask = np.zeros((self.n_btab,), bool)
        wmask[n_hit:nb] = True
        cache = self._paged_scatter(
            cache, {"front": single["front"], "rest": single["rest"]},
            jnp.asarray(row), jnp.asarray(wmask), jnp.int32(slot),
            jnp.int32(length),
        )
        for i in range(n_hit, nb):
            self.allocator.register(
                blocks[i], keys[i], parent_key=keys[i - 1] if i else None
            )
        if full_key is not None:
            self._prompt_logits[full_key] = np.asarray(logits)
            while len(self._prompt_logits) > MAX_CACHED_PROMPT_LOGITS:
                self._prompt_logits.popitem(last=False)
        self._seq[slot] = SeqBlocks(blocks=blocks, length=length)
        return logits, cache

    @property
    def free_blocks(self) -> int:
        return self.allocator.n_free

    def blocks_needed(self, tokens) -> int:
        """Fresh pool blocks an admission of ``tokens`` would consume
        (prefix-cache hits subtracted, free-cached revivals charged)."""
        keys = block_hash_chain(tokens, self.block_size)
        return self.allocator.blocks_needed(len(tokens), keys)

    # ------------------------------------------------------- chunked prefill
    def _chunk_fn(self, final: bool):
        fn = self._chunk_jits.get(final)
        if fn is None:
            if self.bundle.prefill_chunk is None:
                raise NotImplementedError(
                    f"model family {self.bundle.cfg.family!r} has no chunked "
                    f"prefill; use monolithic Engine.insert"
                )
            fn = jax.jit(
                partial(self.bundle.prefill_chunk, final=final),
                donate_argnums=(2,),
            )
            self._chunk_jits[final] = fn
        return fn

    def blocks_needed_chunk(self, tokens, chunk_tokens: int) -> int:
        """Fresh pool blocks needed to *begin* a chunked admission of
        ``tokens`` and run its first chunk — the chunked analogue of
        ``blocks_needed`` (resume-prefix hits discounted, free-cached
        revivals charged).  The quantum scheduler admits on this and grows
        the allocation chunk by chunk."""
        L = len(tokens)
        keys = block_hash_chain(tokens, self.block_size)
        flags = self.allocator.peek_prefix(keys)
        # begin_chunked never resumes past L-1 (the final chunk must run
        # at least one token to produce logits): drop tail hits
        while flags and len(flags) * self.block_size >= L:
            flags.pop()
        # host-tier extension: each recalled block needs a fresh device
        # block (counted inside nb - len(flags) below, since the resume
        # point moves past them)
        n_host = 0
        if self.offload is not None:
            ext = self.offload.match_extension(keys, len(flags))
            cap = (L - 1) // self.block_size - len(flags)
            n_host = min(len(ext), max(0, cap))
        end = min((len(flags) + n_host) * self.block_size + chunk_tokens, L)
        nb = -(-end // self.block_size)
        return (nb - len(flags)) + sum(flags)

    def begin_chunked(self, cache, slot: int, tokens):
        """Open a chunked insertion of the full prompt ``tokens`` into
        ``slot``.  Returns (resume, cache): the position the first
        ``prefill_chunk`` call must start from.

        Paged: takes references on prefix-cache hit blocks (capped at the
        last whole block *before* the prompt end, so the final chunk
        always computes logits) and seeds the slot's host block list —
        the device table row stays zeroed until the final chunk, so
        interleaved decode steps route this slot's scratch writes to the
        null block.  Slab: parks the slot's length at ``capacity`` so the
        scratch writes clamp onto the last row (masked, and rewritten by
        the final chunk when the prompt fills the slab)."""
        if not self.paged:
            cache = self._set_length(
                cache, jnp.int32(slot), jnp.int32(self.capacity)
            )
            return 0, cache
        if slot in self._seq:
            raise ValueError(f"slot {slot} still holds blocks; release first")
        toks = [int(t) for t in tokens]
        keys = block_hash_chain(toks, self.block_size)
        if len(keys) > self.n_btab:
            raise ValueError(
                f"prompt of {len(toks)} tokens exceeds capacity {self.capacity}"
            )
        L = len(toks)
        blocks: list[int] = []
        for key in keys:
            bid = self._lookup_block(key, slot)
            if bid is None:
                break
            blocks.append(bid)
        while blocks and len(blocks) * self.block_size >= L:
            self.allocator.free(blocks.pop())
        # where the device trie runs out, the host tier may extend the
        # match: recalled blocks push the resume point further right
        cache = self._recall_extension(cache, keys, blocks, L, slot)
        resume = len(blocks) * self.block_size
        if resume:
            self.prefix_partial_hits += 1
        self._seq[slot] = SeqBlocks(blocks=blocks, length=resume)
        self._chunk_keys[slot] = keys
        return resume, cache

    def prefill_chunk(self, params, cache, slot: int, tokens, start: int, n: int):
        """Run one chunk — prompt positions [start, start+n) — of an open
        chunked insertion (``begin_chunked`` first).  Returns
        (ok, logits | None, cache): ok=False means the paged pool could
        not grow the allocation (nothing changed — abort or retry later);
        logits are produced only by the final chunk (start+n == len).

        Paged bookkeeping per chunk: fresh blocks are allocated all-or-
        nothing, and every block fully covered by completed chunks is
        hash-registered immediately — an aborted half-prefilled request
        parks its progress in the prefix cache and re-admits from the
        completed-chunk boundary instead of token 0."""
        toks = np.asarray(tokens, np.int32).reshape(-1)
        L = int(toks.shape[0])
        end = start + n
        if not (0 < n and end <= L <= self.capacity):
            raise ValueError(f"bad chunk [{start}, {end}) of {L} tokens")
        final = end == L
        batch = {
            "tokens": jnp.asarray(toks[None, start:end]),
            "start": jnp.int32(start),
            "slot": jnp.int32(slot),
            "total": jnp.int32(L),
        }
        if self.paged:
            seq = self._seq[slot]
            if start != seq.length:
                raise ValueError(
                    f"chunk starts at {start}, slot resident to {seq.length}"
                )
            nb_needed = -(-end // self.block_size)
            fresh: list[int] = []
            while len(seq.blocks) + len(fresh) < nb_needed:
                bid = self._alloc_block(slot)
                if bid is None:
                    for b in fresh:
                        self.allocator.free(b)
                    return False, None, cache
                fresh.append(bid)
            seq.blocks.extend(fresh)
            if fresh:
                # demote evicted prefix blocks before this chunk's appends
                # overwrite the reclaimed rows
                cache = self._drain_evictions(cache)
            row = np.zeros((self.n_btab,), np.int32)
            row[: len(seq.blocks)] = seq.blocks
            batch["table_row"] = jnp.asarray(row)
        logits, cache = self._chunk_fn(final)(params, batch, cache)
        if self.paged:
            seq.length = end
            self.tokens_recomputed += n
            keys = self._chunk_keys[slot]
            for j in range(end // self.block_size):
                self.allocator.register(
                    seq.blocks[j], keys[j],
                    parent_key=keys[j - 1] if j else None,
                )
            if final:
                if L % self.block_size:
                    self.allocator.register(
                        seq.blocks[-1], keys[-1],
                        parent_key=keys[-2] if len(keys) > 1 else None,
                    )
                self.prefill_count += 1
                self._prompt_logits[keys[-1]] = np.asarray(logits)
                while len(self._prompt_logits) > MAX_CACHED_PROMPT_LOGITS:
                    self._prompt_logits.popitem(last=False)
                del self._chunk_keys[slot]
        return True, logits, cache

    def abort_chunked(self, cache, slot: int):
        """Abandon an open chunked insertion (pool dry / preemption): drop
        the slot's block references — registered completed-chunk blocks
        park free-cached, so a re-admission resumes from the boundary."""
        self._chunk_keys.pop(slot, None)
        if self.paged:
            cache = self.release_slot(cache, slot)
        return cache

    def advance_slot(self, cache, slot: int):
        """Guarantee the next decode write of ``slot`` lands in a private,
        allocated block: allocate a fresh tail block on a block boundary,
        or copy-on-write a shared tail.  Returns (ok, cache); ok=False
        means the pool is dry — the caller preempts someone and retries.
        Must be called once per running slot before every decode step.
        """
        seq = self._seq[slot]
        pos = seq.length
        if pos >= self.capacity:
            # at capacity: the write routes to the null block; the
            # scheduler retires the request at this boundary
            return True, cache
        j, off = divmod(pos, self.block_size)
        if off == 0:
            bid = self._alloc_block(slot)
            if bid is None:
                return False, cache
            # recycled blocks carry stale K/V and group stats; the append-
            # time metadata update merges with what's resident, so scrub
            # (demoting any evicted prefix block first — zeroing destroys it)
            cache = self._drain_evictions(cache)
            cache = self._zero_block(cache, jnp.int32(bid))
            seq.blocks.append(bid)
            cache = self._set_table_entry(
                cache, jnp.int32(slot), jnp.int32(j), jnp.int32(bid)
            )
        else:
            b = seq.blocks[j]
            if self.allocator.ref[b] > 1:
                bid = self._alloc_block(slot)
                if bid is None:
                    return False, cache
                cache = self._drain_evictions(cache)
                cache = self._copy_block(cache, jnp.int32(b), jnp.int32(bid))
                self.allocator.free(b)
                self.allocator.cow_copies += 1
                seq.blocks[j] = bid
                cache = self._set_table_entry(
                    cache, jnp.int32(slot), jnp.int32(j), jnp.int32(bid)
                )
        seq.length = pos + 1
        return True, cache

    def release_slot(self, cache, slot: int):
        """Free a retired/preempted slot: drop the block references (hash-
        registered blocks park in the prefix cache) and zero the table
        row, so the slot's scratch decode writes hit the null block."""
        seq = self._seq.pop(slot, None)
        if seq is not None:
            for b in seq.blocks:
                if b != NULL_BLOCK:  # shed middle blocks leave null holes
                    self.allocator.free(b)
            cache = self._set_slot_state(
                cache, jnp.int32(slot),
                jnp.zeros((self.n_btab,), jnp.int32), jnp.int32(0),
            )
        return cache

    # legacy pool_stats key → canonical BlockAllocator.stats() name
    _POOL_STAT_ALIASES = {
        "blocks_in_use": "pool_blocks_in_use",
        "blocks_allocated": "pool_blocks_usable",
        "utilization": "pool_utilization",
        "peak_in_use": "pool_peak_in_use",
        "prefix_block_hits": "pool_prefix_block_hits",
        "cow_copies": "pool_cow_copies",
    }

    def engine_stats(self) -> dict:
        """Engine-level serving counters under their canonical (registry)
        names — the companion of ``BlockAllocator.stats()``."""
        out = dict(
            engine_prefills=self.prefill_count,
            engine_prefix_hits=self.prefix_hits,
            engine_budget_downshifts=self.downshifts,
            engine_budget_restores=self.restores,
            engine_blocks_shed=self.blocks_shed,
            engine_current_budget=self.current_budget,
        )
        if self.paged:
            out.update(
                engine_prefix_partial_hits=self.prefix_partial_hits,
                engine_blocks_recalled=self.blocks_recalled,
                engine_tokens_recalled=self.tokens_recalled,
                engine_tokens_recomputed=self.tokens_recomputed,
            )
        return out

    def pool_stats(self) -> dict:
        """Thin snapshot shim over the canonical accounting: legacy keys
        alias onto ``BlockAllocator.stats()`` / ``engine_stats()`` names
        (kept for existing callers; new code should read the canonical
        ``pool_*`` / ``engine_*`` names or the metrics registry)."""
        canon = self.allocator.stats()
        out = {k: canon[v] for k, v in self._POOL_STAT_ALIASES.items()}
        out.update(
            prefix_hits=self.prefix_hits,
            prefills=self.prefill_count,
            budget_downshifts=self.downshifts,
            budget_restores=self.restores,
            blocks_shed=self.blocks_shed,
            # parked-block aging (trie clock units) — passed through under
            # the canonical names; the legacy aliases predate the trie
            pool_parked_age_p50=canon["pool_parked_age_p50"],
            pool_parked_age_p90=canon["pool_parked_age_p90"],
            pool_parked_age_max=canon["pool_parked_age_max"],
            pool_ttl_evictions=canon["pool_ttl_evictions"],
        )
        return out

    def sample_pool_gauges(self) -> None:
        """Push the canonical pool + engine counters into the metrics
        registry as gauges (sampled by the scheduler once per step; no-op
        when observability is disabled)."""
        if not self.obs.metrics.enabled:
            return
        m = self.obs.metrics
        if self.paged:
            m.set_gauges(self.allocator.stats())
            if self._n_dp > 1:
                # per-shard series ride alongside the unlabeled aggregate
                # (existing consumers keep reading the label-free series)
                for i, st in enumerate(self.allocator.shard_stats()):
                    m.set_gauges(st, shard=str(i))
            if self.offload is not None:
                m.set_gauges(self.offload.stats())
        m.set_gauges(self.engine_stats())

    # --------------------------------------------- graceful budget degradation
    @property
    def degradable(self) -> bool:
        """Whether this engine's policy has a retrieval budget the ladder
        can downshift (fier/quest; 'full' reads everything by definition)."""
        pol = self.bundle.policy
        return pol is not None and pol.kind in ("fier", "quest")

    def _swap_budget(self, budget: int) -> None:
        """Point the decode fns at a bundle rebuilt with ``budget``.

        The rebuilt policy goes through ``DecodePlan.build`` (capability
        matrix + capacity bounds), so an invalid rung fails loudly here
        rather than inside a kernel.  Rungs are cached — thrashing between
        two budgets re-jits nothing.  The cache pytree does not depend on
        the budget, so the live cache carries across the swap.
        """
        fns = self._budget_fns.get(budget)
        if fns is None:
            from repro.models import build_model

            pol2 = dataclasses.replace(self.bundle.policy, budget=budget)
            DecodePlan.build(
                pol2, capacity=self.capacity,
                shard=self.shard if pol2.layout == "paged" else None,
            )
            # dcfg rides along so a degraded bundle keeps the mesh
            # sharding (dropping it would silently fall back to the
            # single-device paged path on a sharded cache)
            bundle2 = build_model(self.bundle.cfg, pol2, self._dcfg)
            fns = self._budget_fns[budget] = self._make_decode_fns(bundle2)
        self._decode, self._decode_active = fns
        self.current_budget = budget

    def downshift_budget(self) -> bool:
        """One rung down the ladder (halve, floored at ``degrade_floor``).
        False when already at the floor / not degradable."""
        if not self.degradable:
            return False
        new = max(self.degrade_floor, self.current_budget // 2)
        if new >= self.current_budget:
            return False
        prev = self.current_budget
        self._swap_budget(new)
        self.downshifts += 1
        if self.obs.enabled:
            self.obs.tracer.instant(
                "budget_downshift", cat="degradation",
                from_budget=prev, to_budget=new)
            self.obs.metrics.counter(
                "budget_downshifts_total",
                "degradation-ladder budget halvings").inc()
        return True

    def restore_budget(self) -> bool:
        """Back to the full configured budget (pressure cleared)."""
        if self.current_budget == self.base_budget:
            return False
        prev = self.current_budget
        self._swap_budget(self.base_budget)
        self.restores += 1
        if self.obs.enabled:
            self.obs.tracer.instant(
                "budget_restore", cat="degradation",
                from_budget=prev, to_budget=self.base_budget)
            self.obs.metrics.counter(
                "budget_restores_total",
                "degradation-ladder full-budget restores").inc()
        return True

    def maybe_restore_budget(self) -> bool:
        """Restore the full budget iff degraded and the free pool has
        recovered past ``restore_free_frac`` of the usable blocks."""
        if self.current_budget == self.base_budget or not self.paged:
            return False
        if self.allocator.n_free < self.restore_free_frac * self.allocator.usable:
            return False
        return self.restore_budget()

    def shed_middle_blocks(self, cache, slot: int):
        """Free the *middle* blocks of a running slot — the memory half of
        a budget downshift (the budget itself is read-side only; shrinking
        it frees nothing).  Keeps the sink blocks at the front and the
        recent-window + writable-tail blocks at the back — exactly the
        rows the degraded policy's guard-rails still read exactly — and
        replaces each shed entry with the null block (reads as zeros,
        masked-by-score like any unselected row).  Shared blocks are
        skipped (dropping one ref of a ref>1 block frees no memory, it
        only loses this slot's access); hash-registered blocks *are*
        shed — they park free-cached with contents intact, evictable for
        fresh allocations and still valid for prefix revival.
        Returns (blocks freed, cache)."""
        seq = self._seq.get(slot)
        pol = self.bundle.policy
        if seq is None or pol is None:
            return 0, cache
        bs = self.block_size
        keep_front = max(1, -(-pol.sink // bs))
        keep_tail = max(2, -(-(pol.recent + 1) // bs))
        freed = 0
        for j in range(keep_front, len(seq.blocks) - keep_tail):
            b = seq.blocks[j]
            if b == NULL_BLOCK or self.allocator.ref[b] > 1:
                continue
            seq.blocks[j] = NULL_BLOCK
            cache = self._set_table_entry(
                cache, jnp.int32(slot), jnp.int32(j), jnp.int32(NULL_BLOCK)
            )
            self.allocator.free(b)
            freed += 1
        self.blocks_shed += freed
        if freed and self.obs.enabled:
            self.obs.tracer.instant(
                "blocks_shed", cat="degradation", slot=slot, freed=freed)
            self.obs.metrics.counter(
                "blocks_shed_total",
                "middle blocks freed by budget degradation").inc(freed)
        return freed, cache

    # ----------------------------------------------------- faults & auditing
    def _corrupt_meta_impl(self, cache, idx):
        """Scramble the FIER side-car at axis-1 index ``idx`` of the rest
        pool — a physical block id (paged) or a slot's batch row (slab).
        Codes bit-flip and (scale, zero) are pushed away from their true
        values; everything stays finite (this fault class is *silent*
        retrieval-quality corruption, not the NaN watchdog's)."""
        rest = cache["rest"]
        if not isinstance(rest, dict) or "meta" not in rest:
            return cache
        from repro.core.quantize import QuantizedKeys

        m = rest["meta"]
        meta = QuantizedKeys(
            m.codes.at[:, idx].set(m.codes[:, idx] ^ jnp.uint8(0xA5)),
            m.scale.at[:, idx].set(-m.scale[:, idx] - 1.0),
            m.zero.at[:, idx].set(-m.zero[:, idx] + 1.0),
            m.group,
        )
        return dict(cache, rest=dict(rest, meta=meta))

    def corrupt_slot_metadata(self, cache, slot: int):
        """Chaos hook: corrupt the FIER metadata backing ``slot``.

        Paged mode targets a *privately held, unregistered* block
        (ref == 1, no prefix-cache hash) so the corruption cannot bleed
        into prefix-sharing requests or future prefix hits; when the slot
        holds no such block yet (fully shared prompt, no decode append),
        nothing happens and the caller retries later.  Slab mode scrambles
        the slot's own batch row.  Returns (corrupted?, cache)."""
        if not self.paged:
            if 0 <= slot < self.n_slots:
                return True, self._corrupt_meta(cache, jnp.int32(slot))
            return False, cache
        seq = self._seq.get(slot)
        if seq is None:
            return False, cache
        for b in reversed(seq.blocks):
            if (
                b != NULL_BLOCK
                and self.allocator.ref[b] == 1
                and self.allocator.key_of(b) is None
            ):
                return True, self._corrupt_meta(cache, jnp.int32(b))
        return False, cache

    def jit_cache_sizes(self) -> dict[str, int]:
        """Compile-cache entry counts of every jitted engine function —
        the overhead-guard tests' compile-count spy: enabling metrics or
        tracing must add ZERO entries to any of these (observability is
        host-side only and never enters a traced computation)."""
        fns: dict[str, Any] = {"prefill": self._prefill}
        for b, (dec, dec_act) in self._budget_fns.items():
            fns[f"decode[{b}]"] = dec
            fns[f"decode_active[{b}]"] = dec_act
        for final, fn in self._chunk_jits.items():
            fns[f"prefill_chunk[final={final}]"] = fn
        fns["set_length"] = self._set_length
        if self.paged:
            fns.update(
                paged_scatter=self._paged_scatter,
                set_slot_state=self._set_slot_state,
                set_table_entry=self._set_table_entry,
                copy_block=self._copy_block,
                zero_block=self._zero_block,
                read_block=self._read_block,
                write_block=self._write_block,
            )
        return {name: int(fn._cache_size()) for name, fn in fns.items()}

    def audit(self) -> None:
        """Cross-check the allocator against the engine's live sequences:
        every block reference the engine holds must be counted exactly by
        the allocator (ref-count conservation), on top of the allocator's
        internal invariants.  Raises ``AllocatorAuditError``; no-op for
        slab engines (nothing to leak)."""
        if not self.paged:
            return
        owners: Counter[int] = Counter()
        for seq in self._seq.values():
            for b in seq.blocks:
                if b != NULL_BLOCK:
                    owners[b] += 1
        host_keys = None
        if self.offload is not None:
            errs = self.offload.audit()
            if errs:
                raise AllocatorAuditError(
                    "host tier audit failed: " + "; ".join(errs)
                )
            host_keys = self.offload.keys()
        self.allocator.audit(dict(owners), host_keys=host_keys)

    def decode(self, params, tokens, cache, active=None, rng=None):
        """One decode step for all slots; inactive slots don't advance.

        tokens [n_slots] int32 → (next_tokens [n_slots], logits, cache).
        When ``rng`` is omitted, a fresh key is split off the engine's
        internal rng — every call samples with a distinct key (the old
        behaviour re-used ``PRNGKey(0)`` each step, so temperature > 0
        serving resampled the same draw forever).
        """
        if active is not None:
            # inactive slots' lengths are frozen inside the jitted step
            # (their cache writes are scratch, overwritten on insert)
            logits, new_cache = self._decode_active(params, tokens, cache, active)
        else:
            logits, new_cache = self._decode(params, tokens, cache)
        if rng is None:
            self._rng, rng = jax.random.split(self._rng)
        nxt = sample_token(rng, logits, self.sampling)
        return nxt, logits, new_cache

    # --------------------------------------------------------- conveniences
    def generate(
        self, params, prompts: jax.Array, lengths: jax.Array, max_new: int,
        extras=None, rng=None,
    ):
        """Static-batch generate: prefill the whole batch then decode
        ``max_new`` tokens.  prompts [B, S]; returns tokens [B, max_new].
        Without an explicit ``rng``, each call draws a fresh key off the
        engine rng (same contract as ``decode``)."""
        if self.paged:
            raise NotImplementedError(
                "paged engines generate through the ContinuousScheduler "
                "(per-request insert + block accounting), not the "
                "static-batch generate path"
            )
        if rng is None:
            self._rng, rng = jax.random.split(self._rng)
        batch = {"tokens": prompts, "lengths": lengths}
        if extras:
            batch.update(extras)
        logits, cache = self._prefill(params, batch)
        tok = sample_token(rng, logits, self.sampling)
        outs = [tok]
        for i in range(max_new - 1):
            rng, sub = jax.random.split(rng)
            tok, _, cache = self.decode(params, tok, cache, rng=sub)
            outs.append(tok)
        return jnp.stack(outs, axis=1)
