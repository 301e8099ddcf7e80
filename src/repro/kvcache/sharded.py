"""Mesh-partitioned paged KV pool: TP×DP sharded decode + allocator.

This module turns the single-device paged subsystem (``kvcache/paged.py``)
into a multi-device one along two mesh axis groups:

* **TP (KV-head parallel)** — every pool leaf is sharded on its KV-head
  axis.  Query heads shard in matching contiguous chunks, so with GQA the
  local query head ``h`` attends to local KV head ``h // rep`` exactly as
  on one device: each shard runs the *unchanged* single-device backend
  decode (``decode_attention``) over its local heads and the concatenated
  result is bit-identical to the single-device oracle.  No LSE merge is
  needed — heads partition the output exactly.  Requires
  ``n_kv_heads % n_tp == 0``.
* **DP (batch parallel)** — the pool's block axis splits into contiguous
  per-shard ranges of ``n_local`` blocks, and the slot axis (block table
  + lengths) splits in matching ranges, so a slot's blocks always live on
  its *home shard*.  Block tables store **global** block ids; inside the
  ``shard_map`` body they are localized with a range test
  (``start <= bid < start + n_local``) that maps every foreign or null id
  to the shard's local null block.  The host side mirrors this with
  :class:`ShardedBlockAllocator`: one inner
  :class:`~repro.kvcache.paged.BlockAllocator` per DP shard, global ids
  ``gid = shard * n_local + local``, each shard's local block 0 reserved
  as its null block (global ids ``shard * n_local`` are never handed
  out), with admission accounting over the per-shard minima.

Selection under this layout is **exact by construction**: TP shards score
their own KV heads over the full sequence, DP shards score their own
batch rows over their full (home-shard) sequence — nobody ever sees a
partial sequence, so FIER's top-k needs no cross-shard threshold
exchange.  The ``local``/``exact`` distinction in :class:`ShardSpec`
matters for the *sequence*-sharded slab path
(``core/distributed.py``) and is kept on the spec so
``DecodePlan.build`` validates it against each backend's
``supports_sharding`` capability uniformly.

Prefix-cache sharing is shard-local: a prompt admitted to a slot on DP
shard 1 cannot revive blocks parked on shard 0 (documented tradeoff —
cross-shard block migration is a follow-up).
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import policy as core_policy

from .paged import BlockAllocator, EvictedBlock, paged_append_kv, \
    paged_append_token_metadata

__all__ = [
    "ShardSpec",
    "ShardedBlockAllocator",
    "shard_cache",
    "sharded_paged_decode_step",
]

SHARD_MODES = ("local", "exact")


@dataclass(frozen=True)
class ShardSpec:
    """How the paged pool and decode step split over a mesh.

    ``tp_axes`` shard KV heads (tensor parallel), ``dp_axes`` shard the
    batch/slot axis (data parallel); ``mode`` is the FIER selection mode
    validated against the backend's ``supports_sharding`` capability
    (``exact`` reproduces single-device top-k bit-identically on this
    layout — see the module docstring).
    """

    mesh: object
    tp_axes: tuple[str, ...] = ()
    dp_axes: tuple[str, ...] = ()
    mode: str = "exact"

    def __post_init__(self):
        object.__setattr__(self, "tp_axes", tuple(self.tp_axes))
        object.__setattr__(self, "dp_axes", tuple(self.dp_axes))
        if self.mode not in SHARD_MODES:
            raise ValueError(
                f"shard mode must be one of {SHARD_MODES}, got {self.mode!r}"
            )
        if not self.tp_axes and not self.dp_axes:
            raise ValueError("ShardSpec needs at least one tp or dp mesh axis")
        names = tuple(self.mesh.axis_names)
        for ax in self.tp_axes + self.dp_axes:
            if ax not in names:
                raise ValueError(
                    f"mesh axis {ax!r} not in mesh axes {names!r}"
                )
        overlap = set(self.tp_axes) & set(self.dp_axes)
        if overlap:
            raise ValueError(f"axes in both tp and dp groups: {sorted(overlap)}")

    @property
    def n_tp(self) -> int:
        return math.prod(self.mesh.shape[a] for a in self.tp_axes)

    @property
    def n_dp(self) -> int:
        return math.prod(self.mesh.shape[a] for a in self.dp_axes)


def _dp_index(spec: ShardSpec):
    """This shard's linear DP index (row-major over ``dp_axes``), as a
    traced scalar.  Only valid inside a ``shard_map`` body."""
    idx = jnp.int32(0)
    mul = 1
    for ax in reversed(spec.dp_axes):
        idx = idx + jax.lax.axis_index(ax) * mul
        mul *= spec.mesh.shape[ax]
    return idx


def localize_block_table(block_table, spec: ShardSpec, n_local: int):
    """Map a global-id block table to this DP shard's local ids.

    A slot's blocks all come from its home shard's contiguous range
    ``[start, start + n_local)``, so the translation ``bid - start`` is
    exact for every block this shard will actually read; ids outside the
    range — the global null block, shed-middle holes, and every other
    shard's rows — collapse to the local null block 0 (each inner
    allocator reserves local row 0, so global ids ``shard * n_local``
    are never handed out and local 0 is always a zeroed row).
    """
    if spec.n_dp == 1:
        return block_table
    start = _dp_index(spec) * n_local
    local = block_table - start
    ok = (block_table >= start) & (block_table < start + n_local)
    return jnp.where(ok, local, 0)


def _pool_leaf_spec(dp, tp):
    """PartitionSpec for a pool-shaped leaf by rank: per-layer pools are
    ``[N, pb, H, D]``, layer-stacked pools ``[L, N, pb, H, D]``."""
    def spec_for(leaf):
        if leaf.ndim == 5:
            return P(None, dp, None, tp, None)
        return P(dp, None, tp, None)
    return spec_for


def sharded_paged_decode_step(
    q,
    k_new,
    v_new,
    k_pool,
    v_pool,
    meta,
    block_table,
    length,
    pol,
    plan,
    spec: ShardSpec,
    *,
    update_meta: bool = True,
):
    """One decode step on the mesh-sharded paged pool.

    Appends the new token's K/V (and side-car metadata) into the sharded
    pool and runs the plan's backend over the local shard — KV heads
    local under TP, batch rows local under DP — returning
    ``(out, k_pool, v_pool, meta)`` exactly like the single-device paged
    branch of ``decode_self_attention``.  The backend itself is
    unchanged: inside the body the plan is re-built shard-free so
    ``decode_attention`` takes its ordinary single-device path on the
    local views.
    """
    plan_inner = dataclasses.replace(plan, shard=None)
    dp = spec.dp_axes if spec.dp_axes else None
    tp = spec.tp_axes if spec.tp_axes else None
    n_local = k_pool.shape[0] // spec.n_dp

    q_spec = P(dp, tp, None)
    new_spec = P(dp, None, tp, None) if k_new.ndim == 4 else P(dp, tp, None)
    pool_spec = P(dp, None, tp, None)
    meta_spec = jax.tree.map(lambda _: pool_spec, meta)
    bt_spec = P(dp, None)
    len_spec = P(dp)

    def body(q_l, kn_l, vn_l, k_l, v_l, meta_l, bt_l, len_l):
        bt_loc = localize_block_table(bt_l, spec, n_local)
        k2, v2 = paged_append_kv(k_l, v_l, kn_l, vn_l, bt_loc, len_l)
        meta2 = meta_l
        if meta_l is not None and update_meta:
            meta2 = paged_append_token_metadata(meta2, k2, bt_loc, len_l, pol)
        view = core_policy.CacheView.paged(k2, v2, meta2, bt_loc, len_l + 1)
        out = core_policy.decode_attention(
            q_l, view, plan_inner, layer=pol.skip_layers
        )
        return out, k2, v2, meta2

    f = jax.shard_map(
        body,
        mesh=spec.mesh,
        in_specs=(q_spec, new_spec, new_spec, pool_spec, pool_spec,
                  meta_spec, bt_spec, len_spec),
        out_specs=(q_spec, pool_spec, pool_spec, meta_spec),
        check_vma=False,
    )
    out, k2, v2, meta2 = f(q, k_new, v_new, k_pool, v_pool, meta,
                           block_table, length)
    if tp is not None:
        # gather the head axis before the caller's output projection: a
        # matmul contracting over a TP-sharded axis would partial-sum
        # per shard and psum-combine, whose reduction order differs from
        # the single-device dot — the O(B·Hq·D) all-gather keeps decode
        # bit-identical to the oracle
        out = jax.lax.with_sharding_constraint(
            out, NamedSharding(spec.mesh, P(dp, None, None))
        )
    return out, k2, v2, meta2


def shard_cache(cache: dict, spec: ShardSpec) -> dict:
    """Place a freshly-initialised paged cache onto the mesh: pool leaves
    sharded DP-on-blocks × TP-on-KV-heads, block table and lengths
    DP-on-slots, everything else replicated."""
    mesh = spec.mesh
    dp = spec.dp_axes if spec.dp_axes else None
    tp = spec.tp_axes if spec.tp_axes else None
    leaf_spec = _pool_leaf_spec(dp, tp)

    def put(leaf, pspec):
        return jax.device_put(leaf, NamedSharding(mesh, pspec))

    out = dict(cache)
    for name, val in cache.items():
        if name == "block_table":
            out[name] = put(val, P(dp, None))
        elif name == "length":
            out[name] = put(val, P(dp))
        elif name == "moe_counts":
            out[name] = put(val, P())
        else:
            out[name] = jax.tree.map(lambda x: put(x, leaf_spec(x)), val)
    return out


# --------------------------------------------------------------------------
# host-side allocator
# --------------------------------------------------------------------------


class _GlobalRefView:
    """Read-only ``allocator.ref[gid]`` over the per-shard ref lists."""

    def __init__(self, alloc: "ShardedBlockAllocator"):
        self._a = alloc

    def __getitem__(self, gid: int) -> int:
        shard, lid = self._a._split(gid)
        return self._a.shards[shard].ref[lid]


class ShardedBlockAllocator:
    """One :class:`BlockAllocator` per DP shard behind the global-id
    surface the engine/scheduler already speak.

    Global id ``gid = shard * n_local + local_id``; each inner allocator
    reserves its local block 0 as the shard's null block, so the global
    ids ``shard * n_local`` are never allocated and the device-side range
    test in :func:`localize_block_table` can collapse foreign ids onto a
    guaranteed-zero row.  Admission accounting is conservative: a
    request's blocks all come from one home shard, so :attr:`usable` and
    :attr:`n_free` report per-shard capacity (``n_local - 1`` and the
    minimum free count) rather than pool-wide sums — a request the
    scheduler admits is guaranteed to fit whichever shard its slot lands
    on.  Prefix lookups are shard-local; callers that don't know the home
    shard yet (pre-admission sizing) get the conservative no-hit answer.
    """

    def __init__(self, n_blocks: int, block_size: int, n_shards: int,
                 park_ttl: float | None = None):
        if n_shards < 1:
            raise ValueError(f"need >= 1 shard, got {n_shards}")
        if n_blocks % n_shards:
            raise ValueError(
                f"pool blocks {n_blocks} not divisible by {n_shards} DP shards"
            )
        self.n_blocks = n_blocks
        self.block_size = block_size
        self.n_shards = n_shards
        self.n_local = n_blocks // n_shards
        self.park_ttl = park_ttl
        self.shards = [
            BlockAllocator(self.n_local, block_size, park_ttl=park_ttl)
            for _ in range(n_shards)
        ]
        self.ref = _GlobalRefView(self)
        # wrapper-level: the engine bumps cow_copies directly, and the
        # chaos injector arms fail_next before knowing which shard will
        # allocate next
        self.cow_copies = 0
        self._fail_next = 0
        self.injected_alloc_failures = 0

    # ------------------------------------------------------------- id mapping
    def _split(self, gid: int) -> tuple[int, int]:
        return divmod(gid, self.n_local)

    def _glob(self, shard: int, lid: int) -> int:
        return shard * self.n_local + lid

    def home(self, gid: int) -> int:
        return gid // self.n_local

    # ------------------------------------------------------------- accounting
    def set_clock(self, clock) -> None:
        for inner in self.shards:
            inner.set_clock(clock)

    def key_of(self, gid: int) -> int | None:
        shard, lid = self._split(gid)
        return self.shards[shard].key_of(lid)

    def key_resident(self, key: int) -> bool:
        return any(inner.key_resident(key) for inner in self.shards)

    @property
    def usable(self) -> int:
        # per-shard: one request's blocks all come from its home shard
        return self.n_local - 1

    @property
    def n_in_use(self) -> int:
        return sum(inner.n_in_use for inner in self.shards)

    @property
    def n_parked(self) -> int:
        return sum(inner.n_parked for inner in self.shards)

    @property
    def n_free(self) -> int:
        # per-device minimum: what any admitted request is guaranteed to
        # find on its home shard (ISSUE: admission over per-device minima)
        return min(inner.n_free for inner in self.shards)

    @property
    def _free(self) -> list[int]:
        out: list[int] = []
        for s, inner in enumerate(self.shards):
            out.extend(self._glob(s, lid) for lid in inner._free)
        return out

    @property
    def peak_in_use(self) -> int:
        return sum(inner.peak_in_use for inner in self.shards)

    @property
    def prefix_block_hits(self) -> int:
        return sum(inner.prefix_block_hits for inner in self.shards)

    @property
    def ttl_evictions(self) -> int:
        return sum(inner.ttl_evictions for inner in self.shards)

    @property
    def record_evictions(self) -> bool:
        return self.shards[0].record_evictions

    @record_evictions.setter
    def record_evictions(self, value: bool) -> None:
        for inner in self.shards:
            inner.record_evictions = value

    def utilization(self) -> float:
        return self.n_in_use / (self.n_blocks - self.n_shards)

    def stats(self) -> dict[str, float]:
        per = [inner.stats() for inner in self.shards]
        out = {k: sum(p[k] for p in per) for k in per[0]}
        ages = sorted(
            age for inner in self.shards for age in inner.tree.parked_ages()
        )
        out.update(
            pool_shards=self.n_shards,
            pool_blocks_total=self.n_blocks,
            pool_blocks_usable=self.n_blocks - self.n_shards,
            pool_utilization=self.utilization(),
            pool_cow_copies=self.cow_copies
            + sum(p["pool_cow_copies"] for p in per),
            pool_injected_alloc_failures=self.injected_alloc_failures
            + sum(p["pool_injected_alloc_failures"] for p in per),
            pool_parked_age_p50=BlockAllocator._percentile(ages, 0.50),
            pool_parked_age_p90=BlockAllocator._percentile(ages, 0.90),
            pool_parked_age_max=ages[-1] if ages else 0.0,
        )
        return out

    def shard_stats(self) -> list[dict[str, float]]:
        """Per-shard ``pool_*`` snapshots (for ``shard``-labeled gauges)."""
        return [inner.stats() for inner in self.shards]

    # -------------------------------------------------------------- alloc/free
    def fail_next(self, n: int = 1) -> None:
        self._fail_next += int(n)

    def alloc(self, shard: int = 0) -> int | None:
        if self._fail_next > 0:
            self._fail_next -= 1
            self.injected_alloc_failures += 1
            return None
        lid = self.shards[shard].alloc()
        return None if lid is None else self._glob(shard, lid)

    def free(self, gid: int) -> None:
        shard, lid = self._split(gid)
        self.shards[shard].free(lid)

    # ------------------------------------------------------------ prefix cache
    def register(self, gid: int, key: int, parent_key: int | None = None) -> None:
        shard, lid = self._split(gid)
        self.shards[shard].register(lid, key, parent_key)

    def lookup(self, key: int, shard: int) -> int | None:
        lid = self.shards[shard].lookup(key)
        return None if lid is None else self._glob(shard, lid)

    def peek(self, keys: list[int], shard: int | None = None) -> tuple[int, int]:
        if shard is None:
            return 0, 0
        return self.shards[shard].peek(keys)

    def peek_prefix(self, keys: list[int], shard: int | None = None) -> list[bool]:
        if shard is None:
            return []
        return self.shards[shard].peek_prefix(keys)

    def blocks_needed(self, n_tokens: int, keys: list[int] | None = None,
                      shard: int | None = None) -> int:
        if keys is None or shard is None:
            return -(-n_tokens // self.block_size)
        return self.shards[shard].blocks_needed(n_tokens, keys)

    # ---------------------------------------------------- eviction / offload
    def expire_parked(self) -> int:
        return sum(inner.expire_parked() for inner in self.shards)

    def take_evicted(self) -> list[EvictedBlock]:
        out: list[EvictedBlock] = []
        for s, inner in enumerate(self.shards):
            out.extend(
                EvictedBlock(self._glob(s, ev.bid), ev.key, ev.parent_key,
                             ev.reason)
                for ev in inner.take_evicted()
            )
        return out

    def drop_key(self, key: int) -> int | None:
        hit = None
        for s, inner in enumerate(self.shards):
            lid = inner.drop_key(key)
            if lid is not None and hit is None:
                hit = self._glob(s, lid)
        return hit

    # ------------------------------------------------------------------- audit
    def audit(
        self,
        owners: dict[int, int] | None = None,
        host_keys: "set[int] | None" = None,
    ) -> None:
        per_owner: list[dict[int, int] | None]
        if owners is None:
            per_owner = [None] * self.n_shards
        else:
            per_owner = [{} for _ in self.shards]
            for gid, refs in owners.items():
                shard, lid = self._split(gid)
                per_owner[shard][lid] = refs
        for inner, own in zip(self.shards, per_owner):
            # host_keys goes to every shard unchanged: the engine's
            # eviction drain only offloads keys resident in *no* shard
            # (key_resident), so cross-tier disjointness holds per shard
            inner.audit(own, host_keys)
