"""Paper Eqs. 4 & 8: selection-phase cache load ratios, measured exactly —
plus the attend-phase bytes the fused select-and-attend path removes.

FIER: (1 + 32/g)/16 of the bf16 key bytes.  Quest: 2/L.  The benchmark
measures the actual bytes of the metadata structures this repo builds and
asserts they equal the formulas (this is also where the paper's
"g=32 ↔ p=16 both 1/8" pairing is verified).

Attend phase: the unfused pipeline *materialises* K'/V' (2·budget·Hkv·D
bf16 written to HBM, then read back by attention → 4·budget·Hkv·D·2 bytes
of extra traffic on top of the budget rows read from the slabs); the
fused kernel reads the selected rows straight from the slabs.  Measured
here from the jaxpr (gather output bytes), not asserted.

Selection phase, fused: the *one-pass* retrieval kernel also removes the
f32 score-tensor round trip between scoring and selection — the two-pass
pipeline writes [B·Hkv·rep, S] f32 out of the score kernel and reads it
back through the reduce + threshold-select stages (≥ 2·4·Hq·S bytes),
the one-pass kernel keeps every block's scores in VREGs.  Measured from
the jaxpr (``count_score_bytes``) and asserted exactly zero.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quantize as qz, quest
from repro.launch.compile_cache import enable_compilation_cache

from .common import bench_model_cfg, emit, emit_paged_score_traffic, emit_score_traffic
from .flopcount import count_fn_gather_bytes


def run():
    B, S, H, D = 1, 4096, 4, 64
    K = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D))
    full_bytes = S * H * D * 2  # bf16 keys per batch row

    for g in (32, 64, 128, 256):
        qk = qz.quantize(K, g)
        measured = (
            qk.codes.nbytes + qk.scale.nbytes + qk.zero.nbytes
        ) / B
        formula = qz.load_ratio(g)
        assert abs(measured / full_bytes - formula) < 1e-9, (g, measured)
        emit(f"load_ratio_fier_g{g}", 0.0,
             f"measured={measured / full_bytes:.6f} formula={formula:.6f}")

    for p in (8, 16, 32):
        meta = quest.build_page_meta(K, p)
        measured = (meta.kmax.nbytes + meta.kmin.nbytes) / B
        formula = 2.0 / p
        assert abs(measured / full_bytes - formula) < 1e-9, (p, measured)
        emit(f"load_ratio_quest_p{p}", 0.0,
             f"measured={measured / full_bytes:.6f} formula={formula:.6f}")

    # the paper's fairness pairing
    assert abs(qz.load_ratio(32) - 2.0 / 16) < 1e-12
    emit("load_ratio_pairing_g32_p16", 0.0, "both=0.125")

    # ------------------------------------------- attend-phase gather bytes
    from repro.core.policy import CacheView, DecodePlan, PolicyConfig, decode_attention

    Bq, Sq, Hkv, Hq, Dq, g = 1, 2048, 4, 8, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    Kc = jax.random.normal(ks[0], (Bq, Sq, Hkv, Dq), jnp.bfloat16)
    Vc = jax.random.normal(ks[1], (Bq, Sq, Hkv, Dq), jnp.bfloat16)
    q = jax.random.normal(ks[2], (Bq, Hq, Dq))
    qk = qz.quantize(Kc.astype(jnp.float32), g)
    length = jnp.full((Bq,), Sq, jnp.int32)
    budget = 256

    pol = PolicyConfig(kind="fier", budget=budget, group=g, skip_layers=0)

    def decode_with(pipeline):
        plan = DecodePlan.build(pol, pipeline=pipeline)
        return lambda q, K, V: decode_attention(
            q, CacheView.slab(K, V, qk, length), plan, layer=1
        )

    unfused = count_fn_gather_bytes(decode_with("reference"), q, Kc, Vc)
    fused = count_fn_gather_bytes(decode_with("one_pass"), q, Kc, Vc)
    copies = 2 * budget * Hkv * Dq * 2 * Bq  # K'+V' bf16, materialised once
    assert unfused >= copies, (unfused, copies)
    emit(
        "attend_gather_bytes_fused_vs_unfused", 0.0,
        f"reference={unfused:.0f} onepass={fused:.0f} kv_copies={copies} "
        f"eliminated={unfused - fused:.0f}",
    )

    # --------------------------------------------- select-phase score bytes
    # shared gate (same helper the CI bench-smoke asserts through): the
    # one-pass kernel materialises zero score bytes, the two-pass pipeline
    # pays at least the f32 [B, Hq, S] write+read floor
    emit_score_traffic(Hq, Hkv, Dq, budget=budget, B=Bq, S=Sq, group=g,
                       check=True)
    emit_paged_score_traffic(Hq, Hkv, Dq, budget=budget, B=Bq, S=Sq,
                             block_size=64, group=g, check=True)


def pool_utilization():
    """Paged-pool utilization under a real continuous-batching workload:
    blocks resident / blocks allocated, peak, prefix-sharing and CoW
    counters, and the slab-vs-pool HBM provisioning ratio.  The pool is
    sized below the summed worst-case contexts, so the run also exercises
    preemption — utilization is what the slab layout can never report
    above `resident/worst-case`."""
    import jax

    from repro.core.policy import PolicyConfig
    from repro.models import build_model
    from repro.serving import ContinuousScheduler, Engine, Request

    cfg = bench_model_cfg()
    capacity, bs, n_slots, pool_blocks = 64, 8, 4, 11
    pol = PolicyConfig(
        kind="fier", budget=16, group=8, skip_layers=1,
        pipeline="one_pass", layout="paged", block_size=bs,
        pool_blocks=pool_blocks,
    )
    bundle = build_model(cfg, pol)
    params = bundle.init(jax.random.PRNGKey(0))
    eng = Engine(bundle, n_slots=n_slots, capacity=capacity)
    sched = ContinuousScheduler(eng, params, pad_prompt_to=16)
    reqs = [
        Request(rid=i, tokens=[3 + i, 4 + i, 5 + i, 6 + i], max_new=20)
        for i in range(6)
    ]
    # snapshot utilization every step via the occupancy hook
    peak_util = 0.0

    orig_decode = eng.decode

    def spy(*a, **kw):
        nonlocal peak_util
        peak_util = max(peak_util, eng.allocator.utilization())
        return orig_decode(*a, **kw)

    eng.decode = spy
    sched.run(reqs)
    st = eng.pool_stats()
    worst_case_blocks = n_slots * (capacity // bs)
    emit(
        "paged_pool_utilization", 0.0,
        f"peak_resident={st['peak_in_use']}/{st['blocks_allocated']} "
        f"peak_util={peak_util:.2f} preemptions={sched.preemptions} "
        f"prefix_block_hits={st['prefix_block_hits']} cow={st['cow_copies']} "
        f"slab_equivalent_blocks={worst_case_blocks} "
        f"hbm_ratio_vs_slab={st['blocks_allocated'] / worst_case_blocks:.3f}",
    )


def main():
    enable_compilation_cache()
    run()
    pool_utilization()


if __name__ == "__main__":
    main()
