"""Traffic-trace serving benchmark: replay a seeded arrival trace through
a live ContinuousScheduler and measure what users feel — TTFT, inter-token
latency, throughput at saturation, preemptions, pool occupancy.

The replay is driven through the scheduler's stepwise API
(``start``/``submit``/``step``) on two clocks at once:

  * **virtual time** — the scheduler's own token clock (``sched.vtime``:
    a prefill token costs 1 unit, a batched decode step costs 1 per
    active slot).  Virtual metrics depend only on the schedule (arrival
    trace, block accounting, chunk quantum), not on the host, so they are
    reproducible across machines and **gated** in CI.
  * **wall clock** — recorded alongside and reported as info metrics
    (interpret-mode kernels and shared CI runners make it unsuitable for
    gating).

TTFT / ITL / throughput are **derived from the request-span trace**
(``repro.obs.tracing.derive_serving_metrics`` over the scheduler's
lifecycle events) — the benchmark no longer keeps its own clock or token
stamps, so the persisted numbers, the metrics-registry snapshot
(``METRICS_serve_trace.json``) and the per-mode Perfetto traces
(``serve_trace_<mode>.trace.json``, viewable via ``tools/obs_report.py``)
can never disagree.

``--smoke`` replays a bursty trace (long prompts bursting into a pool
already held by decoding requests) twice — chunked admission
(``chunk_tokens=256``) vs monolithic — and asserts the chunked schedule's
p99 TTFT is strictly lower at equal (±10%) token throughput: under block
pressure, chunked admission overlaps prefill compute with the wait for
blocks to drain, while a monolithic admission pays its whole prefill
*after* the pool finally fits the prompt.  A third *faulted* pass replays
the same trace on a degradation-enabled engine under a fixed
``ServingFaultInjector`` schedule (cancel, poison, alloc-fail burst) plus
an already-expired deadline, and gates zero leaked blocks at drain.
With ``--devices N`` (the multi-device CI lane) a fourth pass replays
the chunked trace on a mesh-sharded engine (DESIGN.md §Sharded serving)
and gates bit-identical outputs plus zero leaked blocks; shard count and
per-shard occupancy are recorded in the bench doc, and
tools/check_bench_regression.py treats differing shard counts as
distinct baselines.  Results go to ``BENCH_serve_trace.json`` (see
benchmarks/persist.py; baseline checked by
tools/check_bench_regression.py).

``--prefix-mix`` replays a prefix-heavy trace (two thirds of the
requests share one of two 128-token family prefixes, with a
distinct-prompt filler phase that ages the parked prefixes past the
TTL) on two engines sharing the same weights: a baseline whose expired
prefix blocks are destroyed, and a two-tier engine that demotes them to
host DRAM and recalls them on reuse (DESIGN.md §KV reuse tiers).  It
gates identical outputs, a positive prefix hit-rate / recall count, and
strictly fewer recomputed prompt tokens on the offload engine; results
go to ``BENCH_serve_prefix.json``.
"""
from __future__ import annotations

import argparse
import os
import time
from collections import deque

import jax
import numpy as np

from repro.configs import reduced_config
from repro.core.policy import PolicyConfig
from repro.launch.compile_cache import enable_compilation_cache
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.obs.tracing import PID_REQUEST, derive_serving_metrics
from repro.serving import (
    ContinuousScheduler,
    Engine,
    FaultSpec,
    Observability,
    Request,
    ServingFaultInjector,
)

from .persist import metric, write_bench_json

DECODE_TOKEN_COST = 1.0  # virtual units per active slot per decode step


# ------------------------------------------------------------------- traces

def bursty_trace(seed: int, vocab: int) -> list[tuple[float, dict]]:
    """The smoke workload: 4 medium decoders warm the pool, then 3 long
    prompts burst in while most blocks are still held, then a Poisson
    tail of short requests (sharing a family prefix with the burst)."""
    rng = np.random.default_rng(seed)
    toks = lambda n: rng.integers(1, vocab, size=n).tolist()
    family = toks(256)  # shared prefix of the long-prompt family
    trace: list[tuple[float, dict]] = []
    rid = 0
    for _ in range(4):
        trace.append((0.0, dict(rid=rid, tokens=toks(128), max_new=24)))
        rid += 1
    for _ in range(3):
        trace.append(
            (150.0, dict(rid=rid, tokens=family + toks(320), max_new=8))
        )
        rid += 1
    t = 160.0
    for _ in range(6):
        t += float(rng.exponential(40.0))
        trace.append((t, dict(rid=rid, tokens=family[:64] + toks(32), max_new=12)))
        rid += 1
    return trace


def poisson_trace(
    seed: int, vocab: int, *, n_requests: int, mean_gap: float,
    prompt_lo: int = 64, prompt_hi: int = 512, max_new: int = 16,
    n_families: int = 4, prefix_len: int = 64,
) -> list[tuple[float, dict]]:
    """Open-loop Poisson arrivals over shared-prefix prompt families."""
    rng = np.random.default_rng(seed)
    toks = lambda n: rng.integers(1, vocab, size=n).tolist()
    families = [toks(prefix_len) for _ in range(n_families)]
    trace, t = [], 0.0
    for rid in range(n_requests):
        t += float(rng.exponential(mean_gap))
        fam = families[int(rng.integers(0, n_families))]
        n = int(rng.integers(prompt_lo, prompt_hi + 1))
        suffix = toks(max(1, n - prefix_len))
        trace.append((t, dict(rid=rid, tokens=fam + suffix, max_new=max_new)))
    return trace


def prefix_mix_trace(seed: int, vocab: int) -> list[tuple[float, dict]]:
    """The --prefix-mix workload: 12 of 18 requests (67%) share one of
    two 128-token family prefixes.  A warm phase parks both families in
    the prefix cache, a distinct-prompt filler phase ages them past the
    park TTL (a baseline engine destroys the expired blocks; a two-tier
    engine demotes them to host DRAM), then a reuse phase re-sends the
    families — recall vs recompute is exactly the difference measured."""
    rng = np.random.default_rng(seed)
    toks = lambda n: rng.integers(1, vocab, size=n).tolist()
    families = [toks(128) for _ in range(2)]
    trace, rid = [], 0
    for i in range(4):          # warm: park both families
        fam = families[i % 2]
        trace.append((0.0, dict(rid=rid, tokens=fam + toks(32), max_new=8)))
        rid += 1
    t = 900.0
    for _ in range(6):          # fillers: age the parked prefixes out
        trace.append((t, dict(rid=rid, tokens=toks(256), max_new=8)))
        rid += 1
        t += 120.0
    t = 3200.0
    for i in range(8):          # reuse: recall (offload) vs recompute (base)
        fam = families[i % 2]
        trace.append((t, dict(rid=rid, tokens=fam + toks(48), max_new=8)))
        rid += 1
        t += 60.0
    return trace


# ------------------------------------------------------------------- replay

def build_serving(pipeline: str, *, capacity: int, n_slots: int,
                  pool_blocks: int, block_size: int = 32,
                  prefix_ttl: float | None = None, offload_blocks: int = 0,
                  mesh=None, metrics=None):
    cfg = reduced_config("olmo-1b")
    pol = PolicyConfig(
        kind="fier", budget=64, group=32, skip_layers=1, sink=4, recent=32,
        pipeline=pipeline, layout="paged", block_size=block_size,
        pool_blocks=pool_blocks,
    )
    if mesh is not None:
        # sharded pool (DESIGN.md §Sharded serving): Engine.build owns the
        # ShardSpec/DistConfig threading; params init is deterministic from
        # (cfg, key) so the sharded engine's weights match the unsharded one
        eng = Engine.build(
            cfg, n_slots=n_slots, capacity=capacity, policy=pol,
            obs=Observability(metrics=metrics), prefix_ttl=prefix_ttl,
            offload_blocks=offload_blocks, mesh=mesh,
        )
        params = eng.bundle.init(jax.random.PRNGKey(0))
        return cfg, params, eng
    bundle = build_model(cfg, pol)
    params = bundle.init(jax.random.PRNGKey(0))
    eng = Engine(bundle, n_slots=n_slots, capacity=capacity,
                 obs=Observability(metrics=metrics), prefix_ttl=prefix_ttl,
                 offload_blocks=offload_blocks)
    return cfg, params, eng


def device_mesh(devices: int):
    """The bench's mesh shapes: 1 → single-device (no mesh), 2 → DP=2,
    4 → DP=2 × TP=2 (axis names are the Engine.build contract)."""
    if devices == 1:
        return None
    if devices == 2:
        return make_mesh((2,), ("data",))
    if devices == 4:
        return make_mesh((2, 2), ("data", "model"))
    raise SystemExit(f"--devices must be 1, 2 or 4, got {devices}")


def replay(eng, sched, trace, outputs: dict | None = None):
    """Drive one trace through the scheduler; returns the stats dict.

    The scheduler's virtual token clock IS the replay clock: arrivals pin
    ``Request.arrival`` via ``submit(req, arrival=t)``, idle gaps advance
    it through ``idle_until``, and every latency number comes out of the
    request-span trace (``derive_serving_metrics``) — no shadow clock, no
    engine monkey-patching."""
    obs = eng.obs
    sched.start()
    pending = deque((t, Request(**spec)) for t, spec in trace)
    reqs = [r for _, r in pending]
    wall0 = time.monotonic()
    while pending or sched.busy:
        while pending and pending[0][0] <= sched.vtime:
            t, r = pending.popleft()
            sched.submit(r, arrival=t)
        if not sched.busy:
            sched.idle_until(pending[0][0])
            continue
        if not sched.step():
            if pending:
                # idle until the next arrival can be admitted
                sched.idle_until(pending[0][0])
                continue
            raise RuntimeError("trace replay stalled")
    wall_s = time.monotonic() - wall0

    d = derive_serving_metrics(obs.tracer)
    # wall-clock TTFT rides on the events' informational wall_ts
    first_wall: dict[int, float] = {}
    for e in obs.tracer.events:
        if e.pid == PID_REQUEST and e.name == "token" and e.tid not in first_wall:
            first_wall[e.tid] = e.wall_ts - wall0
    wall_ttft = list(first_wall.values())
    pool = eng.pool_stats()
    # the spans are the single source of truth — but the requests are
    # still the ground truth for *what was generated*: every token a
    # request kept must have exactly one span stamp
    assert d["total_tokens"] == sum(len(r.out) for r in reqs), (
        d["total_tokens"], sum(len(r.out) for r in reqs))
    if outputs is not None:
        outputs.update({r.rid: list(r.out) for r in reqs})
    return dict(
        vt_ttft_p50=d["ttft_p50"], vt_ttft_p99=d["ttft_p99"],
        vt_itl_p50=d["itl_p50"], vt_itl_p99=d["itl_p99"],
        vt_tokens_per_kunit=d["tokens_per_kunit"],
        wall_seconds=wall_s,
        wall_ttft_p99_s=float(np.percentile(wall_ttft, 99)) if wall_ttft else 0.0,
        total_tokens=d["total_tokens"], decode_steps=sched.steps,
        preemptions=sched.preemptions, prefill_aborts=sched.prefill_aborts,
        prefill_chunks=sched.prefill_chunks,
        mean_occupancy=sched.mean_occupancy,
        peak_blocks=pool["peak_in_use"],
        prefix_block_hits=pool["prefix_block_hits"],
        # fault-tolerance counters (all zero on a fault-free replay)
        rejected=sched.health.counts["rejected"],
        cancelled=sched.health.counts["cancelled"],
        deadline_exceeded=sched.health.counts["deadline_exceeded"],
        quarantined=sched.health.counts["quarantined"],
        insert_retries=sched.insert_retries,
        budget_downshifts=pool.get("budget_downshifts", 0),
        blocks_shed=pool.get("blocks_shed", 0),
        leaked_blocks=eng.allocator.n_in_use if eng.paged else 0,
    )


# --------------------------------------------------------------------- modes

SMOKE_ENGINE = dict(capacity=1024, n_slots=4, pool_blocks=34, block_size=32)

# the chaos pass's fixed fault schedule: a mid-flight cancel of a burst
# prompt, a poisoned decode step for a warm decoder (quarantine), and a
# transient allocation-failure burst (degradation ladder / insert retry)
FAULT_SCHEDULE = (
    FaultSpec("poison_logits", step=4, rid=2),
    FaultSpec("cancel", step=6, rid=4),
    FaultSpec("alloc_fail", step=8, count=3),
)


def faulted_replay(cfg, params, bundle, *, seed: int, chunk_tokens: int,
                   metrics=None, out_dir: str | None = None):
    """The chaos pass: the same bursty trace, plus one request whose
    deadline is already unmeetable, on a degradation-enabled engine under
    :data:`FAULT_SCHEDULE` — with retrieval introspection on, so the
    snapshot carries budget-utilization / oracle-overlap series from a
    degraded engine.  ``metrics`` shares the fault-free passes' registry;
    ``out_dir`` writes ``serve_trace_faulted.trace.json``.  Returns
    (stats, injector, engine)."""
    obs = Observability(introspect=True, probe_every=2, metrics=metrics)
    eng = Engine(
        bundle, n_slots=SMOKE_ENGINE["n_slots"],
        capacity=SMOKE_ENGINE["capacity"], degrade_floor=16, obs=obs,
    )
    trace = bursty_trace(seed, cfg.vocab)
    rid = 1 + max(spec["rid"] for _, spec in trace)
    trace.append(
        (200.0, dict(rid=rid, tokens=list(range(1, 48)), max_new=8,
                     deadline=10.0))
    )
    inj = ServingFaultInjector(list(FAULT_SCHEDULE))
    sched = ContinuousScheduler(
        eng, params, chunk_tokens=chunk_tokens, injector=inj, audit_every=8
    )
    stats = replay(eng, sched, trace)
    eng.audit()  # invariant check on top of the gated leak metric
    if out_dir is not None:
        obs.tracer.write_chrome_trace(
            os.path.join(out_dir, "serve_trace_faulted.trace.json"))
    return stats, inj, eng


def smoke(out_dir: str, *, seed: int = 0, chunk_tokens: int = 256,
          pipeline: str = "reference", devices: int = 1) -> dict:
    """CI gate: chunked vs monolithic on the bursty trace; writes
    BENCH_serve_trace.json, the per-mode Perfetto traces and the shared
    metrics-registry snapshot, and asserts the tentpole's latency claim.
    ``devices > 1`` adds a sharded pass: the trace replayed on a
    mesh-sharded engine must produce bit-identical outputs to a
    single-device oracle with zero leaked blocks (the multi-device CI
    lane's gate)."""
    cfg, params, eng = build_serving(pipeline, **SMOKE_ENGINE)
    trace = bursty_trace(seed, cfg.vocab)
    results = {}
    for mode, ct in (("chunked", chunk_tokens), ("mono", None)):
        sched = ContinuousScheduler(eng, params, chunk_tokens=ct)
        results[mode] = replay(eng, sched, trace)
        # the next replay's start() resets the tracer — export now
        eng.obs.tracer.write_chrome_trace(
            os.path.join(out_dir, f"serve_trace_{mode}.trace.json"))
        print(f"-- {mode}: " + " ".join(
            f"{k}={v:.1f}" for k, v in sorted(results[mode].items())
        ))
    fr, inj, feng = faulted_replay(
        cfg, params, eng.bundle, seed=seed, chunk_tokens=chunk_tokens,
        metrics=eng.obs.metrics, out_dir=out_dir,
    )
    print("-- faulted: " + " ".join(
        f"{k}={v:.1f}" for k, v in sorted(fr.items())
    ))
    sharded_res = shard_stats = None
    n_dp = n_tp = 1
    if devices > 1:
        mesh = device_mesh(devices)
        # per-shard usable block count matches the single-device pool's
        # (pool-1 usable blocks): each DP shard serves its slot share at
        # the single-device engine's per-slot pressure
        n_dp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("data", 1)
        n_tp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
        shard_engine = dict(
            SMOKE_ENGINE,
            pool_blocks=(SMOKE_ENGINE["pool_blocks"] - 1) * n_dp + n_dp,
        )
        # the sharded engine shares the run's metrics registry, so its
        # per-shard pool_*{shard=i} gauges land in the snapshot (and in
        # obs_report's across-shard rollup)
        _, sparams, seng = build_serving(
            pipeline, **shard_engine, mesh=mesh, metrics=eng.obs.metrics)
        souts: dict = {}
        sched = ContinuousScheduler(seng, sparams, chunk_tokens=chunk_tokens)
        sharded_res = replay(seng, sched, trace, outputs=souts)
        seng.audit()
        shard_stats = seng.allocator.shard_stats()
        print(f"-- sharded (devices={devices} dp={n_dp} tp={n_tp}): " + " ".join(
            f"{k}={v:.1f}" for k, v in sorted(sharded_res.items())
        ))
        # identity oracle: a single-device engine with the sharded run's
        # aggregate usable blocks.  Preemption legitimately changes
        # tokens (a preempted request resumes via re-prefill, whose
        # next-token logits attend over the FULL prefix, while
        # uninterrupted decode attends over the FIER-budgeted
        # selection), so the gate compares two preemption-free
        # schedules — asserted below so a future trace change that
        # reintroduces preemption fails loudly instead of flaking
        ref_engine = dict(
            SMOKE_ENGINE,
            pool_blocks=(SMOKE_ENGINE["pool_blocks"] - 1) * n_dp + 1,
        )
        _, rparams, reng = build_serving(pipeline, **ref_engine)
        ref_outs: dict = {}
        ref_res = replay(
            reng, ContinuousScheduler(reng, rparams, chunk_tokens=chunk_tokens),
            trace, outputs=ref_outs,
        )
        assert ref_res["preemptions"] == 0, (
            "oracle replay preempted — grow the oracle pool", ref_res)
        assert sharded_res["preemptions"] == 0, (
            "sharded replay preempted — grow the per-shard pool", sharded_res)
        # the sharded serving claim, gated: sharding changes WHERE blocks
        # and heads live, never what is generated
        assert souts == ref_outs, "sharded replay changed outputs"
        assert sharded_res["leaked_blocks"] == 0, sharded_res
    ch, mo = results["chunked"], results["mono"]
    ratio = ch["vt_ttft_p99"] / max(mo["vt_ttft_p99"], 1e-9)
    tput_ratio = ch["vt_tokens_per_kunit"] / max(mo["vt_tokens_per_kunit"], 1e-9)

    # every persisted number goes THROUGH the registry: the bench row is
    # read back from the gauge it just set, so BENCH_serve_trace.json and
    # METRICS_serve_trace.json are bit-identical by construction
    metrics = []

    def summary(name, value, *, unit="", better="info", gate=False):
        g = eng.obs.metrics.gauge(
            name, "serve_trace summary metric", unit=unit,
            better=better, gate=gate)
        g.set(float(value))
        metrics.append(metric(name, g.value(), unit=unit, better=better,
                              gate=gate))

    for mode, r in results.items():
        summary(f"{mode}_vt_ttft_p50", r["vt_ttft_p50"], unit="unit",
                better="lower", gate=True)
        summary(f"{mode}_vt_ttft_p99", r["vt_ttft_p99"], unit="unit",
                better="lower", gate=True)
        summary(f"{mode}_vt_itl_p50", r["vt_itl_p50"], unit="unit",
                better="lower", gate=True)
        summary(f"{mode}_vt_itl_p99", r["vt_itl_p99"], unit="unit",
                better="lower", gate=True)
        summary(f"{mode}_vt_tokens_per_kunit", r["vt_tokens_per_kunit"],
                unit="tok/kunit", better="higher", gate=True)
        summary(f"{mode}_wall_seconds", r["wall_seconds"], unit="s")
        summary(f"{mode}_preemptions", r["preemptions"])
        summary(f"{mode}_mean_occupancy", r["mean_occupancy"])
        summary(f"{mode}_peak_blocks", r["peak_blocks"])
        summary(f"{mode}_prefix_block_hits", r["prefix_block_hits"])
    summary("chunked_over_mono_ttft_p99", ratio, better="lower", gate=True)
    summary("chunked_over_mono_tput", tput_ratio, better="higher", gate=True)
    summary("chunked_prefill_chunks", ch["prefill_chunks"])
    summary("chunked_prefill_aborts", ch["prefill_aborts"])
    # chaos pass: leak gate + lifecycle / degradation counters
    summary("faulted_leaked_blocks", fr["leaked_blocks"], unit="blocks",
            better="lower", gate=True)
    summary("faulted_rejected", fr["rejected"])
    summary("faulted_cancelled", fr["cancelled"])
    summary("faulted_deadline_exceeded", fr["deadline_exceeded"])
    summary("faulted_quarantined", fr["quarantined"])
    summary("faulted_budget_downshifts", fr["budget_downshifts"])
    summary("faulted_blocks_shed", fr["blocks_shed"])
    summary("faulted_insert_retries", fr["insert_retries"])
    summary("faulted_total_tokens", fr["total_tokens"])
    if sharded_res is not None:
        # shard count + per-shard occupancy ride in the bench doc (info:
        # the hard gates are the output-identity/leak asserts above, and
        # differing shard counts are distinct baselines to the checker)
        summary("sharded_devices", devices)
        summary("sharded_n_dp", n_dp)
        summary("sharded_n_tp", n_tp)
        summary("sharded_leaked_blocks", sharded_res["leaked_blocks"],
                unit="blocks", better="lower", gate=True)
        summary("sharded_vt_ttft_p99", sharded_res["vt_ttft_p99"],
                unit="unit")
        summary("sharded_mean_occupancy", sharded_res["mean_occupancy"])
        for i, st in enumerate(shard_stats):
            summary(f"sharded_shard{i}_peak_blocks", st["pool_peak_in_use"])
            summary(f"sharded_shard{i}_prefix_block_hits",
                    st["pool_prefix_block_hits"])

    snap_doc = eng.obs.metrics.write_snapshot_json(
        os.path.join(out_dir, "METRICS_serve_trace.json"))
    by_name = {(s["name"], tuple(sorted(s["labels"].items()))): s["value"]
               for s in snap_doc["series"]}
    for m in metrics:
        assert by_name[(m["name"], ())] == m["value"], m

    doc = write_bench_json(
        out_dir, "serve_trace",
        dict(seed=seed, trace="bursty", chunk_tokens=chunk_tokens,
             pipeline=pipeline, decode_token_cost=DECODE_TOKEN_COST,
             devices=devices, shard_dp=n_dp, shard_tp=n_tp,
             **SMOKE_ENGINE),
        metrics,
    )
    # the tentpole claim, asserted: strictly lower p99 TTFT at equal
    # (within 10%) virtual token throughput
    assert ch["vt_ttft_p99"] < mo["vt_ttft_p99"], (ch, mo)
    assert tput_ratio >= 0.9, (ch, mo)
    # the fault-tolerance claim: every scheduled fault fired, each left
    # its structured outcome, and the pool drained without leaking
    assert inj.all_fired, inj.fired_log
    assert fr["leaked_blocks"] == 0, fr
    assert fr["cancelled"] >= 1 and fr["quarantined"] >= 1, fr
    assert fr["deadline_exceeded"] >= 1, fr
    assert feng.allocator.n_in_use == 0
    print(f"smoke ok: ttft_p99 {ch['vt_ttft_p99']:.0f} (chunked) vs "
          f"{mo['vt_ttft_p99']:.0f} (mono), tput ratio {tput_ratio:.2f}; "
          f"faulted pass survived {len(inj.fired_log)} faults, "
          f"0 leaked blocks")
    return doc


# --prefix-mix engine shape: a pool small enough that the filler phase
# pressures the warm families out, a host tier large enough to hold every
# demoted block (host DRAM is the cheap tier), and a park TTL well under
# the filler phase's virtual-time span so expiry — not just pressure —
# moves the prefixes between tiers
PREFIX_ENGINE = dict(capacity=512, n_slots=4, pool_blocks=30, block_size=32)
PREFIX_TTL = 600.0
PREFIX_OFFLOAD_BLOCKS = 80
PREFIX_CHUNK_TOKENS = 64


def prefix_mix(out_dir: str, *, seed: int = 0,
               pipeline: str = "reference") -> dict:
    """CI gate for the two-tier KV reuse subsystem: the prefix-mix trace
    on a baseline (TTL only — expired prefix blocks destroyed) vs a
    host-offload engine (expired blocks demoted, recalled on reuse),
    sharing the same weights.  Asserts bit-identical outputs, a positive
    prefix hit-rate and recall count, and strictly fewer recomputed
    prompt tokens on the offload engine; writes BENCH_serve_prefix.json
    + METRICS_serve_prefix.json + per-variant Perfetto traces."""
    cfg, params, base = build_serving(
        pipeline, **PREFIX_ENGINE, prefix_ttl=PREFIX_TTL)
    off = Engine(
        base.bundle, n_slots=PREFIX_ENGINE["n_slots"],
        capacity=PREFIX_ENGINE["capacity"], obs=Observability(),
        prefix_ttl=PREFIX_TTL, offload_blocks=PREFIX_OFFLOAD_BLOCKS,
    )
    trace = prefix_mix_trace(seed, cfg.vocab)
    n_requests = len(trace)
    engines = {"base": base, "offload": off}
    results, outs = {}, {}
    for name, eng in engines.items():
        sched = ContinuousScheduler(
            eng, params, chunk_tokens=PREFIX_CHUNK_TOKENS)
        outs[name] = {}
        results[name] = replay(eng, sched, trace, outputs=outs[name])
        eng.obs.tracer.write_chrome_trace(
            os.path.join(out_dir, f"serve_prefix_{name}.trace.json"))
        eng.audit()  # device-pool AND host-tier invariants at drain
        print(f"-- {name}: recomputed={eng.tokens_recomputed} "
              f"hits={eng.prefix_partial_hits} "
              f"recalled={eng.blocks_recalled} "
              f"ttft_p99={results[name]['vt_ttft_p99']:.0f}")

    metrics = []
    reg = off.obs.metrics

    def summary(name, value, *, unit="", better="info", gate=False):
        g = reg.gauge(name, "serve_prefix summary metric", unit=unit,
                      better=better, gate=gate)
        g.set(float(value))
        metrics.append(metric(name, g.value(), unit=unit, better=better,
                              gate=gate))

    for name, eng in engines.items():
        r = results[name]
        summary(f"{name}_tokens_recomputed", eng.tokens_recomputed,
                unit="tok", better="lower", gate=True)
        summary(f"{name}_prefix_hit_rate",
                eng.prefix_partial_hits / n_requests,
                better="higher", gate=(name == "offload"))
        summary(f"{name}_vt_ttft_p99", r["vt_ttft_p99"], unit="unit",
                better="lower", gate=True)
        summary(f"{name}_vt_tokens_per_kunit", r["vt_tokens_per_kunit"],
                unit="tok/kunit", better="higher", gate=True)
        summary(f"{name}_total_tokens", r["total_tokens"])
        summary(f"{name}_preemptions", r["preemptions"])
        summary(f"{name}_leaked_blocks", r["leaked_blocks"], unit="blocks",
                better="lower", gate=True)
    summary("offload_blocks_recalled", off.blocks_recalled, unit="blocks",
            better="higher", gate=True)
    summary("offload_tokens_recalled", off.tokens_recalled, unit="tok")
    summary("offload_host_resident", len(off.offload), unit="blocks")
    summary("offload_over_base_recomputed",
            off.tokens_recomputed / max(base.tokens_recomputed, 1),
            better="lower", gate=True)

    snap_doc = reg.write_snapshot_json(
        os.path.join(out_dir, "METRICS_serve_prefix.json"))
    by_name = {(s["name"], tuple(sorted(s["labels"].items()))): s["value"]
               for s in snap_doc["series"]}
    for m in metrics:
        assert by_name[(m["name"], ())] == m["value"], m

    doc = write_bench_json(
        out_dir, "serve_prefix",
        dict(seed=seed, trace="prefix_mix", pipeline=pipeline,
             chunk_tokens=PREFIX_CHUNK_TOKENS, prefix_ttl=PREFIX_TTL,
             offload_blocks=PREFIX_OFFLOAD_BLOCKS,
             decode_token_cost=DECODE_TOKEN_COST, **PREFIX_ENGINE),
        metrics,
    )
    # the subsystem's claim, asserted: the host tier changes WHAT is
    # recomputed, never what is generated
    assert outs["offload"] == outs["base"], "offload changed outputs"
    assert off.prefix_partial_hits > 0, "no prefix hits on the mix trace"
    assert off.blocks_recalled > 0, "host tier never recalled a block"
    assert off.tokens_recomputed < base.tokens_recomputed, (
        off.tokens_recomputed, base.tokens_recomputed)
    assert results["base"]["leaked_blocks"] == 0
    assert results["offload"]["leaked_blocks"] == 0
    print(f"prefix-mix ok: recomputed {off.tokens_recomputed} (offload) vs "
          f"{base.tokens_recomputed} (base), "
          f"{off.blocks_recalled} blocks recalled, "
          f"hit-rate {off.prefix_partial_hits / n_requests:.2f}, "
          f"identical outputs")
    return doc


def full(*, seed: int = 0, chunk_tokens: int = 256,
         pipeline: str = "reference", n_requests: int = 48):
    """Exploratory sweep (not persisted): Poisson arrivals at a few
    rates, chunked vs monolithic side by side."""
    cfg, params, eng = build_serving(pipeline, **SMOKE_ENGINE)
    for mean_gap in (120.0, 60.0, 30.0):
        trace = poisson_trace(
            seed, cfg.vocab, n_requests=n_requests, mean_gap=mean_gap
        )
        for mode, ct in (("chunked", chunk_tokens), ("mono", None)):
            sched = ContinuousScheduler(eng, params, chunk_tokens=ct)
            r = replay(eng, sched, trace)
            print(
                f"gap={mean_gap:5.0f} {mode:7s} "
                f"ttft_p99={r['vt_ttft_p99']:8.1f} "
                f"itl_p99={r['vt_itl_p99']:7.1f} "
                f"tput={r['vt_tokens_per_kunit']:7.1f} "
                f"preempt={r['preemptions']:3d} occ={r['mean_occupancy']:.2f}"
            )


def main():
    enable_compilation_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: bursty trace, chunked vs monolithic, "
                         "writes BENCH_serve_trace.json")
    ap.add_argument("--prefix-mix", action="store_true",
                    help="CI gate: prefix-heavy trace, baseline vs "
                         "host-offload engine, writes "
                         "BENCH_serve_prefix.json")
    ap.add_argument("--out", default=".",
                    help="directory (or file) for BENCH_serve_trace.json")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chunk-tokens", type=int, default=256)
    ap.add_argument("--pipeline", default="reference",
                    choices=("reference", "one_pass"))
    ap.add_argument("--n-requests", type=int, default=48)
    ap.add_argument("--devices", type=int, default=1,
                    help="mesh size for the sharded smoke pass (1 = "
                         "single-device, 2 = DP, 4 = DP×TP; needs "
                         "XLA_FLAGS=--xla_force_host_platform_device_count "
                         "or real devices)")
    args = ap.parse_args()
    if args.devices > 1 and jax.device_count() < args.devices:
        raise SystemExit(
            f"--devices {args.devices} needs >= {args.devices} jax devices, "
            f"found {jax.device_count()} (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={args.devices})")
    if args.smoke or args.prefix_mix:
        os.makedirs(args.out, exist_ok=True)
        if args.smoke:
            smoke(args.out, seed=args.seed, chunk_tokens=args.chunk_tokens,
                  pipeline=args.pipeline, devices=args.devices)
        if args.prefix_mix:
            prefix_mix(args.out, seed=args.seed, pipeline=args.pipeline)
    else:
        full(seed=args.seed, chunk_tokens=args.chunk_tokens,
             pipeline=args.pipeline, n_requests=args.n_requests)


if __name__ == "__main__":
    main()
