#!/usr/bin/env python3
"""Smoke run of the served FIER path on a TPU, at olmo-1b's full width.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # DP=2 x TP=2 sharded serving vs one chip

One chip: six seeded prompts of 2k-6k tokens are served over four slots
(admission, retirement and slot reuse all happen) through the normal entry
points: ``Engine.build(layout="paged")`` with the ``one_pass`` FIER
pipeline, behind a ``ContinuousScheduler`` with chunked prefill.  The
one_pass engine is then checked against the jnp ``reference`` pipeline on
the same requests: the logits of the first decode step, and the overlap of
the one-pass kernel's selected index set with the oracle's top-k.

``--chips 4``: the same requests, first on ``devices[0]`` alone, then
(that engine freed) on a DP=2 x TP=2 mesh over all four devices.  The
greedy tokens must be equal and every allocator audit clean.

Weights are random, made from ``--seed``; the tokens/s printed are a smoke
figure, not a benchmark.  Any failed phase exits non-zero, and without a
TPU the script exits 1 before doing any work.  The last line of stdout is
one JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

ARCH = "olmo-1b"
SLOTS = 4
CAPACITY = 8192
BLOCK_SIZE = 32
BUDGET = 1024            # 12.5% of capacity, near the paper's ~11%
CHUNK_TOKENS = 512
MAX_NEW = 32
PROMPT_LENS = (2048, 5200, 3000, 6144, 2600, 4096)

# Tolerances of the one_pass-vs-reference checks.
#
# Index overlap: the kernel and the jnp oracle evaluate one score
# expression (bf16 key levels z ± s, a bf16 dot with the query, f32
# accumulation), so only the f32 accumulation order differs — MXU inside
# the kernel, XLA's dot in the oracle — and only tokens whose scores lie
# within a few f32 ulps of the budget-th score can trade places.  A wrong
# block, head or position mapping lands near budget / length (~0.2-0.5).
OVERLAP_MEAN_MIN = 0.99
OVERLAP_MIN = 0.95
# First-step logits: both pipelines attend exactly over (almost) the same
# selected tokens, but the attend kernel's online softmax and the oracle's
# dense softmax round differently, and the difference passes through 16
# layers of bf16 activations (8-bit mantissa, ~0.4% per rounding).  The
# bound is on max |Δlogit| relative to the largest |logit| of the row.
LOGIT_REL_TOL = 0.05

FIER_KERNELS = ("paged_fused_retrieve_hm", "paged_fused_sparse_attention_hm")


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Sums XLA backend-compile seconds (JAX's own monitoring event; a
    persistent-cache hit compiles nothing and adds nothing)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration


def prompts_for(cfg, seed: int, lens=PROMPT_LENS) -> list[list[int]]:
    from repro.data.pipeline import lm_tokens

    toks = np.asarray(lm_tokens(seed, 0, len(lens), max(lens), cfg.vocab))
    return [toks[i, :n].tolist() for i, n in enumerate(lens)]


def build_engine(cfg, pipeline: str, *, capacity=CAPACITY, budget=BUDGET,
                 mesh=None):
    from repro.serving import Engine
    from repro.serving.engine import serving_policy

    return Engine.build(
        cfg, n_slots=SLOTS, capacity=capacity,
        policy=serving_policy(budget=budget, pipeline=pipeline),
        layout="paged", block_size=BLOCK_SIZE, mesh=mesh,
    )


def probe(eng, params, prompts, chunk_tokens=CHUNK_TOKENS):
    """Chunk-prefill the first ``n_slots`` prompts into the slots, then run
    one batched decode step.  Returns (prefill tokens, first-step logits
    [n_slots, vocab], first-step tokens, cache)."""
    import jax.numpy as jnp

    n = eng.n_slots
    cache = eng.new_cache()
    first = np.zeros((n,), np.int32)
    for slot, toks in enumerate(prompts[:n]):
        toks = np.asarray(toks, np.int32)
        pos, cache = eng.begin_chunked(cache, slot, toks)
        logits = None
        while pos < len(toks):
            step = min(chunk_tokens, len(toks) - pos)
            ok, logits, cache = eng.prefill_chunk(
                params, cache, slot, toks, pos, step
            )
            check(ok, f"pool ran dry prefilling slot {slot}")
            pos += step
        first[slot] = int(np.argmax(np.asarray(logits)[0]))
    for slot in range(n):
        ok, cache = eng.advance_slot(cache, slot)
        check(ok, f"no tail block for slot {slot}")
    nxt, logits, cache = eng.decode(params, jnp.asarray(first), cache)
    vocab = eng.bundle.cfg.vocab
    return first, np.asarray(logits)[:, :vocab], np.asarray(nxt), cache


def release(eng, cache):
    for slot in range(eng.n_slots):
        cache = eng.release_slot(cache, slot)
    eng.audit()
    return cache


def selection_overlap(eng, cache) -> np.ndarray:
    """Per rest layer, slot and kv head: |kernel ∩ oracle| / budget, where
    the kernel is the one-pass retrieval (``ops.retrieve``) and the oracle
    the jnp top-k over the same side-car (``ref.retrieve``).  The probe
    query is each slot's newest resident key of that layer."""
    import jax
    import jax.numpy as jnp

    from repro.core.policy import CacheView
    from repro.kernels import ops, ref

    pol = eng.bundle.policy
    cfg = eng.bundle.cfg
    bs = eng.block_size
    rep = cfg.n_heads // cfg.n_kv_heads
    sel = dict(group_reduce=pol.group_reduce, sink=pol.sink, recent=pol.recent)

    @jax.jit
    def layer_hits(k_stack, meta_stack, table, length, layer):
        pick = lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False)
        k_pool = pick(k_stack)
        meta = jax.tree.map(pick, meta_stack)
        last = length - 1
        phys = jnp.take_along_axis(table, (last // bs)[:, None], axis=1)[:, 0]
        q = jnp.repeat(k_pool[phys, last % bs], rep, axis=1)   # [B, Hq, D]
        view = CacheView.paged(k_pool, None, meta, table, length)
        got = ops.retrieve(q, view, pol.budget, **sel)
        want = ref.retrieve(q, view, pol.budget, **sel)
        return (got[..., :, None] == want[..., None, :]).any(-1).sum(-1)

    rest = cache["rest"]
    hits = [
        np.asarray(layer_hits(rest["k"], rest["meta"], cache["block_table"],
                              cache["length"], jnp.int32(layer)))
        for layer in range(rest["k"].shape[0])
    ]
    return np.stack(hits) / pol.budget                 # [L_rest, B, Hkv]


def fier_kernels_in_decode(eng, params, cache) -> dict[str, bool]:
    """Which FIER kernels the compiled decode step runs as TPU custom calls."""
    import jax
    import jax.numpy as jnp

    tok = jnp.zeros((eng.n_slots,), jnp.int32)
    txt = jax.jit(eng.bundle.decode_step).lower(params, tok, cache).compile().as_text()
    calls = [ln for ln in txt.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    return {k: any(f"jit({k})" in ln for ln in calls) for k in FIER_KERNELS}


def serve(eng, params, prompts, clock, *, chunk_tokens=CHUNK_TOKENS,
          max_new=MAX_NEW):
    """Serve every prompt through the ContinuousScheduler; returns
    (tokens per request, outcome statuses, wall s, compile s within,
    decode steps)."""
    from repro.serving import ContinuousScheduler, Request

    reqs = [Request(rid=i, tokens=list(p), max_new=max_new)
            for i, p in enumerate(prompts)]
    sched = ContinuousScheduler(eng, params, chunk_tokens=chunk_tokens)
    c0, t0 = clock.seconds, time.perf_counter()
    out = sched.run(reqs)
    wall = time.perf_counter() - t0
    status = {rid: oc.status for rid, oc in out.outcomes.items()}
    steps = sched.steps
    eng.audit()
    # the scheduler holds the session's KV pool: free it now, since two
    # full-width pools and the params do not fit one chip
    del sched, out
    gc.collect()
    return ({r.rid: list(r.out) for r in reqs}, status, wall,
            clock.seconds - c0, steps)


def peak_gb(jax) -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.3f}"


def device_line(jax) -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


# ---------------------------------------------------------------- one chip

def run_one_chip(cfg, seed: int, clock, *, capacity=CAPACITY, budget=BUDGET,
                 lens=PROMPT_LENS, chunk_tokens=CHUNK_TOKENS,
                 max_new=MAX_NEW) -> None:
    import jax

    prompts = prompts_for(cfg, seed, lens)
    t_setup = time.perf_counter()
    eng = build_engine(cfg, "one_pass", capacity=capacity, budget=budget)
    params = eng.bundle.init(jax.random.PRNGKey(seed))

    first, logits_fast, nxt_fast, cache = probe(eng, params, prompts,
                                                chunk_tokens)
    kernels = fier_kernels_in_decode(eng, params, cache)
    print(f"decode step tpu_custom_call: {kernels}")
    check(all(kernels.values()), f"FIER kernels missing from decode: {kernels}")
    overlap = selection_overlap(eng, cache)
    cache = release(eng, cache)
    del cache
    print(f"setup s (first prefill + decode, incl. compile): "
          f"{time.perf_counter() - t_setup:.3f}")
    print(f"compile s so far: {clock.seconds:.3f}")
    print(f"index overlap one_pass vs oracle top-{budget}: mean "
          f"{overlap.mean():.6f} min {overlap.min():.6f} "
          f"(limits {OVERLAP_MEAN_MIN} / {OVERLAP_MIN})")
    check(overlap.mean() >= OVERLAP_MEAN_MIN and overlap.min() >= OVERLAP_MIN,
          "one-pass index set strays from the oracle's top-k")

    toks, status, wall, compile_in, steps = serve(
        eng, params, prompts, clock, chunk_tokens=chunk_tokens,
        max_new=max_new,
    )
    n_tok = sum(len(t) for t in toks.values())
    finished = sum(s == "finished" for s in status.values())
    print(f"requests finished: {finished}/{len(prompts)}  "
          f"tokens generated: {n_tok}")
    print(f"serve wall s: {wall:.3f} (compile within: {compile_in:.3f}) "
          f"over {steps} decode steps; smoke tokens/s (info, not a "
          f"benchmark): {n_tok / max(wall - compile_in, 1e-9):.3f}")
    print(f"pool_stats: {json.dumps(eng.pool_stats(), sort_keys=True)}")
    print("audit: clean")
    check(finished == len(prompts), f"unfinished requests: {status}")
    check(all(len(t) == max_new for t in toks.values()),
          "a request stopped short of max_new")
    print(f"peak device GB (devices[0]): {peak_gb(jax)}")
    del eng
    gc.collect()

    ref_eng = build_engine(cfg, "reference", capacity=capacity, budget=budget)
    first_ref, logits_ref, nxt_ref, cache = probe(ref_eng, params, prompts,
                                                  chunk_tokens)
    release(ref_eng, cache)
    del cache, ref_eng
    gc.collect()
    check(np.array_equal(first, first_ref), "prefill tokens differ")
    rel = (np.abs(logits_fast - logits_ref).max(axis=1)
           / np.abs(logits_ref).max(axis=1))
    print(f"first decode step logits one_pass vs reference: max rel diff "
          f"{rel.max():.6g} (limit {LOGIT_REL_TOL}); tokens equal "
          f"{int((nxt_fast == nxt_ref).sum())}/{len(nxt_ref)}")
    check(rel.max() <= LOGIT_REL_TOL, "one_pass logits stray from reference")
    print(f"compile s total (set-up): {clock.seconds:.3f}")


# -------------------------------------------------------------- four chips

def run_four_chips(cfg, seed: int, clock, *, capacity=CAPACITY,
                   budget=BUDGET, lens=PROMPT_LENS,
                   chunk_tokens=CHUNK_TOKENS, max_new=MAX_NEW) -> None:
    import jax

    from repro.launch.mesh import make_mesh

    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, found {len(devices)}")
    prompts = prompts_for(cfg, seed, lens)

    eng = build_engine(cfg, "one_pass", capacity=capacity, budget=budget)
    params = eng.bundle.init(jax.random.PRNGKey(seed))
    _, logits_one, _, cache = probe(eng, params, prompts, chunk_tokens)
    release(eng, cache)
    del cache
    toks_one, status_one, wall_one, comp_one, _ = serve(
        eng, params, prompts, clock, chunk_tokens=chunk_tokens,
        max_new=max_new,
    )
    print(f"devices[0] alone: finished "
          f"{sum(s == 'finished' for s in status_one.values())}/{len(prompts)}"
          f", serve wall s {wall_one:.3f} (compile within {comp_one:.3f})")
    del eng
    gc.collect()

    mesh = make_mesh((2, 2), ("data", "model"), devices=devices[:4])
    eng = build_engine(cfg, "one_pass", capacity=capacity, budget=budget,
                       mesh=mesh)
    params = eng.place_params(params)
    _, logits_mesh, _, cache = probe(eng, params, prompts, chunk_tokens)
    pool_devs = {d for leaf in jax.tree.leaves(cache["rest"])
                 for d in leaf.sharding.device_set}
    param_devs = {d for leaf in jax.tree.leaves(params)
                  for d in leaf.sharding.device_set}
    print(f"pool on {len(pool_devs)} devices, params on {len(param_devs)}")
    check(pool_devs == set(devices[:4]) and param_devs == set(devices[:4]),
          "pool or params not on all four devices")
    release(eng, cache)
    del cache
    toks_mesh, status_mesh, wall_mesh, comp_mesh, _ = serve(
        eng, params, prompts, clock, chunk_tokens=chunk_tokens,
        max_new=max_new,
    )
    print(f"DP=2 x TP=2: finished "
          f"{sum(s == 'finished' for s in status_mesh.values())}/"
          f"{len(prompts)}, serve wall s {wall_mesh:.3f} "
          f"(compile within {comp_mesh:.3f})")
    print(f"first decode step max |logit diff| mesh vs devices[0]: "
          f"{np.abs(logits_mesh - logits_one).max():.6g}")
    print(f"audits clean (devices[0], per-shard mesh); pool_stats: "
          f"{json.dumps(eng.pool_stats(), sort_keys=True)}")
    check(all(s == "finished" for s in status_one.values())
          and all(s == "finished" for s in status_mesh.values()),
          "unfinished requests")
    agree = {rid: next((i for i, (a, b) in enumerate(zip(t, toks_mesh[rid]))
                        if a != b), len(t))
             for rid, t in toks_one.items()}
    print(f"leading tokens equal per request (of {max_new}): {agree}")
    check(toks_mesh == toks_one, "DP=2 x TP=2 tokens differ from devices[0]")
    print("greedy tokens equal: mesh == devices[0]")
    print(f"compile s total (set-up): {clock.seconds:.3f}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: serve + check on one chip; 4: the sharded path "
                         "(DP=2 x TP=2) against devices[0] alone, only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: the repro package is not at {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax

    dev = device_line(jax)
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev['platform']!r}); "
              f"this smoke run measures nothing off the chip", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compilation_cache

    print(f"compile cache: {enable_compilation_cache()}")
    cfg = get_config(ARCH)
    print(f"device: {dev['kind']} x{dev['count']} ({dev['platform']})")
    print(f"config {cfg.name}: layers {cfg.n_layers} d_model {cfg.d_model} "
          f"heads {cfg.n_heads} kv_heads {cfg.n_kv_heads} d_head "
          f"{cfg.d_head} d_ff {cfg.d_ff} vocab {cfg.vocab}")
    print(f"serving: slots {SLOTS} capacity {CAPACITY} block {BLOCK_SIZE} "
          f"budget {BUDGET} chunk {CHUNK_TOKENS} max_new {MAX_NEW} prompts "
          f"{list(PROMPT_LENS)}")
    clock = CompileClock()
    try:
        if args.chips == 4:
            check(dev["count"] >= 4, f"--chips 4 on {dev['count']} device(s)")
            run_four_chips(cfg, args.seed, clock)
        else:
            run_one_chip(cfg, args.seed, clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
