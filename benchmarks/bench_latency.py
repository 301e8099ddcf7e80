"""Paper Fig. 8: decode latency vs context length, Full-KV vs FIER
(unfused, fused two-pass, and one-pass fused retrieval).

Four measurements:
  1. CPU wall-clock of the jitted decode step at growing cache lengths —
    the *trend* (FIER flattens, full grows linearly) is hardware-agnostic;
    the fused paths additionally run in Pallas interpret mode on CPU, so
    their wall-clock is a correctness smoke, not a perf number;
  2. materialised gather bytes per decode step, counted from the jaxpr
     (scan-aware, all layers): the unfused path writes+reads budget-sized
     K'/V' copies every layer every step; the fused paths must show the
     cache-slab gathers *gone* — measured, not asserted;
  3. materialised score-tensor bytes per decode step
     (``count_score_bytes``): the unfused/two-pass paths round-trip the
     f32 [B, Hq, S] (and [B, Hkv, S]) approximate-score tensors through
     HBM between scoring and selection (≥ 2·4·Hq·S bytes/layer/step);
     the one-pass retrieval kernel must measure **zero** — the property
     the ``--smoke`` CI gate asserts;
  4. the analytic v5e bytes model (decode is HBM-bound): step time ≈
     bytes_touched / 819 GB/s using the exact cache/metadata byte counts —
     the paper's 1.2–1.5× claim mapped onto TPU, and the fused-vs-unfused
     delta (no 2·budget·D bf16 copies per kv head per layer per step).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantize import packed_nbytes
from repro.launch.compile_cache import enable_compilation_cache

from .common import (
    bench_model_cfg, emit, emit_paged_score_traffic, emit_score_traffic,
    policy_bundle, timeit, train_tiny_lm,
)
from .flopcount import count_fn_gather_bytes
from .persist import metric, write_bench_json

HBM_BW = 819e9


def analytic_v5e_speedup(S: int, cfg, budget: int, g: int = 32) -> float:
    """bytes(full)/bytes(fier) per layer at context S (B=1)."""
    Hkv, D = cfg.n_kv_heads, cfg.d_head
    full = 2 * S * Hkv * D * 2
    fier = packed_nbytes(S, Hkv, D, g) + 2 * budget * Hkv * D * 2
    return full / fier


def gather_copy_bytes(cfg, budget: int, B: int, n_sparse: int) -> int:
    """Analytic bytes of the materialised K'/V' gather per decode step:
    2 slabs · budget rows · Hkv · D · bf16, per sparse layer."""
    return 2 * budget * cfg.n_kv_heads * cfg.d_head * 2 * B * n_sparse


def _fier_slab_pipelines():
    """The registered (slab) fier pipelines, straight off the backend's
    capability matrix — new pipelines benchmark without editing this file."""
    from repro.core.policy import get_backend

    return sorted(p for lo, p in get_backend("fier").supports if lo == "slab")


def run():
    cfg, params = train_tiny_lm("lm")
    params = jax.tree.map(jnp.asarray, params)
    B = 4
    budget = 64
    variants = [("full", dict(kind="full"))] + [
        (f"fier_{p}", dict(kind="fier", pipeline=p))
        for p in _fier_slab_pipelines()
    ]
    for S in (512, 1024, 2048):
        tok = jnp.zeros((B,), jnp.int32)
        gbytes = {}
        for name, kw in variants:
            bundle = policy_bundle(cfg, budget=budget, skip=1, **kw)
            cache = bundle.init_cache(B, S, S - 2)
            step = jax.jit(bundle.decode_step)
            us = timeit(step, params, tok, cache, reps=5)
            if name != "full":  # gather accounting only compares fier paths
                gbytes[name] = count_fn_gather_bytes(
                    bundle.decode_step, params, tok, cache
                )
            emit(f"decode_latency_{name}_ctx{S}", us, f"B={B}")
        # the fused paths must eliminate the budget-sized K'/V' copies:
        # unfused − fused == the analytic gather bytes (embedding-lookup
        # gathers etc. are common to both and cancel)
        copies = gather_copy_bytes(cfg, budget, B, cfg.n_layers - 1)
        eliminated = gbytes["fier_reference"] - gbytes["fier_one_pass"]
        emit(
            f"decode_gather_bytes_ctx{S}", 0.0,
            " ".join(f"{n}={v:.0f}" for n, v in sorted(gbytes.items()))
            + f" eliminated={eliminated:.0f} analytic_kv_copies={copies}",
        )
        # the one-pass retrieval kernel must additionally eliminate the
        # f32 score-tensor round trip between scoring and selection
        emit_score_traffic(cfg.n_heads, cfg.n_kv_heads, cfg.d_head,
                           budget=budget, B=B, S=S)
        emit(
            f"decode_latency_v5e_model_ctx{S}", 0.0,
            f"analytic_fullKV_over_FIER={analytic_v5e_speedup(S, cfg, budget):.2f}x",
        )
    # the paper's setting: 32k context, 4k budget, 7B-class GQA dims
    from repro.configs import get_config

    big = get_config("llava-next-mistral-7b")  # mistral-7b backbone
    for S in (8192, 16384, 32768):
        emit(
            f"decode_latency_v5e_model_7b_ctx{S}", 0.0,
            f"analytic_fullKV_over_FIER={analytic_v5e_speedup(S, big, 4096):.2f}x",
        )


def smoke(out_dir: str = "."):
    """Fast CI gate (`--smoke`): assert the one-pass retrieval path
    materialises zero score-tensor bytes (and the two-pass path pays the
    full ≥ 2·4·Hq·S round trip) at a tiny config — the perf property is
    *gated*, not just benchmarked.  No model training involved.

    The gate iterates the backend registry's capability matrix instead
    of hard-coding variant names: every layout the fier backend registers
    a ``one_pass`` pipeline for is asserted zero-score-byte, so a new
    layout cannot land without passing (or explicitly skipping) the gate."""
    from repro.core.policy import get_backend

    cfg = bench_model_cfg()
    parts = []
    metrics = []
    one_pass_layouts = sorted(
        lo for lo, p in get_backend("fier").supports if p == "one_pass"
    )
    assert one_pass_layouts, "fier registers no one_pass pipeline?"
    for layout in one_pass_layouts:
        if layout == "slab":
            sb = emit_score_traffic(cfg.n_heads, cfg.n_kv_heads, cfg.d_head,
                                    budget=32, B=1, S=256, check=True)
            parts.append(
                " ".join(f"slab_{p}={sb[p]:.0f}" for p in sorted(sb))
            )
            for p, v in sorted(sb.items()):
                # the fused one-pass path is the gated zero; the unfused
                # paths' round-trip bytes are recorded for the trajectory
                metrics.append(metric(
                    f"slab_{p}_score_bytes", v, unit="B",
                    better="lower", gate=(p == "one_pass"),
                ))
        elif layout == "paged":
            psb = emit_paged_score_traffic(
                cfg.n_heads, cfg.n_kv_heads, cfg.d_head,
                budget=32, B=1, S=256, block_size=32, check=True,
            )
            parts.append(f"paged_onepass={psb:.0f}")
            metrics.append(metric(
                "paged_one_pass_score_bytes", psb, unit="B",
                better="lower", gate=True,
            ))
        else:
            raise AssertionError(
                f"fier registers one_pass for unknown layout {layout!r}: "
                f"extend the smoke gate"
            )
    emit("bench_smoke_ok", 0.0, " ".join(parts))
    write_bench_json(
        out_dir, "latency",
        dict(budget=32, B=1, S=256, block_size=32,
             one_pass_layouts=one_pass_layouts),
        metrics,
    )


def main():
    enable_compilation_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: assert zero score-tensor bytes on the "
                         "one-pass paths; writes BENCH_latency.json")
    ap.add_argument("--out", default=".",
                    help="directory (or file) for BENCH_latency.json")
    args = ap.parse_args()
    if args.smoke:
        import os

        os.makedirs(args.out, exist_ok=True)
        smoke(args.out)
    else:
        run()


if __name__ == "__main__":
    main()
