"""Roofline analysis driver (§Roofline of EXPERIMENTS.md).

Per (arch × shape) cell on the single-pod 16×16 mesh, derives the three
roofline terms for TPU v5e:

    compute term    = FLOPs_per_chip   / 197e12        (bf16 peak)
    memory term     = HBM_bytes_per_chip / 819e9
    collective term = wire_bytes_per_chip / 50e9        (per-link ICI)

Sources (methodology in EXPERIMENTS.md §Roofline — XLA's cost_analysis
counts loop bodies once, so three measurements combine):
  * FLOPs: scan-aware jaxpr counter (benchmarks/flopcount.py), exact.
  * HBM bytes + collective wire bytes: two depth-extrapolation compiles
    (depth 1 and 2, layer scan unrolled, microbatches=1) →
    total = c1 + (L−1)·(c2 − c1); fusion-aware because they come from the
    partitioned, optimised HLO.
  * memory fit: the full-depth scanned compile (results/dryrun_1pod.jsonl).

Usage:
    PYTHONPATH=src python -m benchmarks.roofline --out results/roofline.jsonl
    PYTHONPATH=src python -m benchmarks.roofline --arch olmo-1b --shape decode_32k
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

PEAK_FLOPS = 197e12      # bf16 / chip
HBM_BW = 819e9           # bytes/s / chip
LINK_BW = 50e9           # bytes/s / link (ICI)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_dryrun(args: list[str], timeout: int = 3600) -> dict:
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--json"] + args
    # a CPU tool: the children compile against 512 host placeholder
    # devices and must never take an accelerator the host has
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=REPO)
    if r.returncode != 0:
        raise RuntimeError(f"dryrun {' '.join(args)} failed:\n{r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def _depths_for(arch: str, kind: str) -> dict:
    from repro.configs import get_config

    cfg = get_config(arch)
    if cfg.family == "hybrid":
        # depth counts superblocks; real model ≈ 13.5 superblocks (81 layers
        # / attn_every=6, the 3-layer tail ≈ half a superblock — documented)
        return {"unit_layers": cfg.n_layers / cfg.attn_every}
    if cfg.family == "encdec" and kind != "decode":
        return {"unit_layers": cfg.n_layers, "enc_layers": cfg.n_enc_layers}
    return {"unit_layers": cfg.n_layers}


def ideal_bytes_per_chip(arch: str, shape_name: str, policy: str,
                         budget: int, devices: int = 256) -> float:
    """Analytic lower bound on HBM bytes per chip for one step — what a
    perfect implementation must still move.

    decode: params/devices + per-layer FIER metadata scan (Eq. 8 load
    ratio) + top-k K'/V' gather + front-layer full K/V + cache append.
    prefill: params + one read/write of activations + KV-cache write.
    train: 3 param passes (fwd read, bwd read, grad write) + opt state RW
    + remat activation traffic (2 reads/write per layer boundary).
    """
    from repro.configs import SHAPES, get_config
    from repro.core.quantize import packed_nbytes

    cfg = get_config(arch)
    sh = SHAPES[shape_name]
    B, S = sh.global_batch, sh.seq_len
    pbytes = cfg.param_count() * (2 if cfg.param_dtype == "bfloat16" else 4)
    if sh.kind == "decode":
        per_chip = pbytes / devices
        if cfg.family == "ssm":
            # recurrent state read+write
            st = cfg.n_layers * B * cfg.n_ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4
            return per_chip + 2 * st / devices
        Hkv, D = cfg.n_kv_heads, cfg.d_head
        if cfg.family == "hybrid":
            n_attn = cfg.n_layers // cfg.attn_every
            st = cfg.n_layers * B * cfg.n_ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4
            per_chip += 2 * st / devices
        else:
            n_attn = cfg.n_layers
        skip = 0 if policy == "full" else 2
        rest = max(n_attn - skip, 0)
        if policy == "fier":
            scan = packed_nbytes(S, Hkv, D, 32)          # Eq. 8 bytes
            gather = 2 * budget * Hkv * D * 2            # K' + V' bf16
            per_layer = scan + gather
        else:                                            # full baseline
            per_layer = 2 * S * Hkv * D * 2
        full_layer = 2 * S * Hkv * D * 2
        total = B * (rest * per_layer + skip * full_layer)
        return per_chip + total / devices
    # train / prefill: parameter passes + boundary activations + cache write
    act = cfg.n_layers * B * S * cfg.d_model * 2
    passes = 3 if sh.kind == "train" else 1
    opt = 2 * pbytes * 2 if sh.kind == "train" else 0  # fp32 moments RW ≈ 4×bf16
    kvw = (
        0 if cfg.family == "ssm" or sh.kind == "train"
        else 2 * cfg.n_layers * B * S * cfg.n_kv_heads * cfg.d_head * 2
    )
    return (passes * pbytes + opt) / devices + (3 * act + kvw) / devices


def analyse_cell(arch: str, shape: str, *, policy: str = "fier",
                 budget: int = 4096, full_record: dict | None = None,
                 dist_mode: str = "local") -> dict:
    base = ["--arch", arch, "--shape", shape, "--policy", policy,
            "--budget", str(budget), "--dist-mode", dist_mode]
    flops_rec = _run_dryrun(base + ["--flops-only"])
    kind = flops_rec["kind"]
    dd = _depths_for(arch, kind)
    L = dd["unit_layers"]

    c1 = _run_dryrun(base + ["--cost-depth", "1"])
    c2 = _run_dryrun(base + ["--cost-depth", "2"])
    recs = {"c1": c1, "c2": c2}
    if "enc_layers" in dd:
        c21 = _run_dryrun(base + ["--cost-depth", "2", "--cost-depth-enc", "1"])
        recs["c21"] = c21

    def extrap(key, sub=None):
        def get(r):
            return r[key] if sub is None else r[key][sub]

        if "enc_layers" in dd:
            per_dec = get(recs["c2"]) - get(recs["c21"])
            per_enc = get(recs["c21"]) - get(recs["c1"])
            return (get(recs["c1"]) + (L - 1) * per_dec
                    + (dd["enc_layers"] - 1) * per_enc)
        per_layer = get(recs["c2"]) - get(recs["c1"])
        return get(recs["c1"]) + (L - 1) * per_layer

    bytes_pc = max(extrap("bytes_accessed"), 0.0)
    coll_pc = max(extrap("collectives", "total"), 0.0)
    # microbatch scaling: the cost compiles run microbatches=1 over the full
    # global batch, which already equals one optimizer step's work — no scale
    flops_pc = flops_rec["jaxpr_flops_per_device"]

    t_comp = flops_pc / PEAK_FLOPS
    t_mem = bytes_pc / HBM_BW
    t_coll = coll_pc / LINK_BW
    dominant = max(
        (("compute", t_comp), ("memory", t_mem), ("collective", t_coll)),
        key=lambda kv: kv[1],
    )[0]
    model_pc = flops_rec["model_flops_per_device"]
    out = {
        "arch": arch, "shape": shape, "kind": kind, "policy": policy,
        "budget": budget, "dist_mode": dist_mode,
        "flops_per_chip": flops_pc,
        "hbm_bytes_per_chip": bytes_pc,
        "collective_bytes_per_chip": coll_pc,
        "t_compute_s": t_comp, "t_memory_s": t_mem, "t_collective_s": t_coll,
        "dominant": dominant,
        "bound_step_s": max(t_comp, t_mem, t_coll),
        "model_flops_per_chip": model_pc,
        "useful_flops_ratio": model_pc / flops_pc if flops_pc else 0.0,
        "roofline_fraction": (
            model_pc / PEAK_FLOPS / max(t_comp, t_mem, t_coll)
            if max(t_comp, t_mem, t_coll) > 0 else 0.0
        ),
        "collective_detail": {
            k: extrap("collectives", k)
            for k in ("all-reduce", "all-gather", "reduce-scatter",
                      "all-to-all", "collective-permute")
        },
    }
    if full_record:
        out["memory_fit"] = {
            "args_gb": full_record["argument_size_in_bytes"] / 1e9,
            "temp_gb": full_record["temp_size_in_bytes"] / 1e9,
            "fits_16gb": (full_record["argument_size_in_bytes"]
                          + full_record["temp_size_in_bytes"]) < 16e9,
        }
    return out


def main() -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"   # this process stays off the chip too
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--policy", default="fier")
    ap.add_argument("--budget", type=int, default=4096)
    ap.add_argument("--dist-mode", default="local")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--full-records", default="results/dryrun_1pod.jsonl")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    full = {}
    if os.path.exists(args.full_records):
        for line in open(args.full_records):
            r = json.loads(line)
            if not r.get("multi_pod"):
                full[(r["arch"], r["shape"])] = r

    cells = []
    if args.all:
        from repro.configs import ARCHS, shape_cells

        for arch in ARCHS:
            for shape in shape_cells(arch):
                cells.append((arch, shape))
    else:
        cells = [(args.arch, args.shape)]

    sink = open(args.out, "a") if args.out else None
    failures = []
    for arch, shape in cells:
        try:
            rec = analyse_cell(arch, shape, policy=args.policy,
                               budget=args.budget, dist_mode=args.dist_mode,
                               full_record=full.get((arch, shape)))
            print(f"{arch:26s} {shape:12s} [{rec['kind']:7s}] "
                  f"comp={rec['t_compute_s']*1e3:8.3f}ms "
                  f"mem={rec['t_memory_s']*1e3:8.3f}ms "
                  f"coll={rec['t_collective_s']*1e3:8.3f}ms "
                  f"dom={rec['dominant']:10s} "
                  f"roofline={rec['roofline_fraction']*100:5.1f}%")
            if sink:
                sink.write(json.dumps(rec) + "\n")
                sink.flush()
        except Exception as e:
            print(f"FAIL {arch} × {shape}: {e}")
            failures.append((arch, shape))
    if sink:
        sink.close()
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
